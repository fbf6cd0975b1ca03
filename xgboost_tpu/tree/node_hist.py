"""One node's histogram and one node's partition, for the leaf-wise grower.

A loss-guide tree (``grow_policy='lossguide'``) splits one leaf a step, so a
step's work has to follow the split node's rows, not the matrix's. The chip
path (``grow_lossguide.grow_tree_lossguide_rows``) keeps the rows' data in a
LEAF-GROUPED ARENA (``build_arena``): a channel-major int32 array
``[2, C, L]`` whose channels are a row's bins packed three to a word
(``pack_bins``), the bits of its g and h, its row id, and zeros to whole
sublanes (C = 16 at 28 columns), with the rows on the lanes. Every open leaf
owns a contiguous column range ``[start, start + count)`` of one of the two
halves (a bit a leaf says which), built once a tree in row order in half 0.
A step

1. partitions the popped leaf's range by its split (``partition_node``):
   a stable split of the rows' data, the rows that go left, then the rows
   that go right, written into the OTHER half at the same range, so both
   children own contiguous ranges there. Two Mosaic kernels move the rows
   themselves, not their ids, each streaming the range in tiles and reading
   a row's bin from the tile's own packed word: ``_node_count_pallas``
   counts the left rows, exactly, in int32, over the split column's sublane
   group; ``_node_move_pallas`` compacts every tile's rows (left first,
   right next, each in order) by a one-hot permutation on the MXU (the
   words split into bytes, exact in bf16, one nonzero product an output
   lane), appends the two runs to VMEM staging tiles at their running
   offsets (the right run's begins at the count) and writes the staging
   tiles out whole at tile-aligned offsets; a tile at either end of the
   range, or where the left run meets the right, is merged by lane mask
   with what the half holds there, so the neighbouring leaves' rows stay as
   they are. Writing into the other half (a ping-pong, not in place) lets
   both runs stream forward in one pass with no copy of the right run back;
2. builds the smaller child's histogram (``node_hist``): a contiguous slice
   of the child's range of the arena, unpacked to feature-major bins and
   ``[3, m]`` g, h and 1, into the Mosaic kernel ``_node_hist_pallas``; the
   sibling's is ``parent - built`` (the caller's).

Both take a dynamic row count, and a program's shapes are static, so the
work follows the rows: the partition kernel loops over the range's tiles
with a trip count it reads from its scalar operands, and the histogram
loops over CHUNKS of ``CHUNK`` rows of the range, the lanes past the count
masked; a call does the work of its rows rounded up to a tile or a chunk,
never the matrix's. A grid sized for n and masked down would cost a tile's
overhead for every one of n's tiles at every step.

The node kernel is the level kernels' construct loop (``_construct_columns``)
over one node's two gradient channels, each carried as two bf16 terms (the
hi/lo split of ``hist_kernel``), and a row count beside them: a feature's
missing rows are the node's count less its bins', so a node without any
gets missing sums of exactly zero (both default directions then tie, and
the first, right, wins, as in the reference), not a rounding. Neither
kernel's name carries ``level``, since the benchmark books every Mosaic call
so named to the level histogram.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

from ..analysis.retrace import guard_jit
from . import hist_kernel as hk

__all__ = ["CHUNK", "NODE_TR", "PART_TR", "build_arena", "chunk_rows",
           "node_hist_fits", "node_hist", "pack_bins", "partition_node"]

# rows a grid step of the node kernel
NODE_TR = 1024

# rows a node histogram takes a loop step: the slice, the unpack and the
# kernel have one shape whatever the node, so the program compiles each
# once; a node pays for at most one chunk it does not fill
CHUNK = 32 * NODE_TR

# rows a tile of the partition's move (its permutation is [PART_TR,
# PART_TR]; on the v5e 512 moved a 3M-row range at 1.40 ns a row where 256
# took 2.05 and 1,024, whose one-hot is VPU-bound, 1.75) and of its count
# (one sublane group of the arena)
PART_TR = 512
_COUNT_TR = 2048

_FIELD_BITS = 10  # a bin id (0 .. B, B <= 1023) in a packed row
_PER_WORD = 3


def node_hist_fits(F: int, B: int) -> bool:
    """Whether the node kernel takes a matrix of ``F`` columns at ``B``
    bins: the unrolled construct loop's width, a bin id in a packed
    field, and the ``[F, 3, B]`` f32 accumulator (8 sublanes a column,
    whole lanes of 128) under the budget the untiled construct kernel
    gives its own."""
    return (0 < F <= hk._MAX_KERNEL_FEATURES
            and 0 < B < (1 << _FIELD_BITS)
            and F * 8 * hk._up(B, 128) * 4 <= hk._VMEM_ACC_BUDGET)


def padded_rows(n: int) -> int:
    return hk._up(max(n, 1), NODE_TR)


def _node_hist_kernel(bins_ref, gh_ref, hist_ref, *, F: int, B: int):
    """Grid step: a ``(Fp, tr)`` block of the node's feature-major bins
    and the ``(3, tr)`` block of their g, h and a 1 (all zero past the
    node's rows) into the node's ``[F, 3, B]`` accumulator: g and h as two
    bf16 terms each, the row count (exact: a bf16 1 into f32) beside
    them."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    g_hi, g_lo = hk._split_hilo(gh_ref[0:1, :])
    h_hi, h_lo = hk._split_hilo(gh_ref[1:2, :])
    one = gh_ref[2:3, :]
    chans = jnp.concatenate([g_hi, h_hi, one, g_lo, h_lo, one * 0.0],
                            axis=0).astype(jnp.bfloat16)  # [6, tr]
    hk._construct_columns(hist_ref, bins_ref[:, :], chans, B, range(F))


# no "level" in this name: the TPU compiler names the Mosaic call after the
# function, and the benchmark books calls so named to the level histogram
@guard_jit(name="node_hist_pallas", static_argnames=("F", "B", "tr"))
def _node_hist_pallas(binsT, gh, *, F: int, B: int, tr: int = NODE_TR):
    """``[F, 3, B]`` histogram (g, h and the row count, missing excluded)
    of the feature-major i32 bins ``[Fp, m]`` and ``[3, m]`` g, h and 1 (0
    on a row that is not the node's)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fp, m = binsT.shape
    assert m % tr == 0, f"rows {m} not padded to {tr}"
    return pl.pallas_call(
        functools.partial(_node_hist_kernel, F=F, B=B),
        grid=(m // tr,),
        in_specs=[
            pl.BlockSpec((Fp, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((3, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((F, 3, B), lambda c: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((F, 3, B), jnp.float32),
        interpret=hk._INTERPRET,
    )(binsT, gh)


def pack_bins(binsT, F: int):
    """Feature-major bins ``[Fp, n]`` (``Fp`` whole sublanes, padded with
    the missing bin) packed three to an int32, ``[ceil(F / 3), n]``: the
    arena's bin channels. Made from the feature-major rows, whose slices
    are whole lanes."""
    W = -(-F // _PER_WORD)
    rows = [binsT[min(k, binsT.shape[0] - 1)] for k in range(W * _PER_WORD)]
    return jnp.stack([rows[3 * w] | (rows[3 * w + 1] << _FIELD_BITS)
                      | (rows[3 * w + 2] << (2 * _FIELD_BITS))
                      for w in range(W)])


def build_arena(packed, grad, hess, L: int):
    """A tree's arena ``[2, C, L]`` int32: half 0 the rows in row order
    (``packed`` ``[W, n]``, then g and h as their bits, then the row id;
    zero in the padding channels and past the last row), half 1 zero."""
    W, n = packed.shape
    bits = lambda v: jax.lax.bitcast_convert_type(  # noqa: E731
        v.astype(jnp.float32), jnp.int32)[None, :]
    C = hk._up(W + 3, hk._SUBLANES)
    rows = jnp.concatenate([packed, bits(grad), bits(hess),
                            jnp.arange(n, dtype=jnp.int32)[None, :]])
    rows = jnp.pad(rows, ((0, C - W - 3), (0, L - n)))
    return jnp.stack([rows, jnp.zeros_like(rows)])


def _unpack(blk, live, W: int, Fp: int, B: int):
    """An arena slice ``[C, m]`` -> the node kernel's feature-major bins
    ``[Fp, m]`` (padded with the missing bin) and ``[3, m]`` g, h and 1,
    zero where not ``live``."""
    mask = (1 << _FIELD_BITS) - 1
    cols = [(blk[w] >> (_FIELD_BITS * k)) & mask
            for w in range(W) for k in range(_PER_WORD)]
    cols += [jnp.full_like(blk[0], B)] * (Fp - len(cols))
    gh = jnp.concatenate([
        jax.lax.bitcast_convert_type(blk[W:W + 2], jnp.float32),
        jnp.ones_like(blk[:1], jnp.float32)])
    return jnp.stack(cols[:Fp]), jnp.where(live[None, :], gh, 0.0)


def chunk_rows(n_pad: int) -> int:
    """Rows a chunk of a node histogram: ``CHUNK``, or the whole padded
    matrix where that is smaller."""
    return min(CHUNK, n_pad)


def _chunks(count, ch: int):
    return (count + ch - 1) // ch


def node_hist(arena, half, start, count, *, F: int, B: int, Fp: int,
              ch: int):
    """Histogram ``[F, 3, B]`` of the rows ``arena[half, :, start : start +
    count]``, chunk by chunk of ``ch`` rows: a chunk's slice of the arena
    unpacked (under ``xgb.node_partition``: it reshapes rows), then the node
    kernel over the chunk (under ``xgb.node_hist``), its lanes past the
    count masked. The arena's lanes run ``ch`` past its rows, so a chunk's
    slice is never clamped."""
    _, C, _ = arena.shape
    W = -(-F // _PER_WORD)

    def body(k, acc):
        with jax.named_scope("xgb.node_partition"):
            blk = jax.lax.dynamic_slice(arena, (half, 0, start + k * ch),
                                        (1, C, ch))[0]
            live = jnp.arange(ch, dtype=jnp.int32) < count - k * ch
            bins, gh = _unpack(blk, live, W, Fp, B)
        with jax.named_scope("xgb.node_hist"):
            return acc + _node_hist_pallas(bins, gh, F=F, B=B)

    return jax.lax.fori_loop(0, _chunks(count, ch), body,
                             jnp.zeros((F, 3, B), jnp.float32))




# ---------------------------------------------------------------------------
# the partition: a count of the left rows, then the move
# ---------------------------------------------------------------------------


def _goes(sc_ref, words, rows, p, B: int):
    """(left, right) masks ``[1, m]`` of the rows at positions ``p`` from
    ``words``, their channels ``rows`` of the arena: only the range's rows
    are either. ``sc_ref``: the half holding the range, its start and
    count, the split's packed word, the field's shift, the split bin, the
    default direction (and, for the move, the left rows' count)."""
    start, end = sc_ref[1], sc_ref[1] + sc_ref[2]
    w = jnp.sum(jnp.where(rows == sc_ref[3], words, 0), axis=0, keepdims=True)
    field = (w >> sc_ref[4]) & ((1 << _FIELD_BITS) - 1)
    go = jnp.where(field >= B, sc_ref[6],
                   (field <= sc_ref[5]).astype(jnp.int32)) != 0
    live = (p >= start) & (p < end)
    return live & go, live & ~go


def _count_kernel(sc_ref, arena, n_left_ref, cbuf, sem, *, TC: int, B: int):
    """The left rows of the range, in int32: the split column's sublane
    group of the arena streamed in ``TC``-row tiles, a lane's count in a
    vector, summed once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    src, start, count = sc_ref[0], sc_ref[1], sc_ref[2]
    a0 = (start // TC) * TC
    n_tiles = (start + count - a0 + TC - 1) // TC
    group = pl.multiple_of((sc_ref[3] // 8) * 8, 8)
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, TC), 0) + group
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, TC), 1)

    def read(k, slot):
        at = pl.multiple_of(a0 + k * TC, TC)
        return pltpu.make_async_copy(
            arena.at[src, pl.ds(group, 8), pl.ds(at, TC)], cbuf.at[slot],
            sem.at[slot])

    @pl.when(n_tiles > 0)
    def _():
        read(0, 0).start()

    def tile(k, acc):
        slot = k % 2

        @pl.when(k + 1 < n_tiles)
        def _():
            read(k + 1, 1 - slot).start()

        read(k, slot).wait()
        gl, _ = _goes(sc_ref, cbuf[slot], rows, a0 + k * TC + lane, B)
        return acc + gl.astype(jnp.int32)

    n_left_ref[0] = jnp.sum(jax.lax.fori_loop(
        0, n_tiles, tile, jnp.zeros((1, TC), jnp.int32)))


def _move_kernel(sc_ref, arena_in, arena, tri, buf, cbuf, stage, wbuf, ebuf,
                 rsem, wsem, esem, *, C: int, T: int, B: int):
    """The range's rows into the other half, left run then right run (see
    the module's docstring); ``sc_ref[7]`` is the left rows' count. A
    loop step compacts the next tile while it stages the tile compacted
    the step before, so that the one's MXU work overlaps the other's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del arena_in  # the same buffer as ``arena`` (aliased)
    src, start, count = sc_ref[0], sc_ref[1], sc_ref[2]
    dst = 1 - src
    mid = start + sc_ref[7]  # where the right run begins
    begin = (start, mid)  # each run's first row
    a0 = (start // T) * T
    n_tiles = (start + count - a0 + T - 1) // T
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * T), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, T), 0)
    flag_rows = jax.lax.broadcasted_iota(jnp.int32, (16, T), 0)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    tri[...] = (out_lane < jax.lax.broadcasted_iota(
        jnp.int32, (T, T), 1)).astype(jnp.bfloat16)  # [i, j]: i < j

    def read(k, slot):
        at = pl.multiple_of(a0 + k * T, T)
        return pltpu.make_async_copy(arena.at[src, :, pl.ds(at, T)],
                                     buf.at[slot], rsem.at[slot])

    def out_tile(pos):
        return arena.at[dst, :, pl.ds(pl.multiple_of(pos, T), T)]

    def write_edge(s, pos, lo, hi):
        """Lanes ``[lo, hi)`` of run ``s``'s staged tile into the tile at
        ``pos``, its other lanes as the half holds them: read, merge and
        write, each waited for."""
        cp = pltpu.make_async_copy(out_tile(pos), ebuf, esem.at[0])
        cp.start()
        cp.wait()
        ebuf[...] = jnp.where((lane >= lo) & (lane < hi), stage[s, :, 0:T],
                              ebuf[...])
        cp = pltpu.make_async_copy(ebuf, out_tile(pos), esem.at[0])
        cp.start()
        cp.wait()

    def wdesc(s, slot, pos):
        return pltpu.make_async_copy(wbuf.at[s, slot], out_tile(pos),
                                     wsem.at[s, slot])

    def write_whole(s, pos, n_out):
        """Run ``s``'s staged tile, every lane its own, written behind the
        loop (two tiles in flight a run)."""
        slot = n_out % 2

        @pl.when(n_out >= 2)
        def _():
            wdesc(s, slot, 0).wait()

        wbuf[s, slot] = stage[s, :, 0:T]
        wdesc(s, slot, pos).start()

    def compact(k, slot):
        """Tile ``k`` (in ``buf[slot]``; stale past the range, where no
        lane is the range's) compacted into ``cbuf[slot]``, left run then
        right run. Returns the runs' lengths."""
        x = buf[slot]
        gl, gr = _goes(sc_ref, x, rows, a0 + k * T + lane, B)
        # the rows' exclusive ranks in their run: the flags times a strictly
        # upper triangular [T, T] of ones on the MXU (exact: counts <= T)
        flags = jnp.where(flag_rows == 0, gl.astype(jnp.float32),
                          jnp.where(flag_rows == 1, gr.astype(jnp.float32),
                                    0.0)).astype(jnp.bfloat16)
        ranks = jnp.dot(flags, tri[...],
                        preferred_element_type=jnp.float32).astype(jnp.int32)
        n_l = jnp.sum(gl.astype(jnp.int32), axis=1, keepdims=True)
        dest = jnp.where(gl, ranks[0:1], jnp.where(gr, n_l + ranks[1:2], -1))
        # its words' bytes (exact in bf16) times the one-hot [T(j), T(i)] of
        # the rows' places, one nonzero product an output lane
        onehot = (out_lane == dest).astype(jnp.bfloat16)
        xb = jnp.concatenate(
            [((x >> (8 * q)) & 255).astype(jnp.float32) for q in range(4)],
            axis=0).astype(jnp.bfloat16)
        moved = jax.lax.dot_general(
            xb, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        comp = moved[0:C]
        for q in range(1, 4):
            comp = comp | (moved[q * C:(q + 1) * C] << (8 * q))
        cbuf[slot] = comp
        return jnp.sum(gl.astype(jnp.int32)), jnp.sum(gr.astype(jnp.int32))

    def place(slot, adds, runs):
        """The compacted tile in ``cbuf[slot]`` appended to the two runs'
        staging, a run's staged tile written where it is full."""
        comp = cbuf[slot]
        comp = jnp.concatenate([comp, jnp.zeros_like(comp)], axis=1)
        in_comp = (0, adds[0])  # where each run lies in ``comp``
        out = []
        for s, (fill, pos, fresh, n_out) in enumerate(runs):
            # this tile's run appended to the run's staging at ``fill``
            rolled = pltpu.roll(comp, (fill - in_comp[s] + 2 * T) % (2 * T), 1)
            stage[s] = jnp.where((lane2 >= fill) & (lane2 < fill + adds[s]),
                                 rolled, stage[s])
            out.append(fill + adds[s])
        for s, (fill, pos, fresh, n_out) in enumerate(runs):
            fill = out[s]
            full = fill >= T
            # a run's first tile begins at its start's lane
            lo = jnp.where(fresh != 0, begin[s] % T, 0)

            @pl.when(full & (lo > 0))
            def _():
                write_edge(s, pos, lo, T)

            @pl.when(full & (lo == 0))
            def _():
                write_whole(s, pos, n_out)

            @pl.when(full)
            def _():
                stage[s, :, 0:T] = stage[s, :, T:2 * T]

            out[s] = (jnp.where(full, fill - T, fill),
                      jnp.where(full, pos + T, pos),
                      jnp.where(full, 0, fresh),
                      jnp.where(full & (lo == 0), n_out + 1, n_out))
        return tuple(out)

    @pl.when(n_tiles > 0)
    def _():
        read(0, 0).start()
        read(0, 0).wait()

    @pl.when(n_tiles > 1)
    def _():
        read(1, 1).start()

    def tile(k, carry):
        runs, adds = carry
        slot, nxt = k % 2, 1 - k % 2

        @pl.when(k + 1 < n_tiles)
        def _():
            read(k + 1, nxt).wait()

        @pl.when(k + 2 < n_tiles)
        def _():
            read(k + 2, slot).start()  # tile k was compacted a step ago

        adds_next = compact(k + 1, nxt)
        return place(slot, adds, runs), adds_next

    one, zero = jnp.int32(1), jnp.int32(0)
    runs, _ = jax.lax.fori_loop(
        0, n_tiles, tile,
        (tuple((r % T, (r // T) * T, one, zero) for r in begin),
         compact(0, 0)))
    for s, (fill, pos, fresh, n_out) in enumerate(runs):
        # the run's last tile, as far as it is filled
        lo = jnp.where(fresh != 0, begin[s] % T, 0)

        @pl.when(fill > lo)
        def _():
            write_edge(s, pos, lo, fill)

        for slot in range(2):
            @pl.when(n_out > slot)
            def _():
                wdesc(s, slot, 0).wait()


# no "level" in these names (as ``_node_hist_pallas``'s)
@guard_jit(name="node_count_pallas", static_argnames=("B",))
def _node_count_pallas(arena, sc, *, B: int):
    """The left rows' count ``[1]`` int32 of the range ``sc`` names."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_count_kernel, TC=_COUNT_TR, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.VMEM((2, 8, _COUNT_TR), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=hk._INTERPRET,
    )(sc, arena)


@guard_jit(name="node_move_pallas", static_argnames=("B",))
def _node_move_pallas(arena, sc, *, B: int):
    """``arena`` with the range ``sc`` names moved into its other half."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, C, _ = arena.shape
    T = PART_TR
    return pl.pallas_call(
        functools.partial(_move_kernel, C=C, T=T, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((T, T), jnp.bfloat16),
                pltpu.VMEM((2, C, T), jnp.int32),
                pltpu.VMEM((2, C, T), jnp.int32),
                pltpu.VMEM((2, C, 2 * T), jnp.int32),
                pltpu.VMEM((2, 2, C, T), jnp.int32),
                pltpu.VMEM((C, T), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={1: 0},
        interpret=hk._INTERPRET,
    )(sc, arena)


def _split_scalars(half, start, count, f, b, default_left):
    """The kernels' scalar operand ``[8]`` (see ``_goes``), the left rows'
    count 0."""
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    f = i32(f)
    return jnp.stack([i32(half), i32(start), i32(count), f // _PER_WORD,
                      _FIELD_BITS * (f % _PER_WORD), i32(b),
                      i32(default_left), i32(0)])


def partition_node(arena, half, start, count, f, b, default_left, *,
                   B: int):
    """Stable partition of the rows ``arena[half, :, start : start +
    count]`` by the numerical split (feature ``f``, bin ``b``, missing bin
    ``B`` to the left where ``default_left``) into ``arena[1 - half]`` at
    the same range: the rows going left first, in their order, then the
    rows going right. The left rows are counted in int32, exact at any n
    (a float32 row count is not above 2**24). Returns (arena, the rows
    that went left)."""
    sc = _split_scalars(half, start, count, f, b, default_left)
    n_left = _node_count_pallas(arena, sc, B=B)[0]
    return _node_move_pallas(arena, sc.at[7].set(n_left), B=B), n_left
