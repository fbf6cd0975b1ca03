"""Operations and bytes of the hot kernels, from shapes alone.

The yardstick's arithmetic: a later PR may change how a kernel computes, not
how much work the algorithm needs. The histogram of one tree level is a
matrix product of the rows' one-hot bin expansion ``[n, F*B]`` with the
gradient channels ``[n, 2K]`` (g and h for each of the K nodes whose
histograms the level builds: ``built_nodes``, not every node the level has);
the package carries each float32 gradient as two bf16 terms (hi + lo), so
the product is done twice (``bf16_terms``).
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
MXU_TILE = 128


def load_peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a device not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def _pad(x: int, tile: int) -> int:
    return -(-x // tile) * tile


def built_nodes(d: int) -> int:
    """Nodes whose histograms level ``d`` of a tree has to build: 1 at the
    root, ``2^(d-1)`` at ``d >= 1``: one child of each of the level above's
    ``2^(d-1)`` splits; its sibling is parent - built.

    That is the algorithm's count, not the program's: histogram subtraction
    is the reference's own method (``src/tree/updater_quantile_hist.cc`` and
    ``updater_gpu_hist.cu`` build the smaller child and subtract for the
    larger), so a floor that counts both children counts work no
    implementation of ``hist`` needs. The rule is unconditional: a program
    that builds both children reads half of what it would against ``2^d``.

    Where the floor built on this count can go stale again (a ``benchmark``
    PR restates it first, then the kernel changes): it counts every one of
    the n rows at every level (the matmul form multiplies each row by every
    built channel; a kernel that compacts rows to the built children does
    less); it takes two bf16 terms a float32 gradient and the bf16 peak (a
    fixed-point histogram on the int8 MXU has another count and another
    peak); and it leaves out the subtraction's bytes, because the
    subtraction runs in XLA, outside the kernels whose time is
    ``level_hist_roofline``'s denominator."""
    return 1 if d == 0 else 1 << (d - 1)


def level_hist_flops(n: int, F: int, B: int, K: int, *, bf16_terms: int = 2,
                     pad_to_mxu: bool = False) -> float:
    """Multiply-adds x 2 of one level's histogram: ``[F*B, n] @ [n, 2K]``
    once per bf16 term, ``K`` the nodes whose histograms the level builds
    (``built_nodes``). ``pad_to_mxu`` rounds the channel width up to the
    128-wide MXU tile, as the hardware executes it."""
    channels = 2 * K * bf16_terms
    if pad_to_mxu:
        channels = _pad(channels, MXU_TILE)
    return 2.0 * n * F * B * channels


def level_hist_bytes(n: int, F: int, B: int, K: int) -> float:
    """HBM bytes one level's histogram has to move, whatever the kernel:
    the narrow bins read once (1 byte at B <= 256, else 2), gradients and
    positions (g, h float32 + pos int32: 12 bytes a row), and the float32
    histogram of the ``K`` built nodes written once. A one-hot expansion
    kept in HBM is the program's choice, not the algorithm's, and is not
    counted: a kernel that streams one reads further from this floor, not
    nearer."""
    return (float(n) * F * (1 if B <= 256 else 2)
            + 12.0 * n
            + 4.0 * F * B * 2 * K)


def level_hist_min_seconds(n: int, F: int, B: int, K: int,
                           peaks: dict) -> tuple:
    """(seconds, "flops" | "bytes"): the least time the histograms of a
    level's ``K`` built nodes can take on a chip with ``peaks``, and which
    of the two bounds it."""
    t_flops = level_hist_flops(n, F, B, K) / peaks["bf16_flops_per_s"]
    t_bytes = level_hist_bytes(n, F, B, K) / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def round_hist_flops(n: int, F: int, B: int, depth: int, **kw) -> float:
    """Histogram flops of one tree: levels 0..depth-1, each with its
    ``built_nodes``."""
    return sum(level_hist_flops(n, F, B, built_nodes(d), **kw)
               for d in range(depth))


def round_hist_bytes(n: int, F: int, B: int, depth: int) -> float:
    return sum(level_hist_bytes(n, F, B, built_nodes(d))
               for d in range(depth))


def walk_bytes_per_row(F: int, trees: int, depth: int) -> float:
    """Least bytes a gather walk moves for one row: its float32 features
    once, and for each tree and level one node record (feature id, threshold,
    two children: 16 bytes) plus the leaf value."""
    return 4.0 * F + trees * (16.0 * depth + 4.0)
