"""Device mesh management: the TPU replacement for the entire rabit
tracker/socket stack (reference: ``rabit/`` + ``tracker.py`` —
SURVEY.md §2.10).

Single-controller JAX needs no rendezvous: the mesh IS the cluster
membership, ranks are mesh coordinates, and the four collective call sites
of the reference (sketch merge quantile.cc:270, histogram AllReduce
hist/histogram.h:201, metric sums, num_feature max learner.cc:596) become
``psum``/``all_gather`` over a named axis. Multi-host: initialize
``jax.distributed`` and build the mesh over all devices — DCN is handled
transparently by the runtime.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..resilience import watchdog as _wd

ROW_AXIS = "data"  # the one parallel axis of GBDT training: rows

_state = threading.local()


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh over the row axis (GBDT's only scalable dimension — the
    'sequence parallelism' analog per SURVEY.md §5: rows sharded, histogram
    reductions fixed-size)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (ROW_AXIS,))


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def collective_active() -> bool:
    """True only when COLLECTIVE multi-process semantics apply: several
    processes AND an active ``mesh_context``. Shared by the learner's
    training routing and the metrics' distributed reductions so they can
    never disagree — a program that merely initialized jax.distributed but
    trains mesh-less per-process boosters must see purely local behavior
    everywhere (no surprise allgathers inside metric evaluation)."""
    return jax.process_count() > 1 and current_mesh() is not None


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]) -> Iterator[None]:
    """Activate a mesh: training inside the context shards rows over it."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


# heartbeat timeout (seconds) that makes the coordination service deaf:
# ~116 days, longer than any job
_DEAF_HEARTBEAT_S = 10_000_000


def form_world(coordinator_address: str, num_processes: int,
               process_id: int) -> Mesh:
    """Elastic-grade world formation: ``jax.distributed.initialize``
    semantics with a runtime that SURVIVES peer death instead of
    propagating it.

    The stock coordination service health-checks members and, on a missed
    heartbeat, broadcasts a fatal error that LOG(FATAL)s every surviving
    process (xla client.h) — the exact opposite of elasticity. Here the
    service is made deaf (an effectively-infinite ``heartbeat_timeout`` on
    both the service and the client; liveness is owned by
    ``parallel.membership``'s file heartbeats) and
    the client skips the shutdown barrier on destruction (a survivor must
    exit cleanly after its peers are gone). Known asymmetry, documented
    in docs/distributed.md: the COORDINATOR process (rank 0 of the
    initial world) hosts the service in-process, so its death still takes
    the runtime down — survivors of a coordinator loss recover by process
    restart + checkpoint resume, not in-process resize (the rabit
    tracker has the same single point of authority)."""
    from jax._src import distributed as _dist
    from jax._src.lib import _jax

    st = _dist.global_state
    if st.client is not None:
        raise RuntimeError(
            "form_world: jax distributed runtime already initialized in "
            "this process; elastic re-formation at world > 1 requires a "
            "process restart (docs/distributed.md, Elastic training)")
    with _wd.watchdog("collective_init",
                      seconds=_wd.deadline_for("collective_init", 900.0)):
        if process_id == 0:
            st.service = _jax.get_distributed_runtime_service(
                "[::]:" + coordinator_address.rsplit(":", 1)[1],
                num_processes, heartbeat_timeout=_DEAF_HEARTBEAT_S)
        client = _jax.get_distributed_runtime_client(
            coordinator_address, process_id, init_timeout=300,
            heartbeat_timeout=_DEAF_HEARTBEAT_S,
            shutdown_on_destruction=False, use_compression=True)
        client.connect()
    st.client = client
    st.process_id = process_id
    st.num_processes = num_processes
    st.coordinator_address = coordinator_address
    return make_mesh(devices=jax.devices())


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
    elastic: bool = False,
) -> Mesh:
    """Multi-host entry point — the role the reference's dask frontend plays
    (``python-package/xgboost/dask.py:838-952``: start RabitTracker, hand
    every worker its rank/URI, build the rabit ring). Single-controller JAX
    collapses all of that to ``jax.distributed.initialize`` + one mesh over
    every process's devices; DCN transport is handled by the runtime, and
    there is no tracker because the mesh IS the membership.

    Call once per process before building DMatrix/Booster objects, then
    train inside ``mesh_context(mesh)`` with each process ingesting its own
    row shard (the ``load_row_split`` analog — see
    ``docs/distributed.md``). Arguments mirror
    ``jax.distributed.initialize`` and may be omitted when the runtime
    auto-detects (TPU pods). ``elastic=True`` routes through
    :func:`form_world` — a peer-death-tolerant runtime whose liveness is
    owned by ``parallel.membership`` instead of the coordination
    service's fail-everything health check. Returns the global mesh.
    """
    if num_processes is not None and num_processes > 1:
        if elastic:
            return form_world(coordinator_address, num_processes,
                              process_id)
        # Deadline around the rendezvous: better a clean WatchdogTimeout
        # than an unbounded hang on a coordinator that never answers.
        # Default 900s (a healthy rendezvous takes seconds-to-minutes);
        # tune/disable via XGBTPU_WATCHDOG="collective_init=...".
        with _wd.watchdog("collective_init",
                          seconds=_wd.deadline_for("collective_init",
                                                   900.0)):
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=local_device_ids,
            )
    return make_mesh(devices=jax.devices())


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def global_pad_rows(n_local: int, unit: int) -> int:
    """The COMMON per-process padded block size: ceil(n_local/unit)*unit,
    maxed over all processes. Multi-process row sharding requires every
    process to contribute equal padded blocks (shard_rows); real row
    counts may be uneven (load_row_split hands ragged slices) — the
    per-process validity masks (grow.py n_arr) make the extra padding
    inert, so processes just agree on the largest block here."""
    n_pad = pad_to_multiple(max(n_local, 1), unit)
    if jax.process_count() > 1:
        from .. import collective

        sizes = collective.process_allgather(
            np.asarray(n_pad, np.int64), site="pad_rows")
        n_pad = int(sizes.max())
    return n_pad


def local_device_count(mesh: Mesh) -> int:
    """Devices of ``mesh`` owned by THIS process (== mesh size when
    single-process). Row padding is computed per process against this, so
    every process's local block is the same fraction of the global array."""
    pi = jax.process_index()
    return sum(1 for d in mesh.devices.flat if d.process_index == pi)


def _put_global(arr, sharding) -> jax.Array:
    """device_put that also works multi-process: each process supplies its
    process-local block (or the full array for replicated specs)."""
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(arr))
    return jax.device_put(arr, sharding)


def _check_equal_blocks(n_local: int) -> None:
    """Multi-process row sharding requires every process to contribute the
    SAME padded block size (global shape inference and the per-shard
    validity mask both assume it). Fails loudly instead of deadlocking."""
    from .. import collective

    sizes = collective.process_allgather(
        np.asarray(n_local, np.int64), site="equal_blocks")
    if not (sizes == sizes[0]).all():
        raise ValueError(
            "multi-process training requires equal PADDED row blocks per "
            f"process; got {sizes.tolist()}. Give every process the same "
            "number of rows (pad the short ones — padded rows are inert)."
        )


def shard_rows(arr: jax.Array, mesh: Mesh) -> jax.Array:
    """Place an array row-sharded over the mesh (rows must divide evenly —
    pad first; padded rows carry zero gradient/hessian so they are inert,
    the fixed-shape analog of the reference's empty-worker handling,
    dask.py:914). Multi-process: ``arr`` is THIS process's row block (the
    load_row_split model — each process ingested its own slice) and the
    global array is their concatenation in process order; all processes
    must contribute equally-sized padded blocks."""
    if jax.process_count() > 1:
        _check_equal_blocks(arr.shape[0])
    spec = P(ROW_AXIS, *([None] * (arr.ndim - 1)))
    return _put_global(arr, NamedSharding(mesh, spec))


def replicate(arr: jax.Array, mesh: Mesh) -> jax.Array:
    """Replicate a (process-identical) array over the whole mesh."""
    return _put_global(arr, NamedSharding(mesh, P()))


def local_rows(arr: jax.Array) -> jax.Array:
    """THIS process's row block of a row-sharded global array (identity
    when single-process): the inverse of ``shard_rows``. Used to bring
    per-row outputs (margins, deltas) back to process-local layout."""
    if jax.process_count() == 1:
        return arr
    from ..observability import trace

    with trace.span("local_rows", bytes=int(arr.nbytes)):
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        import jax.numpy as jnp

        # via host: the shards live committed on DIFFERENT local devices
        # and cannot be concatenated device-side without explicit transfers
        return jnp.asarray(
            np.concatenate([np.asarray(s.data) for s in shards], axis=0))
