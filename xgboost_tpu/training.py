"""train() / cv() loops (reference: ``python-package/xgboost/training.py`` —
train at :49, cv + folds at :189-459) plus the elastic multi-host driver
``elastic_train`` (detection -> quiesce -> resize -> checkpoint replay;
docs/distributed.md, "Elastic training")."""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .callback import (
    CallbackContainer,
    EarlyStopping,
    EvaluationMonitor,
    TrainingCallback,
)
from .data.dmatrix import DMatrix
from .learner import Booster

__all__ = ["train", "cv", "elastic_train", "elastic_exit"]


class _AtomicCheckpoint(TrainingCallback):
    """Per-round crash-safe checkpointing for ``train(resume_from=...)``:
    atomic tmp+fsync+rename writes with a checksum trailer
    (``resilience/checkpoint.py``), pruned to the 2 newest so a previous
    good snapshot always survives the one in flight. Since ISSUE 15 the
    serialization + fsync + rename run on the async writer thread by
    default (``XGBTPU_ASYNC_CKPT=0`` restores the synchronous path): the
    round loop captures the model snapshot at its sync point and blocks
    again only if the PREVIOUS write is still in flight at the next
    checkpoint boundary; ``after_training`` drains so the final round is
    durable before ``train`` returns."""

    def __init__(self, directory: str, interval: int = 1):
        self.directory = directory
        self.interval = max(1, int(interval))

    def _save(self, model, final: bool = False) -> None:
        from .resilience import checkpoint as _ckpt

        rounds = model.num_boosted_rounds()
        if rounds:
            if _ckpt.async_enabled():
                w = _ckpt.async_writer()
                # probe-before-write, async flavor: skip rounds whose
                # commit is in flight or provably on disk (covered() is
                # deletion-safe — a wiped directory re-commits)
                if not w.covered(self.directory, rounds) \
                        and _ckpt.read_checkpoint(_ckpt.checkpoint_path(
                            self.directory, rounds)) is None:
                    w.submit(self.directory, model, rounds)
                if final:
                    w.wait(self.directory)
            elif _ckpt.read_checkpoint(
                    _ckpt.checkpoint_path(self.directory, rounds)) is None:
                _ckpt.save_checkpoint(self.directory, model, rounds)

    def after_iteration(self, model, epoch, evals_log) -> bool:
        if (epoch + 1) % self.interval == 0:
            self._save(model)
        return False

    def after_training(self, model):
        self._save(model, final=True)  # the final round is always durable
        return model


def train(
    params: Dict[str, Any],
    dtrain: DMatrix,
    num_boost_round: int = 10,
    evals: Optional[Sequence[Tuple[DMatrix, str]]] = None,
    obj=None,
    feval=None,
    maximize: Optional[bool] = None,
    early_stopping_rounds: Optional[int] = None,
    evals_result: Optional[dict] = None,
    verbose_eval: Any = True,
    xgb_model: Optional[Booster] = None,
    callbacks: Optional[Sequence[TrainingCallback]] = None,
    custom_metric=None,
    resume_from: Optional[str] = None,
    checkpoint_interval: int = 1,
    checkpoint_shared: bool = False,
    resume_mode: str = "total",
) -> Booster:
    """``resume_from`` (ISSUE 5 tentpole): a directory of crash-safe
    checkpoints. When set, training (a) resumes from the newest VERIFIED
    checkpoint found there — rerunning the same command after a crash
    picks up at the last committed round and grows the same trees as an
    uninterrupted run — and (b) commits an atomic checkpoint every
    ``checkpoint_interval`` rounds. With the default
    ``resume_mode="total"``, ``num_boost_round`` stays the TOTAL round
    count: a run resumed at round r trains the remaining
    ``num_boost_round - r``. ``resume_mode="append"`` (ISSUE 12 —
    continuous training) instead trains ``num_boost_round`` MORE rounds
    on top of the checkpoint, on possibly FRESH ``dtrain`` data:
    boosting is naturally incremental, so periodic append-mode re-trains
    against the same directory plus the serving delivery controller form
    a real online-learning loop (docs/serving.md "Model delivery").
    ``train(N)`` then append-resume ``+M`` on the same data is
    bit-identical to ``train(N + M)`` straight through
    (tests/test_delivery.py). ``checkpoint_shared`` keeps multi-process
    checkpoints in ONE directory (the elastic layer's mode — payloads are
    rank-identical and tmp names pid-unique) instead of per-rank
    subdirectories."""
    if resume_mode not in ("total", "append"):
        raise ValueError(
            f"resume_mode must be 'total' or 'append', got {resume_mode!r}")
    callbacks = list(callbacks) if callbacks else []
    evals = list(evals) if evals else []
    feval = custom_metric if custom_metric is not None else feval
    # scan fast-path eligibility, decided on USER-supplied state before the
    # auto-added monitor/early-stop/checkpoint callbacks join the list
    _no_per_iter_consumer = (
        not evals and not callbacks and obj is None and feval is None
        and early_stopping_rounds is None and resume_from is None
    )

    ckpt_dir: Optional[str] = None
    if resume_from is not None:
        from .resilience import checkpoint as _ckpt

        ckpt_dir = _ckpt.process_dir(resume_from, shared=checkpoint_shared)
        loaded = _ckpt.load_latest(ckpt_dir)
        if loaded is not None and xgb_model is None:
            raw, done_rounds = loaded
            xgb_model = bytes(raw)
            if resume_mode == "total":
                # total-round semantics: an already-complete checkpoint
                # trains 0 further rounds (but still flows through the
                # normal path so caches/callbacks see the same state as a
                # live run)
                num_boost_round = max(0, num_boost_round - done_rounds)
            # append semantics: num_boost_round MORE rounds from here —
            # the continuous-training half of the delivery loop
        callbacks.append(_AtomicCheckpoint(ckpt_dir, checkpoint_interval))

    if verbose_eval:
        period = verbose_eval if isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool) else 1
        callbacks.append(EvaluationMonitor(period=period))
    if early_stopping_rounds is not None:
        callbacks.append(EarlyStopping(rounds=early_stopping_rounds, maximize=maximize))

    if xgb_model is not None:
        from .learner import _PredCache

        bst = xgb_model.copy() if isinstance(xgb_model, Booster) else Booster(params, model_file=xgb_model)
        bst.set_param(params)
        for d, _ in [(dtrain, "train")] + evals:
            bst._caches.setdefault(id(d), _PredCache())
            bst._cache_refs.setdefault(id(d), d)
        start_round = bst.num_boosted_rounds()
    else:
        bst = Booster(params, cache=[dtrain] + [d for d, _ in evals])
        start_round = 0

    container = CallbackContainer(callbacks)
    bst = container.before_training(bst)

    import jax

    from .native import boundary as _boundary
    from .observability import flight as _flight
    from .observability import trace as _trace
    from .pipeline import RoundPipeline, completion_probe
    from .resilience.policy import RetryPolicy as _RetryPolicy
    from .resilience.watchdog import watchdog as _watchdog

    def _commit_on_abort() -> None:
        """A watchdog abort mid-dispatch must not lose the committed
        rounds: flush the last consistent model state as a checkpoint
        (in-flight, uncommitted tree state is never serialized — save_raw
        walks only committed trees). The async writer is drained first so
        the abort-path synchronous write never races an in-flight commit
        of the same round."""
        if ckpt_dir is None:
            return
        try:
            from .resilience import checkpoint as _ckpt

            try:
                _ckpt.async_writer().wait(ckpt_dir)
            except Exception:
                pass  # a parked write failure must not mask THIS abort
            rounds = bst.num_boosted_rounds()
            if rounds:
                _ckpt.save_checkpoint(ckpt_dir, bst, rounds)
        except Exception:
            pass  # the abort itself must still surface

    try:
        if _no_per_iter_consumer and jax.default_backend() == "tpu":
            # no per-iteration consumer (no eval lines, early stopping,
            # checkpoints or custom callbacks): train whole chunks as single
            # scan dispatches (Booster.update_many; falls back per-round for
            # ineligible configs). TPU-only: the scan amortizes dispatch
            # latency, which is what accelerator backends pay; on CPU it only
            # multiplies XLA:CPU compile load (observed LLVM segfaults under
            # the full-suite compile volume), so the classic loop stays.
            with _trace.span("train", rounds=num_boost_round, path="scan"):
                with _watchdog("train_dispatch"):
                    bst.update_many(dtrain, start_round, num_boost_round)
                if bst._pipeline is not None:
                    # end-of-training sync point: the last chunks' async
                    # faults must surface HERE, attributed, not as an
                    # anonymous error at a later save/predict (direct
                    # update_many callers keep cross-call pipelining and
                    # drain at their own boundaries)
                    bst._pipeline.drain()
        else:
            # the async pipelined round loop (ISSUE 13): each round's
            # dispatch overlaps the previous rounds' device execution,
            # bounded to XGBTPU_PIPELINE_DEPTH rounds in flight. Host
            # synchronization happens ONLY at the blessed points — an
            # eval/early-stop/custom-callback boundary, a checkpoint
            # commit, or the end of training — so a consumer-free run
            # never blocks inside the loop (docs/perf.md).
            pipe = RoundPipeline()
            # per-round consumers force a drain every round; when the ONLY
            # consumer is the auto-added interval checkpoint, drain only on
            # the rounds it actually commits — a checkpoint_interval=k run
            # keeps the overlap window on the other k-1 rounds
            _other_consumers = (
                bool(evals) or obj is not None or feval is not None
                or early_stopping_rounds is not None
                or any(not isinstance(c, (EvaluationMonitor,
                                          _AtomicCheckpoint))
                       for c in callbacks))
            _ckpt_cb = ckpt_dir is not None

            def _round_consumer(i: int) -> bool:
                if _other_consumers:
                    return True
                return _ckpt_cb and (i + 1) % max(checkpoint_interval,
                                                  1) == 0

            # the native-boundary containment bracket (ISSUE 20): a fault
            # raised while a native train route is active degrades the
            # owning library (dispatch re-routes to the XLA/level impls)
            # and the ROUND retries on the fallback route. Rounds that
            # already committed into the model are never retried — a
            # post-commit fault re-raises as-is.
            _native_retry = _RetryPolicy(
                "native_dispatch", retries=2,
                retry_types=(_boundary.NativeFault,))

            def _contained_update(i: int) -> None:
                _committed = bst.num_boosted_rounds()
                try:
                    with _watchdog("round_dispatch"):
                        # ``native_dispatch`` chaos site: fires once per
                        # round while a native train route is active
                        _boundary.round_chaos()
                        bst.update(dtrain, i, fobj=obj)
                except Exception as _e:
                    if bst.num_boosted_rounds() != _committed:
                        raise
                    raise _boundary.contain(_e) from _e
            with _trace.span("train", rounds=num_boost_round,
                             path="per_round", pipeline_depth=pipe.depth):
                for i in range(start_round, start_round + num_boost_round):
                    if container.before_iteration(bst, i, dtrain, evals):
                        break
                    _flight.profile_tick(i)
                    _flight.RECORDER.begin_round(i)
                    try:
                        with _trace.span("round", iteration=i):
                            # deadline around the per-round host dispatch
                            # (off unless XGBTPU_WATCHDOG names
                            # round_dispatch or *): a wedged dispatch
                            # aborts cleanly — raise + checkpoint —
                            # instead of hanging the run
                            _t0 = time.perf_counter()
                            _boundary.tick()
                            _native_retry.run(_contained_update, i)
                            # host-blocked dispatch time: the number the
                            # pipelined executor exists to shrink; waits
                            # land in the 'sync' stage instead
                            _flight.note("grow", time.perf_counter() - _t0)
                            _entry = bst._caches.get(id(dtrain))
                            pipe.admit(i, completion_probe(
                                _entry.margin if _entry is not None
                                else None))
                            if _round_consumer(i):
                                # sync point: the consumer must observe a
                                # finished round (and an async fault must
                                # surface HERE, attributed to its round)
                                pipe.drain()
                            stop = container.after_iteration(
                                bst, i, dtrain, evals, feval=feval)
                    finally:
                        _flight.RECORDER.end_round()
                    if stop:
                        break
                pipe.drain()  # end-of-training sync point
    except BaseException as e:
        # ANY abort mid-loop — watchdog expiry, a collective failing
        # because a peer died, an elastic guard raising WorkerLost —
        # flushes the last consistent rounds as a checkpoint before
        # surfacing: this is the quiesce half of the elastic contract
        # (the resize half replays from exactly this snapshot)
        _commit_on_abort()
        _flight.RECORDER.abort_dump(e)  # black box: ring + metrics
        raise
    finally:
        _flight.profile_stop()

    bst = container.after_training(bst)

    if evals_result is not None:
        for k, v in container.history.items():
            evals_result[k] = {mk: list(mv) for mk, mv in v.items()}
    return bst


# ---------------------------------------------------------------------------
# Elastic multi-host training: fault-tolerant membership + checkpoint replay
# ---------------------------------------------------------------------------


class _ElasticGuard(TrainingCallback):
    """Per-round elastic sentinel. At every round boundary it (a) fires
    the ``worker_kill`` chaos site — a scripted hit SIGKILLs this worker,
    the rabit-mock "die at (version, seqno)" analog; (b) exports the
    round into the heartbeat stream; (c) checks membership and raises
    :class:`~xgboost_tpu.parallel.membership.WorkerLost` on a dead peer
    (quiesce at the round boundary) or fences itself if tombstoned."""

    def __init__(self, membership):
        self.membership = membership

    def before_iteration(self, model, epoch, evals_log) -> bool:
        from .parallel.membership import WorkerLost
        from .resilience import chaos
        from .resilience.chaos import ChaosError

        try:
            chaos.hit("worker_kill")
        except ChaosError:
            import signal

            from .utils import console_logger

            console_logger.warning(
                f"chaos: worker_kill fired at round {epoch} — SIGKILLing "
                f"rank {self.membership.rank} (pid {os.getpid()})")
            os.kill(os.getpid(), signal.SIGKILL)
        self.membership.round = epoch
        dead = self.membership.scan()
        if self.membership.fenced:
            raise WorkerLost([self.membership.rank], epoch)
        if dead:
            raise WorkerLost(dead, epoch)
        return False


def _atomic_json(path: str, obj: dict) -> None:
    import json

    from .resilience.checkpoint import atomic_write_bytes

    atomic_write_bytes(path, json.dumps(obj).encode())


def _read_json(path: str) -> Optional[dict]:
    import json

    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _canonical_cuts(run_dir: str, data_fn, max_bin: int, rank: int,
                    members: List[int]):
    """Sharding-invariant binning for bit-exact elastic replay: the
    LOWEST member computes cuts ONCE from the full dataset
    (``data_fn(0, 1)`` — the load_row_split contract's world-1 view)
    through the plain local quantile path, persists them atomically, and
    every generation at every world size bins its shard against them.
    Without this, the distributed sketch's cuts depend on the shard
    count and a post-resize model could never be bit-identical to an
    uninterrupted run at the final world size."""
    import hashlib
    import json

    from .data.quantile import HistogramCuts
    from .resilience.watchdog import watchdog

    path = os.path.join(run_dir, "cuts.json")
    got = _read_json(path)
    if got is None and rank == min(members):
        full = data_fn(0, 1)
        bm = full.get_binned(max_bin)
        payload = {
            "max_bin": int(max_bin),
            "values": np.asarray(bm.cuts.values).tolist(),
            "min_vals": np.asarray(bm.cuts.min_vals).tolist(),
        }
        payload["sha256"] = hashlib.sha256(
            json.dumps(payload, sort_keys=True).encode()).hexdigest()
        _atomic_json(path, payload)
        got = payload
    if got is None:
        # non-writers wait for the writer (deadline-guarded: a dead
        # writer here must abort, not hang — the driver restarts us)
        import time

        with watchdog("elastic_cuts", seconds=300.0):
            while got is None:
                time.sleep(0.1)
                got = _read_json(path)
    check = dict(got)
    sha = check.pop("sha256", None)
    if sha != hashlib.sha256(
            json.dumps(check, sort_keys=True).encode()).hexdigest():
        raise RuntimeError(f"elastic cuts manifest {path} failed its "
                           "checksum; delete it to recompute")
    if int(got["max_bin"]) != int(max_bin):
        raise RuntimeError(
            f"elastic cuts manifest was built for max_bin="
            f"{got['max_bin']}, run requests {max_bin}")
    return HistogramCuts(
        values=np.asarray(got["values"], np.float32),
        min_vals=np.asarray(got["min_vals"], np.float32))


def _bin_with_cuts(d: DMatrix, cuts, max_bin: int) -> DMatrix:
    """Seed ``d``'s quantized-matrix cache with the canonical cuts (the
    ``QuantileDMatrix(ref=...)`` mechanism, applied in place)."""
    from .data.quantile import BinnedMatrix

    cat = d.categorical_features()
    if d._sparse is not None and d._data is None:
        bm = BinnedMatrix.from_sparse(
            d._sparse, max_bin=max_bin, cuts=cuts, categorical=cat)
    else:
        bm = BinnedMatrix.from_dense(
            d.data, max_bin=max_bin, cuts=cuts, categorical=cat)
    d._binned[max_bin] = bm
    return d


_GEN_ENV = "XGBTPU_ELASTIC_GEN"


def elastic_train(
    params: Dict[str, Any],
    data_fn: Callable[[int, int], DMatrix],
    num_boost_round: int,
    *,
    run_dir: str,
    world: int,
    rank: int,
    coordinator: Optional[str] = None,
    checkpoint_interval: int = 1,
    verbose_eval: Any = False,
    callbacks: Optional[Sequence[TrainingCallback]] = None,
) -> Booster:
    """Fault-tolerant multi-host training: worker loss shrinks the world
    and replays from the newest verified checkpoint instead of aborting
    the job (ROADMAP item 1; the reference's rabit LoadCheckPoint story
    at the whole-cluster level). See docs/distributed.md, "Elastic
    training" for the state machine and its guarantees.

    ``data_fn(rank, world) -> DMatrix`` is the re-shardable ingestion
    hook — the ``load_row_split`` contract: called again at every world
    size, it returns that rank's row shard. For bit-exact replay, shards
    must be CONTIGUOUS BLOCKS of one fixed global row order (process-rank
    concatenation then preserves the global order across resizes).

    ``run_dir`` is a directory shared by all workers (local disk on one
    host, NFS on a pod) holding the membership heartbeats, the canonical
    cuts manifest, the generation state and the shared checkpoints.
    ``coordinator`` is ``host:basePort``; generation g rendezvouses on
    ``basePort + g`` (default: localhost, for single-host tests).

    The state machine per worker: TRAIN -> (peer death detected by
    heartbeat silence or a failed collective) -> QUIESCE at a round
    boundary (commit the last consistent rounds) -> RESIZE (tombstone the
    dead, agree on the survivor set, re-form the runtime at the new
    size — in-process when shrinking to one worker, by process restart
    when several survive or when the coordinator died) -> REPLAY (rebin
    against the canonical cuts, ``train(resume_from=...)`` from the
    newest verified checkpoint) -> TRAIN.
    """
    from .observability.metrics import REGISTRY
    from .observability import flight as _flight
    from .observability import trace as _trace
    from .parallel.membership import Membership, WorkerLost, hb_deadline
    from .parallel.mesh import mesh_context
    from .resilience import checkpoint as _ckpt, policy as _policy
    from .utils import console_logger

    os.makedirs(run_dir, exist_ok=True)
    # the fleet black box: per-round records + metrics + trace persist
    # under run_dir/obs/rank<base_rank>/ from here on (obs-report merges
    # them across ranks — docs/observability.md)
    _flight.configure(run_dir, rank=int(rank))
    ckpt_dir = os.path.join(run_dir, "checkpoints")
    member_dir = os.path.join(run_dir, "members")
    gen_path = os.path.join(run_dir, "generation.json")
    max_bin = int(params.get("max_bin", 256))
    base_rank = int(rank)
    host, _, base_port = (coordinator or "localhost:29950").rpartition(":")
    base_port = int(base_port)

    state = _read_json(gen_path) or {
        "generation": 0, "members": list(range(world)),
        "attempted_round": 0,
    }
    env_gen = int(os.environ.get(_GEN_ENV, state["generation"]))
    if env_gen > state["generation"]:
        # restarted ahead of the generation writer (the lowest survivor
        # commits generation.json just before its own restart): wait for
        # the membership agreement to land rather than racing it
        import time

        from .resilience.watchdog import watchdog as _wd_ctx

        with _wd_ctx("elastic_generation", seconds=300.0):
            while state["generation"] < env_gen:
                time.sleep(0.1)
                state = _read_json(gen_path) or state
    gen = max(env_gen, state["generation"])

    cuts = None
    while True:
        members = [m for m in state["members"]]
        if base_rank not in members:
            raise WorkerLost([base_rank])  # fenced before we even started
        world_g = len(members)
        rank_g = members.index(base_rank)
        _trace.instant("elastic_generation", generation=gen,
                       world=world_g, rank=rank_g)
        # stamp the generation on every round record from here on: the
        # fleet table keys (gen, round), so replayed rounds after a
        # resize land in their own entries instead of overwriting gen 0's
        _flight.RECORDER.set_generation(gen)
        mesh = None
        if world_g > 1:
            from .parallel.mesh import init_distributed

            mesh = init_distributed(
                coordinator_address=f"{host}:{base_port + gen}",
                num_processes=world_g, process_id=rank_g, elastic=True)
        # membership starts immediately after the rendezvous barrier (the
        # one moment all ranks are synchronized) — BEFORE the cuts/data
        # work, whose duration varies per rank and must not read as
        # heartbeat silence
        membership = Membership(member_dir, base_rank, members,
                                generation=gen).start()
        if cuts is None:
            cuts = _canonical_cuts(run_dir, data_fn, max_bin, rank_g,
                                   list(range(world_g)))
        dtrain = _bin_with_cuts(data_fn(rank_g, world_g), cuts, max_bin)

        # replay accounting: rounds the previous generation had reached
        # beyond what the checkpoint preserves get re-trained now (header
        # verification only — train() re-reads the payload anyway)
        resumed = 0
        for p in reversed(_ckpt.list_checkpoints(ckpt_dir)):
            ok, _, rounds = _ckpt.verify_checkpoint(p)
            if ok:
                resumed = rounds
                break
        replayed = max(0, int(state.get("attempted_round", 0)) - resumed)
        if gen > 0:
            REGISTRY.counter(
                "elastic_resume_rounds_replayed",
                "Rounds re-trained after elastic resizes").inc(replayed)
            _trace.instant("elastic_replay", generation=gen,
                           resumed=resumed, replayed=replayed)
            _flight.RECORDER.event("elastic_replay", generation=gen,
                                   resumed=resumed, replayed=replayed)

        try:
            import contextlib

            ctx = mesh_context(mesh) if mesh is not None \
                else contextlib.nullcontext()
            with ctx:
                bst = train(
                    params, dtrain, num_boost_round,
                    verbose_eval=verbose_eval,
                    callbacks=[_ElasticGuard(membership)]
                    + (list(callbacks) if callbacks else []),
                    resume_from=ckpt_dir,
                    checkpoint_interval=checkpoint_interval,
                    checkpoint_shared=True,
                )
            membership.stop()
            # elastic workers leave via elastic_exit (os._exit — no
            # atexit): flush the black box and trace NOW or lose them
            _flight.RECORDER.dump("elastic_complete")
            if _trace.enabled():
                _trace.flush()
            return bst
        except BaseException as e:
            # NOTE: the heartbeat agent keeps beating through this whole
            # block — we are alive, and stopping it before the resize
            # decision would make simultaneous survivors read each other
            # as silent and mutually fence (observed, not hypothetical)
            dead: List[int] = []
            # rounds attempted so far: a WorkerLost from the guard fires
            # BEFORE its round runs; a broken collective means the
            # guard's last round was in flight (and will be replayed)
            at_round = int(state.get("attempted_round", 0))
            if isinstance(e, WorkerLost):
                dead = e.ranks
                at_round = max(at_round, max(e.round, 0))
            else:
                suspects = [m for m in members if m != base_rank]
                if _policy.is_worker_loss(e):
                    # a broken collective: corroborate against the
                    # heartbeat stream before shrinking — a transient
                    # network fault must not cost a healthy worker its
                    # shard
                    dead = membership.wait_dead(
                        suspects, timeout=2 * hb_deadline())
                else:
                    # peer loss without a TCP reset (a wedged collective
                    # aborted by the watchdog, an opaque runtime error):
                    # the signature says nothing, but the heartbeat
                    # stream may already know — resize if membership has
                    # declared a peer dead, re-raise otherwise
                    dead = [r for r in membership.scan()
                            if r in suspects]
                if not dead:
                    membership.stop()
                    raise
                at_round = max(at_round, membership.round + 1)
            if base_rank in dead or membership.fenced:
                membership.stop()
                console_logger.warning(
                    f"elastic: rank {base_rank} fenced (tombstoned by a "
                    "peer); exiting rather than split-braining the run")
                raise WorkerLost([base_rank]) from e
            _policy.record_failure("elastic_resize", e)
            # QUIESCE committed its rounds in train()'s abort handler;
            # mark the transition on both the trace and the flight stream
            # (detection -> quiesce -> resize -> replay, obs-report's
            # instant sequence)
            _trace.instant("elastic_quiesce", generation=gen,
                           at_round=at_round, dead=repr(dead))
            _flight.RECORDER.event("elastic_quiesce", generation=gen,
                                   at_round=at_round, dead=repr(dead))
            _flight.RECORDER.dump("elastic_quiesce")
            for r in dead:
                membership.declare_dead(r)
            survivors = [m for m in members if m not in dead]
            # audit trail: preserve the exact snapshot this resize will
            # replay from (retention in the live dir prunes it later) —
            # run_dir/quiesce/gen<g>_ckpt_<rounds>.ckpt
            try:
                import shutil

                for p in reversed(_ckpt.list_checkpoints(ckpt_dir)):
                    if _ckpt.verify_checkpoint(p)[0]:
                        qdir = os.path.join(run_dir, "quiesce")
                        os.makedirs(qdir, exist_ok=True)
                        shutil.copy(p, os.path.join(
                            qdir, f"gen{gen}_{os.path.basename(p)}"))
                        break
            except OSError:
                pass  # the audit copy is best effort, never blocks resize
            gen += 1
            state = {"generation": gen, "members": survivors,
                     "attempted_round": at_round}
            if base_rank == min(survivors):
                _atomic_json(gen_path, state)
            REGISTRY.counter(
                "worker_restarts_total",
                "Training restarts caused by elastic resizes").inc()
            _trace.instant("elastic_resize", generation=gen,
                           dead=repr(dead), world=len(survivors))
            _flight.RECORDER.event("elastic_resize", generation=gen,
                                   dead=repr(dead), world=len(survivors))
            console_logger.warning(
                f"elastic: lost rank(s) {dead}; resizing world "
                f"{len(members)} -> {len(survivors)} (generation {gen}), "
                f"replaying from the newest verified checkpoint")
            membership.stop()
            if len(survivors) == 1:
                # shrink-to-one completes in-process: drop the mesh, keep
                # the (deaf) runtime alive, train locally on the full
                # re-shard — no new rendezvous needed
                continue
            # several survivors: the runtime cannot re-form a smaller
            # world in-process (coordination service lifecycle) — restart
            # this worker image in place; all state is in run_dir
            import sys

            os.environ[_GEN_ENV] = str(gen)
            console_logger.warning(
                f"elastic: re-executing worker for generation {gen} "
                f"(world {len(survivors)})")
            if _trace.enabled():  # execv skips atexit: flush the timeline
                _trace.flush()
            sys.stdout.flush()
            sys.stderr.flush()
            os.execv(sys.executable, [sys.executable] + sys.argv)


def elastic_exit(code: int = 0) -> None:
    """Exit an elastic worker process without tripping the distributed
    runtime's exit-time shutdown barrier (after a peer death the barrier
    can never complete; the stock runtime turns that into a process
    abort). Flushes stdio, then ``os._exit`` — call this LAST, after
    models/metrics are saved."""
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def _make_folds(
    dtrain: DMatrix,
    nfold: int,
    params: Dict[str, Any],
    seed: int,
    stratified: bool,
    folds,
    shuffle: bool = True,
):
    n = dtrain.num_row()
    rng = np.random.RandomState(seed)
    if folds is not None:
        splits = folds if not hasattr(folds, "split") else list(
            folds.split(X=np.zeros(n), y=dtrain.get_label())
        )
    else:
        idx = np.arange(n)
        if shuffle:
            rng.shuffle(idx)
        if stratified and dtrain.info.label is not None:
            label = dtrain.get_label()[idx]
            order = np.argsort(label, kind="stable")
            idx = idx[order]  # interleave classes across folds
            fold_of = np.arange(n) % nfold
        else:
            fold_of = np.repeat(np.arange(nfold), int(np.ceil(n / nfold)))[:n]
        splits = []
        for k in range(nfold):
            test = idx[fold_of == k]
            trainix = idx[fold_of != k]
            splits.append((trainix, test))
    out = []
    for trainix, testix in splits:
        dtr = dtrain.slice(np.asarray(trainix))
        dte = dtrain.slice(np.asarray(testix))
        out.append((dtr, dte))
    return out


def cv(
    params: Dict[str, Any],
    dtrain: DMatrix,
    num_boost_round: int = 10,
    nfold: int = 3,
    stratified: bool = False,
    folds=None,
    metrics: Sequence[str] = (),
    obj=None,
    feval=None,
    maximize: Optional[bool] = None,
    early_stopping_rounds: Optional[int] = None,
    fpreproc=None,
    as_pandas: bool = True,
    verbose_eval: Any = None,
    show_stdv: bool = True,
    seed: int = 0,
    callbacks: Optional[Sequence[TrainingCallback]] = None,
    shuffle: bool = True,
    custom_metric=None,
):
    """K-fold cross-validation (reference training.py:189-459)."""
    params = dict(params)
    if isinstance(metrics, str):
        metrics = [metrics]
    if metrics:
        params["eval_metric"] = list(metrics)
    folds_data = _make_folds(dtrain, nfold, params, seed, stratified, folds, shuffle)
    cvpacks = []
    for dtr, dte in folds_data:
        p = params
        if fpreproc is not None:
            dtr, dte, p = fpreproc(dtr, dte, dict(params))
        cvpacks.append((Booster(p, cache=[dtr, dte]), dtr, dte))

    feval = custom_metric if custom_metric is not None else feval
    history: Dict[str, List[float]] = {}
    rounds_done = 0
    best_iteration = None
    es_state = {"best": None, "rounds": 0}

    results_per_round: List[Dict[str, Tuple[float, float]]] = []
    for i in range(num_boost_round):
        round_scores: Dict[str, List[float]] = {}
        for bst, dtr, dte in cvpacks:
            bst.update(dtr, i, fobj=obj)
            msg = bst.eval_set([(dtr, "train"), (dte, "test")], i, feval=feval)
            for tok in msg.split("\t")[1:]:
                nm, _, val = tok.rpartition(":")
                round_scores.setdefault(nm, []).append(float(val))
        agg = {k: (float(np.mean(v)), float(np.std(v))) for k, v in round_scores.items()}
        results_per_round.append(agg)
        rounds_done = i + 1
        for k, (m, s) in agg.items():
            history.setdefault(f"{k}-mean", []).append(m)
            history.setdefault(f"{k}-std", []).append(s)
        if verbose_eval:
            line = f"[{i}]\t" + "\t".join(
                f"{k}:{m:.5f}" + (f"+{s:.5f}" if show_stdv else "")
                for k, (m, s) in agg.items()
            )
            print(line, flush=True)
        if early_stopping_rounds is not None:
            test_keys = [k for k in agg if k.startswith("test-")]
            if test_keys:
                key = test_keys[-1]
                score = agg[key][0]
                base = key[len("test-"):].split("@")[0]
                is_max = (
                    maximize
                    if maximize is not None
                    else base in EarlyStopping._MAXIMIZE_METRICS
                )
                best = es_state["best"]
                improved = (
                    best is None
                    or (is_max and score > best)
                    or (not is_max and score < best)
                )
                if improved:
                    es_state["best"] = score
                    es_state["rounds"] = 0
                    best_iteration = i
                else:
                    es_state["rounds"] += 1
                    if es_state["rounds"] >= early_stopping_rounds:
                        break
    if early_stopping_rounds is not None and best_iteration is not None:
        for k in history:
            history[k] = history[k][: best_iteration + 1]
    if as_pandas:
        try:
            import pandas as pd

            return pd.DataFrame(history)
        except ImportError:
            pass
    return history
