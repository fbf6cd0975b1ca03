"""Per-round flight recorder: the fleet's always-on black box.

PR 1's telemetry answers "where did the milliseconds go" only when a
trace destination is configured; since the system became an elastic
multi-host fleet (PR 5), its most interesting events — worker death,
quiesce, resize, replay, a watchdog abort — need a record that exists
*by default* and survives the process dying mid-round. This module is
that record (the reference's Timer/Monitor + TrainingObserver tier,
PAPER.md layer 2, scaled to the rabit-style multi-worker setting):

- **Always-on ring buffer** of per-round records: round wall time,
  host-blocked dispatch time, eval/checkpoint/sketch stage times,
  retrace count delta (from ``analysis.retrace``'s guard), collective
  ops/bytes delta (from ``observability.comms``'s counters), host RSS
  and device-memory watermarks. Recording costs a few dict ops plus two
  clock reads per round (pinned ≤ 2% of a small-bench round by
  ``tests/test_flight.py``); ``XGBTPU_FLIGHT=0`` disables it outright.
- **Durable sink** (``configure(run_dir, rank)``): each rank appends
  every completed record as one JSON line to
  ``run_dir/obs/rank<k>/flight.jsonl`` (line-buffered — a SIGKILL loses
  at most the in-flight round), refreshes ``metrics.json`` (the full
  registry snapshot) and keeps the span trace flowing to
  ``trace.jsonl`` with a recorded clock base (``clock.json``) so
  ``python -m xgboost_tpu obs-report`` can merge ranks onto one
  clock-aligned timeline (``observability/fleet.py``).
- **Black-box dump** (``RECORDER.dump(reason)``): the full ring plus
  registry snapshot written atomically to ``blackbox.json`` — fired on
  any training abort (``training.py``), on ``WatchdogTimeout`` expiry
  (``resilience/watchdog.py``) and at elastic quiesce/completion.
- **Profiling window**: ``XGBTPU_PROFILE=<dir>`` captures a
  ``jax.profiler`` device trace for the first ``XGBTPU_PROFILE_ROUNDS``
  (default 5) boosting rounds — the heavyweight device-side complement
  to the always-on host-side records.

Live queries go through :class:`~xgboost_tpu.callback.FlightRecorderMonitor`
(a training callback handing each completed record to user code) or
directly: ``flight.RECORDER.last()`` / ``.records()``.

File formats (all parseable line-wise, ``docs/observability.md``):

- ``flight.jsonl`` — first line ``{"t": "meta", ...}`` (rank, pid,
  clock base), then ``{"t": "round", ...}`` / ``{"t": "event", ...}``
  records, one per line;
- ``blackbox.json`` — one JSON object: meta + ``records`` + ``metrics``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import trace as _trace
from .metrics import REGISTRY

__all__ = [
    "FlightRecorder", "RECORDER", "enabled", "note", "configure",
    "stage_totals", "profile_tick", "profile_stop", "atomic_write_json",
]

_ENV_FLIGHT = "XGBTPU_FLIGHT"
_ENV_BUFFER = "XGBTPU_FLIGHT_BUFFER"
_ENV_PROFILE = "XGBTPU_PROFILE"
_ENV_PROFILE_ROUNDS = "XGBTPU_PROFILE_ROUNDS"

FORMAT = "xgbtpu-flight-v1"

_ROUND_SECONDS_HELP = "Wall time per boosting round (flight recorder)"


def enabled() -> bool:
    """Whether recording is on (``XGBTPU_FLIGHT=0`` turns it off)."""
    return os.environ.get(_ENV_FLIGHT) != "0"


_enabled = enabled


def _rank() -> int:
    """This process's rank, without initializing a backend (same guarded
    read as ``trace._rank_world``)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        return int(jax.process_index())
    except Exception:
        return 0


def atomic_write_json(path: str, doc: Dict[str, Any]) -> bool:
    """Replace-write ``doc`` as JSON (tmp + rename; no fsync — black-box
    artifacts tolerate losing the very last dump on power cut). Shared by
    the training black box here and the serving flight recorder
    (``serving/obs.py``). Best effort: returns False instead of raising,
    because a dump must never mask the abort it documents."""
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return True
    except (OSError, ValueError, TypeError):
        return False


def _rss_peak_mb() -> float:
    """Host peak RSS in MB (``ru_maxrss`` is KB on Linux — one cheap
    syscall, no /proc parse)."""
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    except Exception:
        return 0.0


class FlightRecorder:
    """Ring buffer of per-round records plus the durable sink. One
    process-wide instance (``RECORDER``); all methods are thread-safe
    (membership/degrade events arrive from monitor threads)."""

    def __init__(self, maxlen: Optional[int] = None) -> None:
        if maxlen is None:
            try:
                maxlen = int(os.environ.get(_ENV_BUFFER, "4096") or 4096)
            except ValueError:
                maxlen = 4096
        self._lock = threading.RLock()
        self._ring: "deque[Dict[str, Any]]" = deque(maxlen=max(maxlen, 16))
        self._open: Optional[Dict[str, Any]] = None
        self._depth = 0  # nested begin_round (update -> update_many)
        self._generation = 0  # elastic generation (set_generation)
        self._t0 = 0.0
        # cumulative per-stage seconds for the whole process (bench's
        # per-stage breakdown reads deltas of this — includes stage time
        # spent outside any round, e.g. the initial sketch)
        self._stage_totals: Dict[str, float] = {}
        # deltas are computed against the previous round's absolute totals
        self._last_retraces = 0
        self._last_coll = (0.0, 0.0)
        # sink state (configure)
        self._dir: Optional[str] = None
        self._rank: Optional[int] = None
        self._file = None
        self._dev_mem_ok: Optional[bool] = None  # probe once

    # ------------------------------------------------------------------
    # deltas / watermarks
    # ------------------------------------------------------------------
    def _retrace_total(self) -> int:
        from ..analysis.retrace import retrace_counts

        return sum(retrace_counts().values())

    def _coll_totals(self) -> tuple:
        ops = by = 0.0
        for name in ("collective_ops_total", "collective_bytes_total"):
            fam = REGISTRY.get(name)
            if fam is None:
                continue
            total = sum(child.value for _, child in fam.series())
            if name.endswith("ops_total"):
                ops = total
            else:
                by = total
        return ops, by

    def _dev_peak_mb(self) -> Optional[float]:
        if self._dev_mem_ok is False:
            return None
        try:
            jax = sys.modules.get("jax")
            if jax is None:
                raise RuntimeError("jax not imported")
            stats = jax.local_devices()[0].memory_stats()
            peak = (stats or {}).get("peak_bytes_in_use")
            if peak is None:
                raise RuntimeError("no peak_bytes_in_use")
            self._dev_mem_ok = True
            return peak / (1024.0 * 1024.0)
        except Exception:
            self._dev_mem_ok = False
            return None

    # ------------------------------------------------------------------
    # round lifecycle (the training loop's three calls)
    # ------------------------------------------------------------------
    def set_generation(self, generation: int) -> None:
        """The elastic generation stamped on subsequent round records
        (``elastic_train`` bumps it at every resize, so the fleet table
        can key replayed rounds as (gen, round))."""
        with self._lock:
            self._generation = int(generation)

    def begin_round(self, round_idx: int, rounds: int = 1,
                    generation: Optional[int] = None) -> bool:
        """Open a round record. Returns True when THIS call owns the
        record — a nested begin (``update`` routing through
        ``update_many`` under a mesh) returns False, and the nested
        caller must then skip its own stage notes for work the owner
        already times (else ``stages.grow`` double-counts)."""
        if not _enabled():
            return False
        with self._lock:
            if self._open is not None:  # nested (update -> update_many)
                self._depth += 1
                return False
            if self._dir is None:
                env = os.environ.get(_ENV_FLIGHT)
                if env and env not in ("0", "1"):
                    self._configure_locked(env, None)
            self._t0 = time.perf_counter()
            self._open = {
                "t": "round", "round": int(round_idx), "rounds": int(rounds),
                "gen": int(self._generation if generation is None
                           else generation),
                "unix_ms": time.time() * 1e3,
                "stages": {},
            }
            return True

    def note(self, stage: str, seconds: float) -> None:
        """Charge ``seconds`` of wall time to ``stage`` (``grow`` /
        ``eval`` / ``checkpoint`` / ``sketch`` / ...) — accumulated into
        the open round record (if any) AND the process-lifetime stage
        totals (``stage_totals``, the bench breakdown's source)."""
        if not _enabled():
            return
        with self._lock:
            self._stage_totals[stage] = (
                self._stage_totals.get(stage, 0.0) + seconds)
            if self._open is not None:
                st = self._open["stages"]
                st[stage] = st.get(stage, 0.0) + seconds

    def end_round(self) -> Optional[Dict[str, Any]]:
        if not _enabled():
            return None
        with self._lock:
            if self._depth:
                self._depth -= 1
                return None
            rec = self._open
            if rec is None:
                return None
            self._open = None
            wall = time.perf_counter() - self._t0
            rec["wall_s"] = round(wall, 6)
            rec["stages"] = {k: round(v, 6)
                             for k, v in rec["stages"].items()}
            try:
                rt = self._retrace_total()
                rec["retraces"] = rt - self._last_retraces
                self._last_retraces = rt
            except Exception:
                rec["retraces"] = -1
            ops, by = self._coll_totals()
            rec["coll_ops"] = ops - self._last_coll[0]
            rec["coll_bytes"] = by - self._last_coll[1]
            self._last_coll = (ops, by)
            rec["rss_peak_mb"] = round(_rss_peak_mb(), 1)
            dev = self._dev_peak_mb()
            if dev is not None:
                rec["dev_peak_mb"] = round(dev, 1)
            self._ring.append(rec)
            self._write_line(rec)
        REGISTRY.histogram(
            "round_seconds", _ROUND_SECONDS_HELP).observe(wall)
        if self._dir is not None:
            self._refresh_sidecars()
        return rec

    def event(self, name: str, **args: Any) -> None:
        """A fleet event (worker death, degrade transition, quiesce,
        watchdog abort): recorded in the ring + sink; ``obs-report``
        renders these as instants on the merged timeline."""
        if not _enabled():
            return
        rec = {"t": "event", "name": name,
               "unix_ms": time.time() * 1e3}
        if args:
            rec["args"] = {k: v for k, v in args.items()}
        with self._lock:
            self._ring.append(rec)
            self._write_line(rec)

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------
    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def last(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            for rec in reversed(self._ring):
                if rec.get("t") == "round":
                    return rec
            return None

    def stage_totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._stage_totals)

    @property
    def run_dir(self) -> Optional[str]:
        with self._lock:
            return self._dir

    # ------------------------------------------------------------------
    # sink
    # ------------------------------------------------------------------
    def configure(self, run_dir: str, rank: Optional[int] = None) -> str:
        """Attach the durable sink at ``run_dir/obs/rank<k>/``. First
        caller wins (``elastic_train`` configures before ``train``'s
        ``resume_from`` fallback would); returns the rank directory."""
        with self._lock:
            if self._dir is None:
                self._configure_locked(run_dir, rank)
            return self._dir  # type: ignore[return-value]

    def _configure_locked(self, run_dir: str, rank: Optional[int]) -> None:
        rank = _rank() if rank is None else int(rank)
        d = os.path.join(run_dir, "obs", f"rank{rank}")
        try:
            os.makedirs(d, exist_ok=True)
            self._file = open(os.path.join(d, "flight.jsonl"), "a")
        except OSError:
            self._file = None
            return
        self._dir = d
        self._rank = rank
        meta = {
            "t": "meta", "format": FORMAT, "rank": rank,
            "pid": os.getpid(), "unix_ms": time.time() * 1e3,
            "clock": _trace.clock_base(),
        }
        self._write_line(meta)
        try:
            with open(os.path.join(d, "clock.json"), "w") as f:
                json.dump(_trace.clock_base(), f)
        except OSError:
            pass
        # keep the span trace flowing into the same rank directory (a
        # user-set XGBTPU_TRACE / set_config destination still wins)
        _trace.set_sink(os.path.join(d, "trace.jsonl"))

    def _write_line(self, rec: Dict[str, Any]) -> None:
        if self._file is None:
            return
        try:
            self._file.write(json.dumps(rec) + "\n")
            self._file.flush()
        except (OSError, ValueError):
            pass

    def _refresh_sidecars(self) -> None:
        """Refresh ``metrics.json`` + flush the trace ring so a SIGKILL
        between rounds leaves current sidecars on disk. Plain
        replace-write (no fsync): this runs every round and the previous
        snapshot is an acceptable loss on power cut."""
        d = self._dir
        if d is None:
            return
        try:
            tmp = os.path.join(d, f".metrics.tmp.{os.getpid()}")
            with open(tmp, "w") as f:
                json.dump(REGISTRY.snapshot(), f)
            os.replace(tmp, os.path.join(d, "metrics.json"))
        except (OSError, ValueError):
            pass
        try:
            if _trace.enabled():
                _trace.flush()
        except Exception:
            pass

    # ------------------------------------------------------------------
    # black box
    # ------------------------------------------------------------------
    def dump(self, reason: str, path: Optional[str] = None) -> Optional[str]:
        """Write the full ring + registry snapshot as one atomic JSON
        file (``blackbox.json`` in the rank's obs directory unless
        ``path`` is given). Best effort — a dump must never mask the
        abort it documents. Returns the written path, or None when no
        sink is configured and no path was given."""
        if not _enabled():
            return None
        with self._lock:
            if path is None:
                if self._dir is None:
                    return None
                path = os.path.join(self._dir, "blackbox.json")
            doc = {
                "format": FORMAT, "reason": reason,
                "rank": self._rank if self._rank is not None else _rank(),
                "pid": os.getpid(), "unix_ms": time.time() * 1e3,
                "clock": _trace.clock_base(),
                "stage_totals_s": {k: round(v, 6) for k, v
                                   in self._stage_totals.items()},
                "records": list(self._ring),
            }
        try:
            doc["metrics"] = REGISTRY.snapshot()
        except Exception:
            doc["metrics"] = {}
        try:
            # the resolved kernel routing table: which impl served each
            # op when the box was dumped (attributes a perf/fault record
            # to its route — dispatch/core.py)
            from .. import dispatch

            doc["dispatch"] = dispatch.table_snapshot()
        except Exception:
            doc["dispatch"] = {}
        if not atomic_write_json(path, doc):
            return None
        self._refresh_sidecars()
        return path

    def abort_dump(self, exc: BaseException) -> None:
        """The training loop's abort hook: record the abort as an event,
        then dump the black box — both best effort."""
        try:
            self.event("train_abort", error=type(exc).__name__,
                       detail=str(exc)[:200])
            self.dump(f"abort:{type(exc).__name__}")
        except Exception:
            pass

    # ------------------------------------------------------------------
    def reset(self) -> None:
        """Tests: drop records/totals, detach the sink, release the trace
        sink override."""
        with self._lock:
            self._ring.clear()
            self._open = None
            self._depth = 0
            self._generation = 0
            self._stage_totals.clear()
            self._last_retraces = 0
            self._last_coll = (0.0, 0.0)
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
            self._file = None
            self._dir = None
            self._rank = None
        _trace.set_sink(None)


RECORDER = FlightRecorder()


def note(stage: str, seconds: float) -> None:
    RECORDER.note(stage, seconds)


def configure(run_dir: str, rank: Optional[int] = None) -> str:
    return RECORDER.configure(run_dir, rank)


def stage_totals() -> Dict[str, float]:
    return RECORDER.stage_totals()


# ---------------------------------------------------------------------------
# profiling window: XGBTPU_PROFILE=<dir> captures a jax.profiler device
# trace for the first XGBTPU_PROFILE_ROUNDS rounds of the next train loop
# ---------------------------------------------------------------------------

_prof_lock = threading.RLock()  # reentrant: _stop_locked re-enters
_prof_state = {"active": False, "stop_after": -1, "used": False}


def profile_tick(round_idx: int) -> None:
    """Called at each round boundary by the training loop. Starts the
    profiler window on the first tick (once per process), stops it after
    ``XGBTPU_PROFILE_ROUNDS`` rounds. Never raises into training."""
    directory = os.environ.get(_ENV_PROFILE)
    if not directory:
        return
    with _prof_lock:
        if _prof_state["active"]:
            if round_idx >= _prof_state["stop_after"]:
                _stop_locked()
            return
        if _prof_state["used"]:
            return
        try:
            rounds = max(1, int(os.environ.get(_ENV_PROFILE_ROUNDS, "5")))
        except ValueError:
            rounds = 5
        try:
            import jax

            os.makedirs(directory, exist_ok=True)
            jax.profiler.start_trace(directory)
        except Exception as e:
            from ..utils import console_logger

            console_logger.warning(f"flight: profiler window failed to "
                                   f"start ({e}); continuing unprofiled")
            _prof_state["used"] = True
            return
        _prof_state["active"] = True
        _prof_state["used"] = True
        _prof_state["stop_after"] = round_idx + rounds
        _trace.instant("profile_window_start", dir=directory, rounds=rounds)


def _stop_locked() -> None:
    try:
        import jax

        jax.profiler.stop_trace()
        from ..utils import console_logger

        console_logger.info(
            f"flight: jax.profiler window captured into "
            f"{os.environ.get(_ENV_PROFILE)}")
    except Exception:
        pass
    with _prof_lock:  # re-entrant: callers already hold it
        _prof_state["active"] = False
    _trace.instant("profile_window_stop")


def profile_stop() -> None:
    """Close a still-open window (train-loop ``finally``): a profile of
    fewer rounds beats a corrupt unterminated capture."""
    with _prof_lock:
        if _prof_state["active"]:
            _stop_locked()


def profile_reset() -> None:
    """Tests: allow another window in the same process."""
    with _prof_lock:
        if _prof_state["active"]:
            _stop_locked()
        _prof_state["used"] = False
        _prof_state["stop_after"] = -1
