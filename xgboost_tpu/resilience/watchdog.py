"""Deadline watchdog: abort a wedged host dispatch cleanly instead of
hanging the run.

The failure mode this exists for: a blocking call (collective init /
first dispatch) that never returns — no exception, no progress, an
outside kill the only exit.
``watchdog(site, seconds)`` arms a daemon timer around the guarded block;
on expiry it records ``watchdog_timeouts_total{site}``, runs the caller's
``on_timeout`` callback (best effort — e.g. a trace flush), then
interrupts the main thread so the block raises ``WatchdogTimeout`` —
letting ``train()`` commit a checkpoint and exit with a real error.

Honest limitation: ``_thread.interrupt_main`` is delivered between Python
bytecodes. A dispatch wedged inside a C extension that never returns to
the interpreter cannot be interrupted this way — for that terminal case
a process-level deadline (the caller's ``timeout``) remains the backstop.
Everything short of that (polling loops, host-side retries, collective
setup written in Python) aborts cleanly.

Deadlines come from ``XGBTPU_WATCHDOG`` (bare seconds, or
``site=S,*=S`` — the shared env grammar) or the call site's default;
0 / unset means no watchdog. Only the main thread can be guarded (the
interrupt targets it); elsewhere the context manager is a no-op.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Iterator, Optional

__all__ = ["WatchdogTimeout", "watchdog", "deadline_for"]

_ENV = "XGBTPU_WATCHDOG"


class WatchdogTimeout(RuntimeError):
    """A watchdogged block exceeded its deadline."""

    def __init__(self, site: str, seconds: float):
        super().__init__(
            f"watchdog: {site!r} exceeded its {seconds:g}s deadline "
            f"({_ENV}); aborting instead of wedging")
        self.site = site
        self.seconds = seconds


def deadline_for(site: str, default: Optional[float] = None
                 ) -> Optional[float]:
    """Deadline seconds for ``site`` per ``XGBTPU_WATCHDOG`` (bare float
    or ``site=S,*=S``), else ``default``. <= 0 disables."""
    raw = os.environ.get(_ENV)
    if not raw:
        return default
    fallback = default
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
        else:
            k, v = "*", part
        try:
            fv = float(v)
        except ValueError:
            continue  # malformed env must never break training
        if k == site:
            return fv
        if k == "*":
            fallback = fv
    return fallback


@contextlib.contextmanager
def watchdog(site: str, seconds: Optional[float] = None,
             on_timeout: Optional[Callable[[], None]] = None
             ) -> Iterator[None]:
    """Guard the enclosed block with a ``seconds`` deadline (default: the
    env deadline for ``site``). Raises ``WatchdogTimeout`` when it expires."""
    if seconds is None:
        seconds = deadline_for(site)
    if (not seconds or seconds <= 0
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    fired = threading.Event()
    handled = threading.Event()

    def _expire() -> None:
        import _thread

        # interrupt IMMEDIATELY after setting the flag: any work between
        # the two widens the race where the guarded block exits, the
        # finally's absorb-sleep expires, and the pending interrupt lands
        # at an arbitrary later point (e.g. inside an abort handler)
        fired.set()
        _thread.interrupt_main()
        try:  # best-effort telemetry AFTER the abort is in flight
            from ..observability.metrics import REGISTRY
            from ..observability import flight, trace
            from ..utils import console_logger

            REGISTRY.counter(
                "watchdog_timeouts_total",
                "Deadline expiries by watchdogged site",
            ).labels(site=site).inc()
            trace.instant("watchdog_timeout", site=site, seconds=seconds)
            # black-box dump from THIS thread: the main thread may be too
            # wedged to ever reach train()'s abort handler
            flight.RECORDER.event("watchdog_timeout", site=site,
                                  seconds=seconds)
            flight.RECORDER.dump(f"watchdog:{site}")
            console_logger.warning(
                f"watchdog: {site!r} still running after {seconds:g}s — "
                "interrupting the main thread")
            if on_timeout is not None:
                on_timeout()
        except Exception:
            pass
        finally:
            handled.set()

    timer = threading.Timer(seconds, _expire)
    timer.daemon = True
    timer.start()
    try:
        yield
    except KeyboardInterrupt:
        if fired.is_set():
            # wait for the expiry thread's telemetry/on_timeout to finish
            # so callers observe a fully-recorded timeout
            handled.wait(5.0)
            raise WatchdogTimeout(site, seconds) from None
        raise  # a real Ctrl-C stays a Ctrl-C
    finally:
        timer.cancel()
        if fired.is_set():
            # the timer fired but the interrupt may not have landed yet
            # (the block finished in the race window): give the pending
            # KeyboardInterrupt a bytecode boundary to arrive at, swallow
            # it, and surface the timeout deterministically below
            try:
                time.sleep(0.05)
            except KeyboardInterrupt:
                pass
    if fired.is_set():
        handled.wait(5.0)
        raise WatchdogTimeout(site, seconds)
