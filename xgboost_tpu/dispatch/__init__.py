"""Backend-neutral kernel dispatch: one registry routing every op.

Public surface (see ``core.py`` for the design notes):

- :func:`resolve` / :class:`Ctx` / :class:`Decision` /
  :class:`DispatchError` — the lookup; :func:`note` counts a route a call
  site took from its own input, beside the resolutions.
- :func:`register` / :class:`KernelImpl` — add an impl (a GPU backend is
  a table entry).
- :func:`pinned_off` / :func:`degraded` / :data:`LEGACY_ENVS` —
  compat/admission reads and the old kill-switch grammar.
- :func:`explain` / :func:`last_decisions` / :func:`table_snapshot` /
  :func:`op_names` / :func:`set_report_ctx` — the report CLI, the
  benchmark's printed routes and the flight black box.
- :func:`reset` — drop cached decisions and route history (tests).
"""

from .core import (  # noqa: F401
    Ctx,
    Decision,
    DispatchError,
    KernelImpl,
    LEGACY_ENVS,
    degraded,
    explain,
    last_decisions,
    note,
    op_names,
    pinned_off,
    register,
    reset,
    resolve,
    set_report_ctx,
    table_snapshot,
)

__all__ = [
    "Ctx", "Decision", "DispatchError", "KernelImpl", "LEGACY_ENVS",
    "degraded", "explain", "last_decisions", "note", "op_names",
    "pinned_off", "register", "reset", "resolve", "set_report_ctx",
    "table_snapshot",
]
