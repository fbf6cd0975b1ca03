"""Read a ``jax.profiler`` trace (``*.xplane.pb``) into a plain event table.

Needs nothing but JAX (``jax.profiler.ProfileData``). What one trace of this
program on a TPU v5e holds (looked at by hand, PR 22): a plane
``/device:TPU:<i>`` per chip with the lines ``XLA Modules`` (one event per
executed program: the device is busy exactly then), ``XLA Ops`` (one event
per HLO instruction, nested: a ``while`` spans its body's ops) and ``Async
XLA Ops`` (copies in flight); a plane ``/host:CPU`` with one line per
thread, on the same clock, where ``jax.profiler.TraceAnnotation`` spans
appear under their names. An op event's name is the whole HLO instruction:
``%_hoisted_level_pallas.42 = (...) custom-call(...),
custom_call_target="tpu_custom_call", ...`` for a Mosaic (Pallas) kernel.

The table is JSON-able, so a trimmed recording can be kept beside the tests:
``{"devices": {plane: {"modules": [[name, start_ns, dur_ns], ...],
"ops": [...]}}, "host_spans": [[name, start_ns, dur_ns, thread], ...]}``.
"""

import glob
import os

SPAN_PREFIX = "bench."
NAME_CHARS = 400  # an HLO instruction can run to kilobytes


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _short(name: str) -> str:
    """Keep the head (instruction name, result shape, opcode) and the
    custom-call target, which sits behind the operands."""
    if len(name) <= NAME_CHARS:
        return name
    tail = ""
    at = name.find("custom_call_target=")
    if at >= 0:
        tail = " ... " + name[at:at + 60]
    return name[:NAME_CHARS] + tail


def load_table(path: str) -> dict:
    """The event table of one ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    host_spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    key = "modules" if line.name == "XLA Modules" else "ops"
                    lines[key] = [[_short(e.name), float(e.start_ns),
                                   float(e.duration_ns)]
                                  for e in line.events]
            devices[plane.name] = {"modules": lines.get("modules", []),
                                   "ops": lines.get("ops", [])}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append([e.name, float(e.start_ns),
                                           float(e.duration_ns), line.name])
    return {"devices": devices, "host_spans": host_spans}
