"""Programs the process compiled and wrote to the persistent compilation
cache because it held none: ``compile_cache_events_total{result="miss"}``.
0 on a warm run; above 0, the run built programs an earlier run of the
checkout had not (a shape that follows the seed, an edited source line).
Nothing where the program has no such series or has seen neither a hit nor
a miss (no persistent cache)."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    if registry.total(record, "compile_cache_events_total") is None:
        return None
    return registry.total(record, "compile_cache_events_total",
                          result="miss") or 0.0
