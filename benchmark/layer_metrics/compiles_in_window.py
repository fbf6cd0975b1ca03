"""Programs traced or built inside the measured window; expected 0.

``recompiles_total`` (the retrace guard) plus
``predict_bucket_cache_misses_total``, as deltas over the window. Anything
above 0 voids the run's timings: a compile left set-up for the window."""


def read(summary, record, cell):
    return record.get("compiles_in_window")
