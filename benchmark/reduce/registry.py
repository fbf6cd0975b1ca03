"""Totals out of the program's own metrics registry, for the per-layer
metrics of ``setup_s``: the set-up ledger's series (``jit_seconds_total``,
``compile_cache_events_total``, ``setup_stage_seconds_total``,
``hbm_peak_bytes``), which the program keeps in-process from its import on.

A reader sums a series over the process at the end of the run. It reads
nothing on an empty record, from a program without the series (the parent
of the PR that brought it), or where no child carries the labels asked
for."""


def series(name: str, **labels) -> list:
    """The values of ``name``'s children whose labels hold ``labels``."""
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return []
    return [child.value for have, child in fam.series()
            if all(have.get(k) == str(v) for k, v in labels.items())]


def total(record, name: str, **labels):
    """The sum of those values, or nothing (see above)."""
    values = series(name, **labels) if record else []
    return float(sum(values)) if values else None

