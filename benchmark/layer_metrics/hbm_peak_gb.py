"""Peak device memory of the run (``memory_stats``), fullest chip, in GB."""


def read(summary, record, cell):
    peak = record.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
