"""Device busy time of the traced window per thousand rows answered."""


def read(summary, record, cell):
    rows = record.get("rows_answered")
    if not summary or not rows or summary["busy_s"] <= 0:
        return None
    return 1e3 * summary["busy_s"] / (rows / 1e3)
