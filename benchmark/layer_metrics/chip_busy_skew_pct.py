"""(max - min) / max of the chips' busy time in the traced window."""


def read(summary, record, cell):
    if not summary or summary["chips"] < 2 or summary["busy_max_s"] <= 0:
        return None
    return 100.0 * (summary["busy_max_s"] - summary["busy_min_s"]) \
        / summary["busy_max_s"]
