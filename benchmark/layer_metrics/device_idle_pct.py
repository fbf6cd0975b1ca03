"""Share of the traced window in which no program ran, on the idlest chip."""


def read(summary, record, cell):
    if not summary or summary["window_s"] <= 0 or not summary["chips"]:
        return None
    return 100.0 * (1.0 - summary["busy_min_s"] / summary["window_s"])
