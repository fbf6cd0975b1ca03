"""Collective time with nothing else running on the chip, per round."""


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary["chips"] < 2:
        return None
    return 1e3 * summary["collective_exposed_s"] / rounds
