"""Device time of the Mosaic (Pallas) calls per boosting round, mean chip."""


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary["mosaic_s"] <= 0:
        return None
    return 1e3 * summary["mosaic_s"] / rounds
