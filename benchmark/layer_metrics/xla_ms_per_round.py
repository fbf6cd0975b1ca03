"""Device busy time that is not a Mosaic call, per boosting round.

Split evaluation, partition, finalize, gradients, copies: everything XLA
compiled itself (collectives included, where there are any)."""


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary["busy_s"] <= 0:
        return None
    return 1e3 * (summary["busy_s"] - summary["mosaic_s"]) / rounds
