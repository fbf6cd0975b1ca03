"""99th percentile of the server's own ``serving_queue_wait_seconds``.

Read from the registry histogram's bucket counts as a delta over the window,
interpolated inside the bucket as Prometheus does: its resolution is the
bucket ladder (0.25, 0.5, 1, 2.5 s at the top)."""


def read(summary, record, cell):
    hist = record.get("queue_wait_hist")
    if not hist or sum(hist["counts"]) == 0:
        return None
    target = 0.99 * sum(hist["counts"])
    cum, lo = 0.0, 0.0
    for ub, c in zip(hist["buckets"], hist["counts"]):
        if c and cum + c >= target:
            return 1e3 * (lo + (ub - lo) * (target - cum) / c)
        cum += c
        lo = ub
    return 1e3 * hist["buckets"][-1]
