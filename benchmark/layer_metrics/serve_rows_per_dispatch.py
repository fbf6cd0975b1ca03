"""Rows a coalesced dispatch carried, from the server's dispatch ring."""


def read(summary, record, cell):
    n = record.get("dispatches")
    return record["dispatch_rows"] / n if n else None
