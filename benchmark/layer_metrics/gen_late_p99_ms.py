"""How late the load generator sent (actual send - due time), 99th pct."""


def read(summary, record, cell):
    return record.get("gen_late_p99_ms")
