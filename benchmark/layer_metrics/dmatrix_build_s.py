"""Host seconds for ``DMatrix`` + sketch + bins to ``block_until_ready``,
cache warm: the same work in every cell (``onehot_build_s`` is beside it)."""


def read(summary, record, cell):
    return record.get("dmatrix_build_s")
