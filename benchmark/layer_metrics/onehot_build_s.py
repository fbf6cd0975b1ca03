"""Host seconds to build the resident one-hot, cache warm.

Read where one chip builds it outside the fit. Under a mesh the first chunk
builds it, the run record's ``warmup_s`` holds it, and this reader finds
nothing."""


def read(summary, record, cell):
    return record.get("onehot_build_s")
