"""Bytes of the packed layout (``Dispatcher.layout``) over the nonzeros'
value and index bytes at the configured precision.  A count."""

BYTES_PER_NONZERO = {"f32i32": 8, "bf16i32": 6, "bf16i16": 4}


def read(run):
    if run.layout_bytes is None or not run.nnz:
        return None
    per = BYTES_PER_NONZERO[run.cfg["precision"]]
    return run.layout_bytes / (run.nnz * per)
