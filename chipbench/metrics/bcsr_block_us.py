"""Device microseconds a BCSR grid step takes in the traced window: the
device time of every non-transfer op, over the completed calls times the
``blocks`` attr of the window's ``repro.execute`` spans (one launch's grid
steps, stored blocks times d-passes).  None where no such span carries
``blocks``: another kernel ran, or the program logs no such attr."""
from yard import spans


def read(run):
    tr = run.device_trace
    if tr is None or not run.calls:
        return None
    found = spans.named(run, "window", "repro.execute")
    if found is None:
        return None
    blocks = {s.attrs.get("blocks") for s in found}
    if len(blocks) != 1 or None in blocks:
        return None
    return 1e6 * tr["spmm_device_s"] / (len(run.calls) * blocks.pop())
