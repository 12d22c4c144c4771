"""Share of the roofline in the traced window: the least time of the
compulsory work of every call or launch (``yard.work``), summed, over
the device time of every non-transfer device op in the window, whatever
kernel ran."""
from yard.readers import roofline_pct


def read(run):
    return roofline_pct(run) if run.batches else None
