"""Mean host milliseconds the engine spends copying a finished batch to
the host: the window's ``repro.engine.fetch`` spans."""
from yard.spans import window_mean_ms


def read(run):
    return window_mean_ms(run, "repro.engine.fetch")
