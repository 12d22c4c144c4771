"""Mean host milliseconds the engine spends staging a batch (concatenate,
pad, enqueue the copy to the device): the window's
``repro.engine.stage`` spans."""
from yard.spans import window_mean_ms


def read(run):
    return window_mean_ms(run, "repro.engine.stage")
