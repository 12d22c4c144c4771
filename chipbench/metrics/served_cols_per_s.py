"""Right-hand-side columns answered inside the window, over the window:
answers that reach the host between its open and its close, ramp
requests' included, so that in the steady state the rate does not carry
the latency of the last requests."""


def read(run):
    if not run.requests:
        return None
    cols = sum(c for done, c in run.answers if run.t0 <= done <= run.t_end)
    return cols / run.window_s
