"""GFLOP/s of the replay window: ``2 * nnz * d`` summed over every call
completed in it, over the window's seconds (host clock)."""


def read(run):
    if not run.calls:
        return None
    return sum(f for f, _ in run.work) / run.window_s / 1e9
