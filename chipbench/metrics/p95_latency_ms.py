"""95th percentile (nearest rank) of due-to-answer latency over every
request due in the window; a request that failed or never came misses
every limit (``yard.readers.latency_s``)."""
from yard.readers import latency_s, nearest_rank


def read(run):
    if not run.requests:
        return None
    return 1e3 * nearest_rank([latency_s(run, r) for r in run.requests],
                              0.95)
