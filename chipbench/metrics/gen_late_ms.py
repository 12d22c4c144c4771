"""95th percentile (nearest rank) of how late the load generator
submitted each request after it was due, in ms."""
from yard.readers import nearest_rank


def read(run):
    late = [r["submitted"] - r["due"] for r in run.requests
            if r["submitted"] is not None]
    if not late:
        return None
    return 1e3 * nearest_rank(late, 0.95)
