"""The serve driver's arrival schedule: the same work for every seed,
Poisson at the traffic file's rate."""
import numpy as np
import pytest

from yard import names

serve = names.load("drivers", "serve")
TRAFFIC = names.traffic("serve")


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_every_seed_gets_the_same_work(seed):
    due, widths = serve.schedule(TRAFFIC, 40.0, seed)
    ref_due, ref_widths = serve.schedule(TRAFFIC, 40.0, 7)
    assert len(due) == round(TRAFFIC["rate_per_s"] * 40)
    assert sorted(widths) == sorted(ref_widths)
    assert sorted(np.diff(due, prepend=0)) == pytest.approx(
        sorted(np.diff(ref_due, prepend=0)))
    assert due[-1] == pytest.approx(40.0)
    assert np.all(np.diff(due) > 0)
