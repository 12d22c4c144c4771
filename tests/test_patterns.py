"""Pattern generators + structure classifier (paper Table III regimes)."""
import numpy as np
import pytest

from repro.core import banded, blocked, classify, erdos_renyi, scale_free
from repro.core.classify import (HILL_MIN_DEGREES, block_stats, degree_gini,
                                 hill_alpha, hub_dominance)
from repro.core.patterns import COOMatrix, paper_suite


@pytest.mark.parametrize("gen,expected", [
    (lambda: erdos_renyi(4096, 8, seed=1), "random"),
    (lambda: banded(4096, 1, seed=2), "diagonal"),
    (lambda: banded(4096, 4, fill=0.9, seed=3), "diagonal"),
    (lambda: blocked(4096, t=64, num_blocks=128, nnz_per_block=40, seed=4),
     "blocked"),
    (lambda: scale_free(4096, 16, alpha=2.2, seed=5), "scale_free"),
])
def test_classifier_recovers_regime(gen, expected):
    m = gen()
    report = classify(m)
    assert report.regime == expected, report.stats


def test_generators_deterministic():
    a = erdos_renyi(1024, 4, seed=7)
    b = erdos_renyi(1024, 4, seed=7)
    np.testing.assert_array_equal(a.rows, b.rows)
    np.testing.assert_array_equal(a.vals, b.vals)
    c = erdos_renyi(1024, 4, seed=8)
    assert not np.array_equal(a.rows, c.rows)


def test_coo_invariants():
    for gen in paper_suite(scale=10).values():
        m = gen()
        assert m.nnz == len(m.rows) == len(m.cols) == len(m.vals)
        assert m.rows.min() >= 0 and m.rows.max() < m.n
        assert m.cols.min() >= 0 and m.cols.max() < m.n
        # sorted row-major, unique
        lin = m.rows.astype(np.int64) * m.n + m.cols
        assert np.all(np.diff(lin) > 0)
        ptr = m.row_ptr()
        assert ptr[0] == 0 and ptr[-1] == m.nnz


def test_ideal_diagonal_is_one_per_row():
    m = banded(2048, 1, seed=0)
    assert m.nnz == 2048
    np.testing.assert_array_equal(m.rows, m.cols)


def test_block_stats_match_model():
    """Empirical occupied columns per block ~ the paper's z formula."""
    t, D = 64, 40.0
    m = blocked(2 ** 14, t=t, num_blocks=400, nnz_per_block=D, seed=9)
    stats = block_stats(m, t)
    assert stats["D"] == pytest.approx(D, rel=0.25)
    assert stats["z_emp"] == pytest.approx(stats["z_model"], rel=0.2)


def test_scale_free_tail():
    m = scale_free(2 ** 14, 16, alpha=2.2, seed=11)
    deg = np.bincount(m.rows, minlength=m.n)
    assert degree_gini(deg) > 0.5            # heavy tail
    alpha = hill_alpha(deg)
    assert 1.5 < alpha < 3.5
    # Hubs exist: top 0.1% of rows own a disproportionate share.
    k = max(1, m.n // 1000)
    top = np.sort(deg)[::-1][:k].sum()
    assert top / m.nnz > 10 * (k / m.n)


def test_er_has_no_structure():
    m = erdos_renyi(2 ** 12, 8, seed=13)
    deg = np.bincount(m.rows, minlength=m.n)
    assert degree_gini(deg) < 0.45


def test_er_delivers_exact_density():
    """The draw-then-dedup generator used to lose ~avg_deg/(2n) of its
    entries to birthday collisions; nnz must now equal the request."""
    for n, deg, seed in [(1024, 8, 0), (256, 32, 1), (4096, 64, 2)]:
        m = erdos_renyi(n, deg, seed=seed)
        assert m.nnz == round(n * deg), (n, deg)
        assert m.meta["achieved_nnz"] == m.nnz
        assert m.meta["achieved_avg_degree"] == pytest.approx(deg)
    # Saturating request caps at the dense matrix, no infinite loop.
    assert erdos_renyi(16, 16, seed=3).nnz == 256


def test_generators_record_achieved_density():
    m = banded(512, 4, fill=0.7, seed=5)
    assert m.meta["achieved_nnz"] == m.nnz
    assert m.meta["achieved_avg_degree"] == pytest.approx(m.nnz / m.n)


def test_hill_alpha_small_and_flat_vectors():
    """inf means *no detectable heavy tail* — by design, not by accident
    (the old clamp read deg[size-1], degenerating the estimator)."""
    # Below the documented sample floor: inf, never a spurious estimate.
    assert hill_alpha(np.full(HILL_MIN_DEGREES - 1, 5)) == float("inf")
    assert hill_alpha(np.zeros(100, dtype=int)) == float("inf")
    # Flat degree vectors (uniform/banded) have no tail at any size.
    assert hill_alpha(np.full(10_000, 7)) == float("inf")
    # A genuine power law at corpus scale stays finite and in range:
    # the old clamp's failure mode was inf exactly here.
    deg = np.bincount(scale_free(256, 8, alpha=2.2, seed=8).rows,
                      minlength=256)
    assert 1.5 < hill_alpha(deg) < 3.5


def test_hub_dominance_separates_hubs_from_uniform():
    assert hub_dominance(np.full(1000, 5)) == pytest.approx(1.0)
    assert hub_dominance(np.zeros(10)) == 0.0
    sf = np.bincount(scale_free(256, 8, alpha=2.1, seed=8).rows,
                     minlength=256)
    er = np.bincount(erdos_renyi(256, 8, seed=1).rows, minlength=256)
    assert hub_dominance(sf) > 7.0 > hub_dominance(er)


def _transpose(m: COOMatrix) -> COOMatrix:
    lin = m.cols.astype(np.int64) * m.n + m.rows
    order = np.argsort(lin, kind="stable")
    return COOMatrix(n=m.n, rows=m.cols[order], cols=m.rows[order],
                     vals=m.vals[order], pattern=m.pattern, meta={})


@pytest.mark.parametrize("n,deg", [(256, 8), (4096, 16)])
def test_classifier_detects_column_hubs(n, deg):
    """Transposed scale-free: uniform row degrees, heavy column tail.
    Row-only degree statistics classified this as ``random``."""
    mt = _transpose(scale_free(n, deg, alpha=2.2, seed=5))
    report = classify(mt)
    assert report.regime == "scale_free", report.stats
    assert report.stats["tail_axis"] == "col"
    assert report.stats["col_gini"] > report.stats["row_gini"]


def test_classifier_small_matrix_regimes():
    """Corpus-scale (n of a few hundred) versions of every regime: the
    sizes the vendored samples live at, where the pre-fix classifier
    sent banded, blocked, and scale-free matrices all to ``random``."""
    cases = [
        (erdos_renyi(256, 8, seed=1), "random"),
        (banded(224, 5, fill=0.85, seed=5), "diagonal"),
        (blocked(256, t=32, num_blocks=16, nnz_per_block=256, seed=6),
         "blocked"),
        (scale_free(256, 8, alpha=2.1, seed=8), "scale_free"),
    ]
    for m, expected in cases:
        assert classify(m).regime == expected, (m.pattern, m.n)


@pytest.mark.parametrize("nodes", [(2, 2, 2), (4, 3, 5), (6, 6, 6)])
def test_hex_mesh_counts_symmetry_and_node_blocks(nodes):
    """Closed-form nonzeros, a symmetric pattern, and dense 3 x 3 blocks
    for every coupled pair of nodes."""
    from repro.core import hex_mesh
    nx, ny, nz = nodes
    m = hex_mesh(nx, ny, nz)
    assert m.n == 3 * nx * ny * nz
    assert m.nnz == 9 * (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    pattern = np.zeros((m.n, m.n), dtype=bool)
    pattern[m.rows, m.cols] = True
    assert (pattern == pattern.T).all()
    nodes_coupled = pattern.reshape(m.n // 3, 3, m.n // 3, 3)
    assert (nodes_coupled.all(axis=(1, 3)) == nodes_coupled.any(
        axis=(1, 3))).all()
    # A node inside the mesh couples to the 27 nodes around it.
    if min(nodes) >= 3:
        inner = 1 + nx * (1 + ny)
        assert nodes_coupled[inner].any(axis=(0, 2)).sum() == 27
