"""Symmetric power-law graph (Chung-Lu): the adjacency of an undirected
social graph, whose row and column degrees follow one distribution.

Vertex ``i`` (rank ``1..n`` before a random relabelling) has weight
``i^(-1/(alpha-1))``, so its expected degree follows a power law with
exponent ``alpha``; weights are capped so that no expected degree passes
``max_degree``.  ``round(mean_degree * n / 2)`` undirected edges are
drawn with both ends chosen in proportion to weight; self-loops are
dropped and every edge is stored both ways.  Duplicates are removed
afterwards (``yard.operator.dedup``), so the delivered nonzeros per row
fall a little below ``mean_degree``.  Vertex ids are shuffled by the
seed, so hubs are spread over the rows as in a crawled graph's
numbering.
"""
from __future__ import annotations

import numpy as np


def generate(n: int, params: dict, seed: int):
    """Rows and columns before de-duplication.

    ``params``: ``alpha`` (degree exponent), ``mean_degree`` (requested
    nonzeros per row, before de-duplication) and ``max_degree`` (cap on
    any vertex's expected degree).
    """
    beta = 1.0 / (float(params["alpha"]) - 1.0)
    edges = int(round(float(params["mean_degree"]) * n / 2))
    w = np.arange(1, n + 1, dtype=np.float64) ** -beta
    expected = 2.0 * edges * w / w.sum()
    w = np.minimum(expected, float(params["max_degree"]))
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    rng = np.random.default_rng(seed)
    ends = np.searchsorted(cdf, rng.uniform(size=2 * edges), side="right")
    ends = np.minimum(ends, n - 1)
    label = rng.permutation(n)
    u, v = label[ends[:edges]], label[ends[edges:]]
    keep = u != v
    u, v = u[keep], v[keep]
    return np.concatenate([u, v]), np.concatenate([v, u])
