"""Stiffness pattern of a 3D solid FEM mesh (3D elasticity): trilinear
hexahedra on a grid of nodes, ``dof`` displacement unknowns per node.

Nodes are numbered lexicographically (x fastest) with their unknowns
interleaved, so row ``dof * node + k`` is unknown ``k`` of ``node``.  Each
row couples to every unknown of every node that shares an element with
its node: 27 nodes inside the mesh, fewer on its faces.  The pattern is
symmetric, made of dense ``dof x dof`` node blocks, and holds ``dof**2 *
(3nx - 2)(3ny - 2)(3nz - 2)`` nonzeros.  It is the same pattern as the
program's ``repro.core.patterns.hex_mesh``, built here so that the
benchmark imports nothing from the program.  The mesh is fixed by its
sizes: the structure seed changes nothing.
"""
from __future__ import annotations

import numpy as np


def _grid(n: int, params: dict):
    """The configured grid, or, where ``n`` is not its number of unknowns
    (a ``--rehearse`` run), the largest cube mesh whose unknowns fit in
    ``n`` rows; the rows after it stay empty."""
    dof = int(params["dof"])
    nodes = [int(v) for v in params["nodes"]]
    if dof * nodes[0] * nodes[1] * nodes[2] == n:
        return nodes, dof
    side = int(round((n // dof) ** (1.0 / 3.0))) + 1
    while dof * side ** 3 > n:
        side -= 1
    return [side, side, side], dof


def generate(n: int, params: dict, seed: int):
    """Rows and columns of the mesh's stiffness pattern.

    ``params``: ``nodes`` (``[nx, ny, nz]``, nodes along each axis),
    ``dof`` (unknowns per node) and ``numbering`` (``lexicographic``).
    """
    del seed                                   # the mesh is deterministic
    if params["numbering"] != "lexicographic":
        raise ValueError(f"unknown numbering {params['numbering']!r}")
    (nx, ny, nz), dof = _grid(n, params)
    node = np.arange(nx * ny * nz, dtype=np.int64)
    x, y, z = node % nx, node // nx % ny, node // (nx * ny)
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                ok = ((x + dx >= 0) & (x + dx < nx) & (y + dy >= 0)
                      & (y + dy < ny) & (z + dz >= 0) & (z + dz < nz))
                i = node[ok]
                j = i + dx + nx * (dy + ny * dz)
                for a in range(dof):
                    for b in range(dof):
                        rows.append(dof * i + a)
                        cols.append(dof * j + b)
    return np.concatenate(rows), np.concatenate(cols)
