"""The cell's sparse operator: structure from its configuration, values
from ``--seed``.

The structure depends only on the configuration (its generator, sizes and
``structure_seed``), so it is cached as ``.npy`` files under
``chipbench/cache/`` the first time a checkout makes it.  Only the
benchmark's input is cached: the program's plan and packed layout are
always built anew, and ``setup_s`` measures them.
"""
from __future__ import annotations

import hashlib
import json

import numpy as np

from yard import names

CACHE = names.HERE / "cache"


def structure_key(cfg: dict) -> str:
    """A name for the structure that changes with anything that shapes it."""
    spec = {"n": cfg["n"], "generator": cfg["generator"],
            "structure_seed": cfg["structure_seed"]}
    digest = hashlib.sha256(json.dumps(spec, sort_keys=True).encode())
    return f"{cfg['name']}-{digest.hexdigest()[:12]}"


def dedup(n: int, rows, cols):
    """Clip to ``[0, n)``, drop duplicates, sort row-major (int32)."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    keep = (rows >= 0) & (rows < n) & (cols >= 0) & (cols < n)
    lin = np.unique(rows[keep] * n + cols[keep])
    return (lin // n).astype(np.int32), (lin % n).astype(np.int32)


def structure(cfg: dict):
    """``(rows, cols)`` of the configured operator, cached by key."""
    key = structure_key(cfg)
    rpath, cpath = CACHE / f"{key}.rows.npy", CACHE / f"{key}.cols.npy"
    if rpath.is_file() and cpath.is_file():
        return np.load(rpath), np.load(cpath)
    gen = names.load("generators", cfg["generator"]["kind"])
    rows, cols = dedup(cfg["n"], *gen.generate(
        cfg["n"], cfg["generator"], cfg["structure_seed"]))
    CACHE.mkdir(exist_ok=True)
    # Write under a temporary name, then rename: a run that is killed
    # mid-write leaves no truncated file behind.
    for path, arr in ((rpath, rows), (cpath, cols)):
        tmp = path.with_suffix(".tmp.npy")
        np.save(tmp, arr)
        tmp.replace(path)
    return rows, cols


def values(nnz: int, seed: int) -> np.ndarray:
    """The operator's values from ``--seed``: uniform on [0.5, 1.5),
    rounded to float32 so that the float32 the configuration stores them
    in holds them exactly and the float64 reference sees the same ones."""
    rng = np.random.default_rng([seed, 1])
    vals = rng.uniform(0.5, 1.5, size=nnz).astype(np.float32)
    return vals.astype(np.float64)
