"""What a run records, and the set-up steps every driver shares.

A driver fills one :class:`Run`; each metric's reader
(``chipbench/metrics/<name>.py``) reads its number from it and returns
``None`` where the run has nothing for it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

from yard import operator, work
from yard.reference import Reference


@dataclasses.dataclass
class Run:
    """One run of one cell."""

    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    peak: dict
    t_start: float                      # perf_counter at process start
    #: Set-up parts in seconds, in order (import, device, generate, ...).
    setup_parts: dict = dataclasses.field(default_factory=dict)
    setup_s: Optional[float] = None
    #: Window bounds on the perf_counter clock (serve: ``t_end`` is the
    #: close, ``t0 + seconds``; answers may come after it).
    t0: Optional[float] = None
    t_end: Optional[float] = None
    #: Replay: one ``(launched, done)`` per call completed in the window.
    calls: list = dataclasses.field(default_factory=list)
    #: Serve: one dict per request due in the window (``due``,
    #: ``submitted``, ``done`` or None, ``cols``, ``error``, and the
    #: answer's sample rows ``c_rows`` for the check).
    requests: list = dataclasses.field(default_factory=list)
    #: Serve: ``(done, cols)`` of every answer, ramp requests included.
    answers: list = dataclasses.field(default_factory=list)
    #: Serve: ``(request columns, padded columns, queued_s)`` per batch
    #: the engine ran in the window (its batch log).
    batches: list = dataclasses.field(default_factory=list)
    #: Compulsory work: ``(flops, bytes)`` of each call or launch.
    work: list = dataclasses.field(default_factory=list)
    nnz: int = 0
    nonempty_cols: int = 0
    chosen: str = ""
    layout_bytes: Optional[int] = None
    #: Reduced trace (``yard.trace.reduce``), traced runs only.
    device_trace: Optional[dict] = None
    memory_peak_bytes: Optional[int] = None
    #: ``{name: (value, limit, op)}`` compared for ``correct``: the run
    #: is correct when every ``value op limit`` holds (op ``<=``/``>=``).
    checks: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    #: Put the control (``Reference.control``) in the program's place.
    control: bool = False

    def check(self, name: str, value: float, limit: float,
              op: str = "<=") -> None:
        self.checks[name] = (value, limit, op)

    def correct(self) -> bool:
        return all(v <= lim if op == "<=" else v >= lim
                   for v, lim, op in self.checks.values())

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def least_time_s(self) -> float:
        """Sum of the least time of every call or launch in ``work``."""
        return sum(work.least_time(f, b, self.peak)[0] for f, b in self.work)

    @contextlib.contextmanager
    def part(self, name: str):
        """Time a set-up part into ``setup_parts``."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup_parts[name] = time.perf_counter() - t


@dataclasses.dataclass
class Operator:
    """The configured operator as the program gets it, and its reference."""

    coo: object                         # repro.core.patterns.COOMatrix
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray

    def reference(self, seed: int) -> Reference:
        return Reference(self.coo.n, self.rows, self.cols, self.vals, seed)


def make_operator(run: Run) -> Operator:
    """Structure from the configuration, values from the seed."""
    from repro.core.patterns import COOMatrix
    n = run.cfg["n"]
    rows, cols = operator.structure(run.cfg)
    vals = operator.values(rows.shape[0], run.seed)
    coo = COOMatrix(n=n, rows=rows, cols=cols, vals=vals,
                    pattern=run.cfg["generator"]["kind"])
    run.nnz = int(rows.shape[0])
    run.nonempty_cols = int(np.count_nonzero(np.bincount(cols, minlength=n)))
    return Operator(coo=coo, rows=rows, cols=cols, vals=vals)


def plan_and_pack(run: Run, op: Operator, d: int):
    """The user's path: ``Dispatcher.plan``, then ``sparse.plan`` binding
    the layout, timed apart as ``plan`` and ``pack``.  Returns the
    ``StreamPlan``."""
    import jax
    from repro import sparse
    disp = sparse.default_dispatcher()
    with run.part("plan"):
        disp.plan(op.coo, d, reuse=run.cfg["reuse"],
                  precision=run.cfg["precision"])
    with run.part("pack"):
        sp = sparse.plan(op.coo, sparse.BSpec(
            d=d, reuse=run.cfg["reuse"], precision=run.cfg["precision"]))
        layout = disp.layout(op.coo, sp.dispatch)
        jax.block_until_ready(layout)
    run.chosen = sp.chosen
    run.layout_bytes = int(sum(
        x.nbytes for x in jax.tree_util.tree_leaves(layout)
        if hasattr(x, "nbytes")))
    return sp


def memory_peak(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)
