"""Arithmetic shared by the metric readers in ``chipbench/metrics/``."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank: the smallest value with at
    least ``q`` of all values at or below it."""
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def roofline_pct(run):
    """``100 * sum of least times / SpMM device time`` of a traced window,
    or None without a trace or work."""
    tr = run.device_trace
    if tr is None or not run.work or tr["spmm_device_s"] <= 0:
        return None
    return 100.0 * run.least_time_s() / tr["spmm_device_s"]


def idle_pct(run):
    tr = run.device_trace
    return None if tr is None else tr["idle_pct"]


def latency_s(run, r) -> float:
    """Due-to-answer seconds of request ``r``.  One that never came counts
    as answered when the wait for it ended, a limit past the close, so it
    misses any latency limit."""
    end = r["done"]
    if end is None:
        end = run.t0 + run.seconds + run.traffic["late_limit_s"]
    return end - r["due"]
