"""From a profiler trace to the device's busy time, idle share, SpMM
device time, top operations and idle gaps.

Two steps, so that the second can be checked on a small recorded trace:

1. :func:`collect` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
   and keeps, as plain tuples ``(name, start_ns, dur_ns)``:
   the device operations (TPU: the ``XLA Ops`` line of each
   ``/device:TPU:<i>`` plane; CPU rehearsal: the XLA client threads of
   ``/host:CPU``), host-to-device and device-to-host transfers (a
   transfer line of the device plane, or the host calls in
   ``HOST_COPIES``), and the benchmark's own host spans (names starting
   ``chipbench.``).
2. :func:`reduce` takes those and the window (the ``chipbench.window``
   span) and computes:

   * ``busy_s``: the union of device-op intervals inside the window,
     averaged over the devices;
   * ``idle_pct``: ``100 * (1 - busy / window)``;
   * ``spmm_device_s``: the same union over every device op in the
     window except transfers.  The window holds only the SpMM calls (their
     inputs are made before it and the correctness work runs after it),
     so this is the device time of the SpMM work, whatever kernel or XLA
     op implemented it;
   * ``copy_s``: the union of transfer intervals in the window;
   * ``top_ops``: the eight device ops with the most time, by name, then
     the host-to-device and device-to-host copies (``copy.h2d``,
     ``copy.d2h``: the union of the host calls that waited for them);
   * ``idle_gaps``: the ten longest gaps in the window in which no device
     op ran, each named by the innermost benchmark span open at its
     midpoint.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
#: Substrings (lower case) of device-plane lines or ops that are
#: host<->device transfers rather than computation.
TRANSFER_WORDS = ("transfer", "h2d", "d2h", "host to device",
                  "device to host", "hosttodevice", "devicetohost")
#: Host events that span a whole host<->device copy as the caller waits
#: for it (JAX's own trace names), and the direction of each.
HOST_COPIES = {"DevicePutWithSharding": "h2d",
               "np.asarray(jax.Array)": "d2h"}
#: Host-side bookkeeping events of the CPU backend's thread pools.
CPU_NOISE = ("ThreadpoolListener", "SlinkyThreadPool")


def _is_transfer(name: str) -> bool:
    low = name.lower()
    return any(w in low for w in TRANSFER_WORDS)


def op_name(name: str) -> str:
    """An op's name without the HLO text the TPU trace appends to it:
    ``%csr_spmm.1 = f32[...] custom-call(...)`` -> ``csr_spmm.1``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def latest_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def collect(xplane_path: str) -> dict:
    """Events of one trace: ``{"devices": {device: [op, ...]},
    "transfers": [...], "spans": [...], "lines": {...},
    "host_names": {...}}``.

    ``lines`` counts events per ``plane/line`` name and ``host_names`` the
    commonest host event names, to show what the trace held.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices, transfers, spans, lines, host = {}, [], [], {}, {}
    host_long = []
    tpu = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            lines[f"{plane.name}/{line.name}"] = len(evs)
            if plane.name.startswith("/device:TPU:"):
                if _is_transfer(line.name):
                    transfers.extend(evs)
                elif line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(evs)
            elif plane.name == "/host:CPU":
                for e in evs:
                    host[e[0]] = host.get(e[0], 0) + 1
                    if e[2] >= 1_000_000:
                        host_long.append((line.name, *e))
                spans.extend(e for e in evs if e[0].startswith(SPAN_PREFIX))
                transfers.extend(e for e in evs if e[0] in HOST_COPIES)
                if not tpu and line.name.startswith(
                        ("tf_XLAPjRtCpuClient", "tf_XLAEigen")):
                    devices.setdefault("/host:CPU", []).extend(
                        e for e in evs
                        if e[2] > 0 and not e[0].startswith(CPU_NOISE))
    common = sorted(host.items(), key=lambda kv: -kv[1])[:60]
    return {"devices": devices, "transfers": transfers, "spans": spans,
            "lines": lines, "host_names": dict(common),
            "host_long": host_long}


def _union(intervals, lo: int, hi: int):
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for _, s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def window_of(events: dict):
    """``(start_ns, end_ns)`` of the ``chipbench.window`` span."""
    wins = [e for e in events["spans"] if e[0] == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    _, s, d = wins[0]
    return s, s + d


def reduce(events: dict) -> dict:
    """The window's device numbers (see the module docstring)."""
    lo, hi = window_of(events)
    window_ns = hi - lo
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, compute = [], []
    for ops in devices.values():
        busy.append(_length(_union(ops, lo, hi)))
        compute.append(_length(_union(
            [e for e in ops if not _is_transfer(e[0])], lo, hi)))
    merged_first = _union(next(iter(devices.values())), lo, hi)
    totals = {}
    for ops in devices.values():
        for name, s, d in ops:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                totals[name] = totals.get(name, 0) + clipped
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:8]
    for kind in ("h2d", "d2h"):
        t = _length(_union([e for e in events["transfers"]
                            if HOST_COPIES.get(e[0], "") == kind], lo, hi))
        if t:
            top.append((f"copy.{kind}", t))
    gaps, prev = [], lo
    for s, e in merged_first + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [e for e in events["spans"] if e[0] != WINDOW_SPAN]
    named = []
    for s, e in gaps[:10]:
        mid = (s + e) // 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
        inner = min(open_, key=lambda sp: sp[2])[0] if open_ else "none"
        named.append([inner, (e - s) / 1e9])
    ndev = len(devices)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / ndev / 1e9,
        "idle_pct": 100.0 * (1.0 - sum(busy) / ndev / window_ns),
        "spmm_device_s": sum(compute) / ndev / 1e9,
        "copy_s": _length(_union(events["transfers"], lo, hi)) / 1e9,
        "top_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": named,
        "devices": ndev,
    }
