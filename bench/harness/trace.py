"""From a profiler trace to the numbers the per-layer metrics read.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain lists: the device operations of each chip (the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane) and the host annotations the benchmark
wrote (``bench.*`` and the layer spans), all on the trace's clock in
nanoseconds. Everything after that works on those lists, so it can be
checked on a small recorded trace (``bench/tests/data``):

* :func:`merge` unions intervals; :func:`clipped` measures a union inside
  a window;
* :func:`op_seconds` sums device time by operation name;
* :func:`gaps` lists the idle stretches of a device inside a window, and
  :func:`name_gaps` names each by the innermost host span around its
  middle.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# off a TPU (the CPU rehearsal) XLA's operations run on these host threads
CPU_OPS_LINE = "tf_XLAPjRtCpuClient"
# the benchmark's own host spans (serve.Spans)
HOST_SPANS = ("bench.", "engine.", "batcher.", "router.", "fleet.")


def extract(trace_dir: str) -> dict:
    """``{"devices": {id: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` from the newest trace.
    Without a TPU plane, the CPU client's operations stand as device 0."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host, cpu_ops = {}, [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                host += [e for e in evs if e[0].startswith(HOST_SPANS)]
                if line.name.startswith(CPU_OPS_LINE):
                    cpu_ops += [e for e in evs if e[2] > 0]
    if not devices and cpu_ops:
        devices[0] = cpu_ops
    return {"devices": devices, "host": host}


def merge(intervals) -> np.ndarray:
    """Union of ``[start, end]`` intervals, as sorted disjoint rows."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0])]
    out = [a[0].copy()]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.asarray(out)


def clipped(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of a merged union inside ``[lo, hi]``."""
    if not len(merged):
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


def intersect(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two merged unions."""
    return float(sum(clipped(a, s, e) for s, e in b))


def busy(events) -> np.ndarray:
    """Merged busy intervals of one device's operations."""
    return merge([[s, s + d] for _, s, d in events])


def short(name: str) -> str:
    """An operation's name without its HLO text (``%op.3 = ...``)."""
    return name.split(" = ", 1)[0]


def op_seconds(events, lo: float, hi: float, pattern: str | None = None
               ) -> dict:
    """Device seconds by short operation name for events that start in
    ``[lo, hi)``, optionally only those whose full text matches
    ``pattern``."""
    rx = re.compile(pattern) if pattern else None
    out: dict = {}
    for name, s, d in events:
        if lo <= s < hi and (rx is None or rx.search(name)):
            k = short(name)
            out[k] = out.get(k, 0.0) + d * 1e-9
    return out


def gaps(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle stretches of a device inside ``[lo, hi]``."""
    edges = [lo]
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        edges += [max(s, lo), min(e, hi)]
    edges.append(hi)
    g = np.asarray(edges, np.float64).reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def name_gaps(idle: np.ndarray, host) -> dict:
    """Idle seconds by the innermost host span around each gap's middle
    (``"none"`` where no span is open)."""
    if not len(idle):
        return {}
    order = np.argsort(0.5 * (idle[:, 0] + idle[:, 1]))
    idle = idle[order]
    mid = 0.5 * (idle[:, 0] + idle[:, 1])
    names = np.full(len(idle), "none", dtype=object)
    free = np.ones(len(idle), bool)
    for n, s, d in sorted(host, key=lambda r: r[2]):   # innermost first
        a, b = np.searchsorted(mid, s), np.searchsorted(mid, s + d, "right")
        pick = np.nonzero(free[a:b])[0] + a
        names[pick] = n
        free[pick] = False
    out: dict = {}
    for n, (s, e) in zip(names, idle):
        out[n] = out.get(n, 0.0) + (e - s) * 1e-9
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
