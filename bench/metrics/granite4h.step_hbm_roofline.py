"""Share of the HBM roofline that the whole decode step of the hybrid
Mamba-2 / attention LM reaches in the traced ticks: the least time the
chip could take for them (the bytes each tick needs, ``_hybrid_bytes.py``,
over the HBM bandwidth) over the device time of the step's operations in
those ticks (the union of the ``XLA Ops`` events in the traced window;
host transfers are not device operations and are left out).

Each tick's ``kv_len`` comes from the served tick indices, the benchmark's
own count: the ``j``-th token of a stream is served with ``j + 1``
positions in its cache. The step's time is the chip's only on a TPU, so
off one the reader stays silent (``DEVICE_ONLY``).
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _hybrid_bytes import tick_bytes  # noqa: E402

LAYER = "program step (core/program.py, core/deltamamba2h.py)"
UNIT = "%"
MOVES = "frame_latency_p50_ms"
DEVICE_ONLY = True


def read(r):
    import jax
    if r.trace is None or r.work is None or r.trace["busy_s"] <= 0 \
            or jax.devices()[0].platform != "tpu":
        return None
    ka, kb = r.work["traced"]
    per_tick = [[] for _ in range(kb - ka)]
    for s in r.run.streams_in_window():
        ticks = r.run.serve_ticks(s)
        for j in np.nonzero((ticks >= ka) & (ticks < kb))[0]:
            per_tick[ticks[j] - ka].append(j + 1)
    least = sum(tick_bytes(r.cell.config, lens) for lens in per_tick) \
        / r.peaks["hbm_bytes_per_s"]
    if least <= 0:
        return None
    return 100.0 * least / r.trace["busy_s"]
