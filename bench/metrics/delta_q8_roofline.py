"""Share of its roofline that the int8 delta kernel reaches in the traced
ticks: the least time the chip could take for the work the algorithm
needs (per tick and layer, the larger of the fired columns' operations
over the int8 peak and their weight bytes over the HBM bandwidth; see
``_work.py``), over the device time of the kernel's events.

The kernel is the ``pallas_call`` of ``kernels/delta_q8.py``. On a TPU
the trace names its events by the HLO of the custom call, after the
jitted function that wraps the kernel (``_fused_q8_step``, and
``_fused_q8_step_dbuf`` for the double-buffered variant), with the target
``tpu_custom_call``: ``KERNELS`` matches that text. Off a TPU the kernel
is not run, and the reader stays silent (``DEVICE_ONLY``).
"""
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _work import least_seconds  # noqa: E402

LAYER = "kernels (kernels/delta_q8.py)"
UNIT = "%"
MOVES = "frame_latency_p50_ms"
DEVICE_ONLY = True
GATES = {"gru": 3, "lstm": 4}       # the kernel's weight rows per hidden unit
KERNELS = r"^%_fused_q8_step(_dbuf)?(\.\d+)? = .*tpu_custom_call"


def read(r):
    if r.trace is None or r.work is None:
        return None
    t = r.trace
    rx = re.compile(KERNELS)
    kern = sum(d * 1e-9 for ev in t["events"].values() for name, s, d in ev
               if t["lo"] <= s < t["hi"] and rx.search(name))
    if kern <= 0:
        return None
    cfg = r.cell.config
    least = least_seconds(r.work["union"], r.work["counts"],
                          gates=GATES[cfg["cell"]], hidden=cfg["hidden_size"],
                          weight_bits=cfg["weight_bits"],
                          peak_ops=r.peaks["int8_ops_per_s"],
                          peak_bytes=r.peaks["hbm_bytes_per_s"])
    if least <= 0:          # no column fired in the traced ticks
        return None
    return 100.0 * least / kern
