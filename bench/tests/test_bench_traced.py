"""The traced run of each cell on the CPU: the per-layer readers find
what they read, except those that read what only a TPU runs (a reader
with ``DEVICE_ONLY``, such as a kernel's roofline: off a TPU the kernel
is not run, and its reader stays silent)."""
import time

import pytest

from harness import main, spec

PEAKS = spec.peaks("TPU v5 lite")


@pytest.mark.parametrize("name", spec.workloads())
def test_traced_run_reports_per_layer_metrics(tiny_cell, name, tmp_path):
    cell = tiny_cell(name)
    res = main.run_cell(cell, 7, 1.0, True, time.perf_counter(),
                        workdir=str(tmp_path), peaks=PEAKS,
                        log=lambda *a, **k: None)
    assert res["correct"], res["compared"]
    readers = cell.readers()
    want = {n for n, r in readers.items()
            if not getattr(r, "DEVICE_ONLY", False)}
    assert set(res["metrics"]) == want
    d = res["device"]
    assert 0 < d["busy_s"] <= d["window_s"]
    idle = [v["value"] for k, v in res["metrics"].items()
            if k.startswith("device.idle_share")]
    assert len(idle) == 1 and 0 <= idle[0] <= 100
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert len(res["breakdown"]["device_ops"]) <= 10
