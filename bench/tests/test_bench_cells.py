"""Each cell's whole run, rehearsed on the CPU at a tiny width: the same
harness functions as on the chip (weights, traffic, warm-up, the timed
loop, the reference check), with the device check left out."""
import json
import time

import pytest

from harness import main, spec

CELLS = spec.workloads()
PEAKS = spec.peaks("TPU v5 lite")


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(tiny_cell, name, tmp_path):
    cell = tiny_cell(name)
    res = main.run_cell(cell, 2**31 + 17, 1.0, False, time.perf_counter(),
                        workdir=str(tmp_path), peaks=PEAKS,
                        log=lambda *a, **k: None)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["compared"]["missing_outputs"]["value"] == 0
    json.dumps(res)
