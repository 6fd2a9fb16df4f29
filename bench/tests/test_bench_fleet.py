"""The router over a four-way sharded fleet, rehearsed on four virtual
CPU devices at a tiny width: the same timed loop and reference check as
the one-chip cells, and the router's queue wait read from it."""
import json
import os
import time

import jax

from harness import main, spec

PEAKS = spec.peaks("TPU v5 lite")


def _fleet_cell():
    with open(os.path.join(spec.BENCH, "traffic", "fleet4-speech.json")) as f:
        t = json.load(f)
    with open(os.path.join(spec.BENCH, "configs", "gru768.json")) as f:
        c = json.load(f)
    e2e = [{"name": n, "unit": "ms"} for n in ("frame_latency_p50_ms",
                                              "frame_latency_p95_ms")]
    e2e.append({"name": "setup_s", "unit": "s"})
    per = [{"name": n, "unit": u} for n, u in (
        ("router.queue_wait_p95_ms", "ms"), ("engine.host_ms_per_tick", "ms"),
        ("device.idle_share", "%"))]
    return spec.Cell("gru768-q8-fleet4-speech", 4, c, t, e2e, per, 20)


def test_router_over_four_shards(shrink, tmp_path):
    assert len(jax.devices()) >= 4
    cell = shrink(_fleet_cell(), slots=8, rate=30.0, max_len=60)
    for trace in (False, True):
        res = main.run_cell(cell, 2**31 + 41, 1.0, trace, time.perf_counter(),
                            workdir=str(tmp_path), peaks=PEAKS,
                            log=lambda *a, **k: None)
        assert res["correct"], res["compared"]
        assert res["attempted"] > 0
    assert set(res["metrics"]) == {"router.queue_wait_p95_ms",
                                   "engine.host_ms_per_tick",
                                   "device.idle_share"}
    assert res["metrics"]["router.queue_wait_p95_ms"]["value"] >= 0
