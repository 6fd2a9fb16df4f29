"""The reduction from a profiler trace to busy time, operation time and
named idle gaps, on a trace recorded on a TPU v5e (eleven ticks of the
batch-1 speech cell, cut to the traced window) and on hand-made cases."""
import json
import os
import re

import numpy as np
import pytest

from harness import main, serve, spec
from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_b1.json")


def test_merge_gaps_and_names():
    m = tr.merge([[5, 7], [0, 2], [1, 3], [6, 9]])
    assert m.tolist() == [[0, 3], [5, 9]]
    assert tr.clipped(m, 1, 6) == 3.0
    g = tr.gaps(m, 0, 10)
    assert g.tolist() == [[3, 5], [9, 10]]
    host = [["outer", 0, 10], ["inner", 2.5, 0.5]]      # name, start, length
    assert tr.name_gaps(g, host) == pytest.approx(
        {"outer": 3e-9})       # the gap mid-points 4 and 9.5
    assert tr.name_gaps(np.array([[2.6, 2.8]]), host) == pytest.approx(
        {"inner": 0.2e-9})
    assert tr.intersect(m, tr.merge([[2, 6]])) == 2.0
    assert tr.short("%op.3 = f32[1] custom-call(...)") == "%op.3"


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return json.load(f)


def _run(rec):
    r = rec["run"]
    return serve.Run(hop=r["hop"], t0=r["t0"], n_pre=r["n_pre"],
                     tick_due=np.array(r["tick_due"]),
                     tick_start=np.array(r["tick_start"]),
                     tick_done=np.array(r["tick_done"]),
                     tick_cpu=np.zeros(len(r["tick_done"])), window=(0, 0),
                     arrival_tick=np.zeros(1, np.int64),
                     adm_tick=np.zeros(1, np.int64),
                     served=np.array([len(r["tick_done"])]), outputs=[])


def test_recorded_trace_reduces_as_on_the_chip(recorded):
    ev = {"devices": {int(k): v for k, v in recorded["devices"].items()},
          "host": recorded["host"]}
    red = main.reduce_trace(ev, _run(recorded), tuple(recorded["traced"]), 1)
    want = recorded["reduced"]
    for k in ("lo", "hi", "window_s", "busy_s", "tick_busy_s", "tick_s"):
        assert red[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["tick_busy_s"] <= red["tick_s"]
    # busy and idle partition the window
    assert red["busy_s"] + sum(red["idle"].values()) == pytest.approx(
        red["window_s"], rel=1e-6)
    # device operations do not overlap on one chip: their time is the busy
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"],
                                                     rel=0.05)


def test_recorded_trace_names_the_delta_kernel(recorded):
    mod = spec.load_module(os.path.join(spec.BENCH, "metrics",
                                        "delta_q8_roofline.py"), "roof")
    rx = re.compile(mod.KERNELS)
    lo, hi = recorded["reduced"]["lo"], recorded["reduced"]["hi"]
    hits = [e for e in recorded["devices"]["0"]
            if lo <= e[1] < hi and rx.search(e[0])]
    ka, kb = recorded["traced"]
    assert len(hits) == 2 * (kb - ka)          # one per layer and tick
    assert {tr.short(e[0]) for e in hits} <= set(recorded["reduced"]["ops"])
