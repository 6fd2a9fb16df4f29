"""The readers of the program's own host spans, on a hand-made trace:
milliseconds of one span name per ``bench.tick``, both counted over the
traced window ``[lo, hi)``."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

from harness import spec

MS = 1e6                                  # trace durations are in ns


def _spans():
    path = os.path.join(spec.BENCH, "metrics", "_spans.py")
    s = importlib.util.spec_from_file_location("bench_spans", path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m.ms_per_tick


def _reading(host, lo=100.0, hi=400.0):
    return SimpleNamespace(trace={"host": host, "lo": lo, "hi": hi})


TICKS = [["bench.tick", 100.0, 90.0], ["bench.tick", 200.0, 90.0],
         ["bench.tick", 300.0, 90.0],
         ["bench.tick", 400.0, 90.0]]                 # at hi: outside


def test_sums_the_window_and_divides_by_its_ticks():
    host = TICKS + [
        ["engine.h2d", 101.0, 1 * MS], ["engine.h2d", 201.0, 2 * MS],
        ["engine.h2d", 301.0, 3 * MS],
        ["engine.h2d", 99.0, 50 * MS],                 # before lo
        ["engine.h2d", 400.0, 50 * MS],                # at hi
        ["engine.launch", 102.0, 7 * MS]]
    ms_per_tick = _spans()
    assert ms_per_tick(_reading(host), "engine.h2d") == pytest.approx(2.0)
    assert ms_per_tick(_reading(host), "engine.launch") == pytest.approx(
        7.0 / 3)


def test_missing_trace_ticks_or_span_read_none():
    ms_per_tick = _spans()
    host = TICKS + [["engine.h2d", 101.0, MS]]
    assert ms_per_tick(SimpleNamespace(trace=None), "engine.h2d") is None
    assert ms_per_tick(_reading(host[3:]), "engine.h2d") is None
    assert ms_per_tick(_reading(host), "engine.unpack") is None
    # a span found only outside the window is not found
    assert ms_per_tick(_reading(host, lo=150.0), "engine.h2d") is None


def test_a_window_with_no_harvest_reads_zero_beside_the_batchers_spans():
    ms_per_tick = _spans()
    host = TICKS + [["batcher.slice", 105.0, MS]]
    assert ms_per_tick(_reading(host), "batcher.harvest",
                       zero_beside="batcher.slice") == 0.0
    # a program that writes no batcher spans reads as missing, not as 0
    assert ms_per_tick(_reading(TICKS), "batcher.harvest",
                       zero_beside="batcher.slice") is None
    host.append(["batcher.harvest", 310.0, 6 * MS])
    assert ms_per_tick(_reading(host), "batcher.harvest",
                       zero_beside="batcher.slice") == pytest.approx(2.0)


@pytest.mark.parametrize("metric, span", [
    ("engine.h2d_ms_per_tick", "engine.h2d"),
    ("engine.launch_ms_per_tick", "engine.launch"),
    ("engine.h2d_ms_per_tick.batched", "engine.h2d"),
    ("engine.launch_ms_per_tick.batched", "engine.launch"),
    ("batcher.slice_ms_per_tick", "batcher.slice"),
    ("batcher.harvest_ms_per_tick", "batcher.harvest"),
])
def test_each_reader_reads_its_own_span(metric, span):
    names = ["engine.h2d", "engine.launch", "engine.unpack", "batcher.slice",
             "batcher.harvest"]
    host = TICKS + [[n, 110.0 + i, (i + 1) * 3 * MS]
                    for i, n in enumerate(names)]
    got = spec.load_reader(metric).read(_reading(host))
    assert got == pytest.approx(names.index(span) + 1.0)
