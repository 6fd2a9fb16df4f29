"""Where the serving path's host spans sit in the profiler's trace.

``DeltaStreamEngine`` writes ``engine.h2d`` / ``engine.launch`` and
``DeltaStreamBatcher`` writes ``batcher.admit`` /
``batcher.stage`` / ``batcher.slice`` / ``batcher.harvest`` as
``jax.profiler.TraceAnnotation``s, on the clock of the device ops; the
benchmark's per-layer readers sum them per tick. These tests record a
trace of a few ticks on the CPU and check each span's count, order and
nesting.
"""
import glob
import os

import jax
import numpy as np

from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.serve.engine import DeltaStreamEngine
from repro.serve.scheduler import DeltaStreamBatcher

I_DIM = 8
ENGINE = ["engine.h2d", "engine.launch"]


def _engine(n_streams=1):
    task = GruTaskConfig(I_DIM, 16, 1, 2, task="regression")
    params = init_gru_model(jax.random.PRNGKey(0), task)
    return DeltaStreamEngine(params, task, n_streams=n_streams)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``engine.*`` and ``batcher.*``
    host spans it wrote as ``(name, start_ns, end_ns)``, outer spans
    before the spans they hold."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    pd = jax.profiler.ProfileData.from_file(paths[0])
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("engine.", "batcher."))]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _disjoint(spans):
    return all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_single_stream_step_writes_only_h2d_then_launch(tmp_path):
    """A batch-1 tick is one compiled call: the output's ``[0]`` slice runs
    inside it, so no span follows the launch."""
    eng = _engine()
    frames = np.random.default_rng(0).normal(size=(3, I_DIM)) \
        .astype(np.float32)
    np.asarray(eng.step(frames[0]))          # compile outside the trace
    spans = _traced(tmp_path, lambda: [np.asarray(eng.step(f))
                                       for f in frames])
    assert [s[0] for s in spans] == ENGINE * 3
    assert _disjoint(spans)


def test_multi_stream_step_has_no_unpack(tmp_path):
    eng = _engine(n_streams=8)
    x = np.ones((8, I_DIM), np.float32)
    np.asarray(eng.step(x))
    spans = _traced(tmp_path, lambda: [np.asarray(eng.step(x))
                                       for _ in range(3)])
    assert [s[0] for s in spans] == ENGINE * 3
    assert _disjoint(spans)


def test_step_many_writes_one_h2d_and_one_launch(tmp_path):
    eng = _engine()
    xs = np.ones((4, I_DIM), np.float32)
    np.asarray(eng.step_many(xs))
    spans = _traced(tmp_path, lambda: np.asarray(eng.step_many(xs)))
    assert [s[0] for s in spans] == ENGINE
    assert _disjoint(spans)


def test_batcher_spans_nest_in_the_tick_and_harvest_only_when_done(
        tmp_path):
    """Two streams of 2 and 4 frames: the short one finishes on tick 1, and
    only that tick harvests. Each tick is wrapped as the benchmark wraps
    it, in a ``batcher.step`` span; every other span of the tick lies
    inside it, one after another, in the order the work happens."""
    eng = _engine(n_streams=2)
    rng = np.random.default_rng(1)
    b = DeltaStreamBatcher(eng)
    for n in (2, 4):                          # compile outside the trace
        b.submit(rng.normal(size=(n, I_DIM)).astype(np.float32))
    b.run_until_drained()
    for n in (2, 4):
        b.submit(rng.normal(size=(n, I_DIM)).astype(np.float32))
    done = []

    def ticks():
        for _ in range(4):
            with jax.profiler.TraceAnnotation("batcher.step"):
                done.append(len(b.step()))

    spans = _traced(tmp_path, ticks)
    assert done == [0, 1, 0, 1]
    outer = [s for s in spans if s[0] == "batcher.step"]
    assert len(outer) == 4
    inner = [s for s in spans if s[0] != "batcher.step"]
    assert _disjoint(inner)
    per_tick = [[s for s in inner if _inside(s, o)] for o in outer]
    assert sum(map(len, per_tick)) == len(inner)
    want = ["batcher.stage"] + ENGINE + ["batcher.slice"]
    assert [s[0] for s in per_tick[0]] == ["batcher.admit"] + want
    assert [s[0] for s in per_tick[1]] == want + ["batcher.harvest"]
    assert [s[0] for s in per_tick[2]] == want
    assert [s[0] for s in per_tick[3]] == want + ["batcher.harvest"]

