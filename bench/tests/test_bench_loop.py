"""Tick-grid latency accounting: frames are timed from when they were
due, so a stalled tick raises the latency of the frames after it, and a
stream that waits for a slot carries the wait in every frame."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import serve, traffic

HOP = 0.05          # long enough that a loaded test machine keeps time


class _StallingEngine:
    """``step`` echoes the frame; one tick sleeps ``stall_s``."""

    task = SimpleNamespace(output_size=2)

    def __init__(self, stall_at, stall_s, every_s=0.0):
        self.k, self.stall_at, self.stall_s = 0, stall_at, stall_s
        self.every_s = every_s

    def step(self, x):
        if self.k == self.stall_at:
            time.sleep(self.stall_s)
        if self.every_s:
            time.sleep(self.every_s)
        self.k += 1
        return np.array([x[0], 0.0], np.float32)


def _engine_run(stall_at=-1, stall_s=0.0):
    frames = np.arange(40, dtype=np.float32)[:, None].repeat(3, 1)
    return serve.engine_loop(
        _StallingEngine(stall_at, stall_s), frames, hop=HOP, n_pre=0,
        seconds=1.0, closed=False, spans=serve.Spans(False),
        tracer=serve.Tracer(None))


def test_stall_carries_into_later_frames():
    run = _engine_run(stall_at=10, stall_s=0.175)
    lat = run.frame_latency() * 1e3
    assert len(lat) == 20
    assert lat[10] >= 175
    assert lat[11] >= 125 and lat[12] >= 75     # still late: open loop
    assert lat[11] > lat[12] > lat[13]
    assert np.all(lat[:10] < 25) and np.all(lat[15:] < 25)
    assert np.array_equal(run.outputs[0][:, 0], np.arange(20))


def test_unstalled_ticks_are_on_time():
    run = _engine_run()
    late = run.tick_start - run.tick_due
    assert np.all(late >= 0) and np.median(late) < HOP / 5


def test_closed_loop_sends_when_the_output_is_back():
    eng = _StallingEngine(-1, 0.0, every_s=5e-4)
    frames = np.zeros((5000, 3), np.float32)
    run = serve.engine_loop(eng, frames, hop=HOP, n_pre=2, seconds=0.2,
                            closed=True, spans=serve.Spans(False),
                            tracer=serve.Tracer(None))
    w = run.window_ticks()
    assert len(w) > 20
    assert np.all(run.tick_start[w][1:] >= run.tick_done[w][:-1])
    assert run.window[1] - run.window[0] >= 0.2


def test_queued_stream_carries_its_wait(tiny_cell):
    """One slot, two streams due on tick 0: the second waits for the
    first, and each of its frames is late by that wait."""
    from repro.serve.engine import DeltaStreamEngine
    from repro.serve.scheduler import DeltaStreamBatcher
    cell = tiny_cell("gru768-q8-s128-speech", slots=1)
    fam = cell.family()
    w = fam.weights(cell.config, 0)
    task, theta = fam.task(cell.config)
    eng = DeltaStreamEngine(fam.program(cell.config, w), task,
                            thresholds=theta, n_streams=2)
    b = DeltaStreamBatcher(eng)
    rng = np.random.default_rng(0)
    frames = [rng.standard_normal((n, 40)).astype(np.float32)
              for n in (6, 4, 3)]
    sched = traffic.Schedule(np.array([0, 0, 0]), frames)
    serve.warm_batcher(b, frames[0], sched.lengths)
    eng._slot_busy[1] = True        # hold one of the two slots: one free
    run = serve.batcher_loop(b, sched, hop=HOP, n_pre=0, seconds=0.7,
                             spans=serve.Spans(False),
                             tracer=serve.Tracer(None))
    assert list(run.adm_tick) == [0, 6, 10]
    assert list(run.served) == [6, 4, 3]
    lat = run.frame_latency() * 1e3
    first, second, third = lat[:6], lat[6:10], lat[10:]
    assert np.all(second >= 6 * HOP * 1e3 - 1)
    assert np.all(third >= 10 * HOP * 1e3 - 1)
    assert first.max() < 6 * HOP * 1e3
    assert [len(o) for o in run.outputs] == [6, 4, 3]


class _TokenEngine:
    """``step`` records each frame's type and shape and, after a
    millisecond, answers with a row of ``width`` logits that carries the
    frame."""

    def __init__(self, width):
        self.width, self.seen = width, []

    def step(self, x):
        self.seen.append((x.dtype, x.shape))
        time.sleep(1e-3)
        y = np.zeros(self.width, np.float32)
        y[0] = x.sum()
        return y


@pytest.mark.parametrize("closed", [False, True])
def test_token_frames_reach_step_unchanged_and_wide_outputs_grow(closed):
    """An ``int32`` schedule of token ids reaches ``step`` as it is, and
    a 100,352-wide output is kept in blocks grown as frames are served
    (ten rows to a block here), not preallocated for the whole cap."""
    eng = _TokenEngine(100_352)
    frames = np.arange(5000, dtype=np.int32)[:, None]
    run = serve.engine_loop(eng, frames, hop=0.002, n_pre=2, seconds=0.1,
                            closed=closed, spans=serve.Spans(False),
                            tracer=serve.Tracer(None))
    n = int(run.served[0])
    assert n > 2 * serve.BLOCK_BYTES // (4 * 100_352)
    assert set(eng.seen) == {(np.dtype(np.int32), (1,))}
    assert len(eng.seen) == n
    out = run.outputs[0]
    assert out.shape == (n, 100_352) and out.dtype == np.float32
    assert np.array_equal(out[:, 0], np.arange(n))
