"""The timed loops: the system under test driven on a wall-clock grid.

Three entry points, chosen by the traffic mix's ``entry``:

* ``engine``: one stream through ``DeltaStreamEngine.step``; a frame's
  output is ready when the returned array has been copied to the host.
* ``batcher``: streams through ``DeltaStreamBatcher.step`` (admission, one
  engine step for every slot, harvest); a frame's output is ready when
  ``block_until_ready`` on the tick's per-stream outputs has returned.
* ``router``: streams through ``StreamRouter.tick`` over a
  ``ShardedStreamFleet``; outputs are ready as for the batcher.

``paced`` loops run tick ``k`` at ``t0 + k * hop`` or, when the tick before
it overran, as soon as it ends, so lateness carries into later frames. A
frame's latency runs from when it was due (its stream's arrival tick plus
its index) to when its output is ready. The ``closed`` loop sends a frame
when the previous output is back; its latency runs from the send.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

LEAD_S = 0.02            # from the end of set-up to tick 0
BLOCK_BYTES = 1 << 22    # outputs are kept in blocks of about this size


def pace(due: float) -> None:
    """Return at ``due`` (sleep, then spin the last stretch)."""
    while True:
        d = due - time.perf_counter()
        if d <= 0:
            return
        if d > 0.002:
            time.sleep(d - 0.0015)


class Spans:
    """Host spans around the benchmark's calls into the program's layers.

    Only a traced run records them: each call is timed on the host clock
    and written into the profiler's trace as a ``TraceAnnotation`` of the
    same name, so idle gaps on the device can be named by what the host
    was doing.
    """

    def __init__(self, on: bool):
        self.on = on
        self.t: dict[str, list] = collections.defaultdict(list)

    def timed(self, name: str, fn):
        """``fn``, timed and annotated under ``name`` when spans are on."""
        if not self.on:
            return fn
        rec = self.t[name]
        ann = jax.profiler.TraceAnnotation

        def f(*a, **k):
            with ann(name):
                t0 = time.perf_counter()
                out = fn(*a, **k)
                rec.append((t0, time.perf_counter()))
            return out
        return f

    def wrap(self, obj, attr: str, name: str) -> None:
        """Time the method ``obj.attr`` of one instance."""
        if self.on:
            setattr(obj, attr, self.timed(name, getattr(obj, attr)))

    def clear(self) -> None:
        for v in self.t.values():
            v.clear()

    def arrays(self) -> dict:
        return {k: np.asarray(v, np.float64).reshape(-1, 2)
                for k, v in self.t.items()}


class Tracer:
    """Runs the profiler from before the loop's first tick (so starting it
    delays no tick) to the start of tick ``stop`` (paced loops) or
    ``span_s`` into the window (the closed loop); a no-op when off.
    ``traced`` is then the pair of loop ticks ``(first, end)`` of the
    window that the trace covers."""

    def __init__(self, trace_dir: str | None, stop: int = -1,
                 span_s: float = 3.0):
        self.dir, self.stop, self.span_s = trace_dir, stop, span_s
        self.running = False
        self.traced = None

    def on(self) -> None:
        if self.dir is None or self.running:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True

    def off(self, first: int, end: int) -> None:
        if self.running:
            jax.profiler.stop_trace()
            self.running = False
            self.traced = (first, end)

    def before(self, k: int, n_pre: int) -> None:
        if self.running and k == self.stop:
            self.off(n_pre, k)


class Rows:
    """Per-frame outputs stored as they are served, in blocks of about
    ``BLOCK_BYTES`` allocated as they fill (the first output fixes the
    shape and dtype), so a wide output costs memory only for the frames
    served."""

    def __init__(self):
        self.blocks, self.base, self.end = [], 0, 0

    def put(self, k: int, y) -> None:
        """Store the output of frame ``k``; frames come in order."""
        if k >= self.end:
            rows = max(1, BLOCK_BYTES // max(y.nbytes, 1))
            self.blocks.append(np.empty((rows, *y.shape), y.dtype))
            self.base, self.end = k, k + rows
        self.blocks[-1][k - self.base] = y

    def take(self, n: int) -> np.ndarray:
        """The first ``n`` outputs as one array."""
        return np.concatenate(self.blocks)[:n]


@dataclass
class Run:
    """What a timed loop leaves for the metrics and the check."""

    hop: float
    t0: float
    n_pre: int
    tick_due: np.ndarray
    tick_start: np.ndarray
    tick_done: np.ndarray
    tick_cpu: np.ndarray            # process CPU seconds within each tick
    window: tuple
    arrival_tick: np.ndarray        # per stream
    adm_tick: np.ndarray            # per stream; -1 not yet, -2 rejected
    served: np.ndarray              # frames served per stream
    outputs: list                   # per stream, [served, O] numpy
    rejected: int = 0
    closed: bool = False

    @property
    def n_ticks(self) -> int:
        return len(self.tick_done)

    def window_ticks(self) -> np.ndarray:
        """Loop ticks inside the measured window."""
        return np.arange(self.n_pre, self.n_ticks)

    def frame_latency(self) -> np.ndarray:
        """Latency of every stream-frame served in a window tick (s)."""
        if self.closed:
            w = self.window_ticks()
            return self.tick_done[w] - self.tick_start[w]
        out = []
        for s in np.nonzero(self.adm_tick >= 0)[0]:
            j = np.arange(self.served[s])
            k = self.adm_tick[s] + j
            keep = k >= self.n_pre
            due = self.t0 + (self.arrival_tick[s] + j[keep]) * self.hop
            out.append(self.tick_done[k[keep]] - due)
        return np.concatenate(out) if out else np.zeros(0)

    def queued(self, k: int) -> int:
        """Streams that had arrived by tick ``k`` and were not yet in
        service (nor rejected) when it started."""
        arrived = self.arrival_tick <= k
        waiting = (self.adm_tick == -1) | (self.adm_tick > k)
        return int(np.sum(arrived & waiting))

    def serve_ticks(self, s: int) -> np.ndarray:
        """The loop tick that served each frame of stream ``s``."""
        return self.adm_tick[s] + np.arange(self.served[s])

    def streams_in_window(self) -> list:
        last = self.adm_tick + self.served - 1
        return [int(s) for s in np.nonzero(
            (self.adm_tick >= 0) & (self.served > 0)
            & (last >= self.n_pre))[0]]


def _paced(n: int, n_pre: int, hop: float, spans: Spans, tracer: Tracer,
           tick) -> tuple:
    """Run ``tick(k)`` for ``k < n`` on the grid ``t0 + k * hop`` (or as
    soon as the tick before it ends); returns ``t0``, the due, start and
    done times of every tick and the CPU time spent in it. Host spans
    restart with the window."""
    due, start, done = np.empty(n), np.empty(n), np.empty(n)
    cpu = np.empty(n)
    wait_due = spans.timed("bench.pace", pace)
    ann = jax.profiler.TraceAnnotation if spans.on else None
    t0 = time.perf_counter() + LEAD_S
    for k in range(n):
        tracer.before(k, n_pre)
        if k == n_pre:
            spans.clear()
        due[k] = t0 + k * hop
        wait_due(due[k])
        c0, ts = time.process_time(), time.perf_counter()
        if ann is None:
            tick(k)
        else:
            with ann("bench.tick"):
                tick(k)
        start[k], done[k] = ts, time.perf_counter()
        cpu[k] = time.process_time() - c0
    tracer.off(n_pre, n)
    return t0, due, start, done, cpu


# -- the engine: one stream ---------------------------------------------------

def warm_engine(eng, frame) -> None:
    for _ in range(3):
        np.asarray(eng.step(frame))
    eng.reset()


def engine_loop(eng, frames: np.ndarray, *, hop: float, n_pre: int,
                seconds: float, closed: bool, spans: Spans,
                tracer: Tracer) -> Run:
    """One stream, paced on the grid or in a closed loop."""
    step = eng.step
    fetch = spans.timed("bench.output", np.asarray)
    if closed:
        return _closed_loop(step, fetch, frames, hop=hop, n_pre=n_pre,
                            seconds=seconds, spans=spans, tracer=tracer)
    n = n_pre + int(round(seconds / hop))
    outs = Rows()
    put = outs.put

    def tick(k):
        put(k, fetch(step(frames[k])))

    t0, due, start, done, cpu = _paced(n, n_pre, hop, spans, tracer, tick)
    return Run(hop=hop, t0=t0, n_pre=n_pre, tick_due=due, tick_start=start,
               tick_done=done, tick_cpu=cpu, window=(t0 + n_pre * hop, t0 + n * hop),
               arrival_tick=np.zeros(1, np.int64),
               adm_tick=np.zeros(1, np.int64), served=np.array([n]),
               outputs=[outs.take(n)])


def _closed_loop(step, fetch, frames, *, hop, n_pre, seconds, spans,
                 tracer) -> Run:
    """Send each frame when the previous output is back, for ``n_pre *
    hop`` seconds before the window and ``seconds`` in it."""
    cap = len(frames)
    due, done, cpu = np.empty(cap), np.empty(cap), np.empty(cap)
    outs = Rows()
    put = outs.put
    ann = jax.profiler.TraceAnnotation if spans.on else None
    t0 = time.perf_counter() + LEAD_S
    k0 = w0 = w1 = None
    k = 0
    while k < cap:
        ts = time.perf_counter()
        if k0 is None and ts >= t0 + n_pre * hop:
            k0, w0 = k, ts
            spans.clear()
        if tracer.running and w0 is not None and ts >= w0 + tracer.span_s:
            tracer.off(k0, k)
        if w0 is not None and ts >= w0 + seconds:
            w1 = ts
            break
        c0 = time.process_time()
        if ann is None:
            put(k, fetch(step(frames[k])))
        else:
            with ann("bench.tick"):
                put(k, fetch(step(frames[k])))
        due[k], done[k] = ts, time.perf_counter()
        cpu[k] = time.process_time() - c0
        k += 1
    if w1 is None:
        raise RuntimeError(
            f"the closed loop used all {cap} frames before its window "
            "closed; raise max_frames_per_s in the traffic mix")
    tracer.off(k0, k)
    return Run(hop=hop, t0=t0, n_pre=k0, tick_due=due[:k],
               tick_start=due[:k], tick_done=done[:k], tick_cpu=cpu[:k],
               window=(w0, w1),
               arrival_tick=np.zeros(1, np.int64),
               adm_tick=np.zeros(1, np.int64), served=np.array([k]),
               outputs=[outs.take(k)], closed=True)


# -- the batcher: many streams on one engine ----------------------------------

def _warm_harvest(row, lengths) -> None:
    """Compile what a harvest of each stream length runs: ``jnp.stack`` of
    its per-frame output slices, which is ``jnp.concatenate`` of the slices
    each given a leading axis, and the copy to the host. The same
    concatenations, on one slice repeated, cost a fraction of the stack's
    per-slice dispatches."""
    e = jnp.expand_dims(row, 0)
    for ln in sorted({int(x) for x in lengths}):
        np.asarray(jnp.concatenate([e] * ln, axis=0))


def warm_batcher(batcher, frames: np.ndarray, lengths) -> None:
    eng = batcher.engine
    for _ in range(eng.n_streams + 1):
        batcher.submit(frames[:2])
    batcher.run_until_drained()
    probe = jnp.reshape(eng.step(np.zeros(
        (eng.n_streams, *frames.shape[1:]), frames.dtype)),
        (eng.n_streams, -1))
    _warm_harvest(probe[0], lengths)


def batcher_loop(batcher, sched, *, hop: float, n_pre: int, seconds: float,
                 spans: Spans, tracer: Tracer) -> Run:
    n = n_pre + int(round(seconds / hop))
    adm = np.zeros(n, np.int64)
    counters, step, slots = batcher.counters, batcher.step, batcher.slots
    frames, arr = sched.frames, sched.arrival_tick
    wait = spans.timed("bench.output", jax.block_until_ready)
    finished, uids = [], []
    nxt = [0]

    def tick(k):
        while nxt[0] < len(frames) and arr[nxt[0]] <= k:
            uids.append(batcher.submit(frames[nxt[0]]))
            nxt[0] += 1
        a0 = counters["admitted"]
        finished.extend(step())
        wait([r.outputs[-1] for r in slots if r is not None])
        adm[k] = counters["admitted"] - a0

    t0, due, start, done, cpu = _paced(n, n_pre, hop, spans, tracer, tick)
    n_streams = len(frames)
    # admission is first in, first out, so the u-th stream submitted is the
    # u-th admitted
    cum = np.cumsum(adm)
    adm_tick = np.full(n_streams, -1, np.int64)
    n_adm = int(cum[-1]) if n else 0
    adm_tick[:n_adm] = np.searchsorted(cum, np.arange(n_adm), side="right")
    lengths = sched.lengths
    served = np.where(adm_tick >= 0, np.minimum(lengths, n - adm_tick), 0)
    by_uid = {r.uid: r.outputs for r in finished}
    live = {r.uid: r.outputs for r in slots if r is not None}
    o_dim = batcher.engine.task.output_size
    outputs = []
    for u in uids:
        o = by_uid.get(u, live.get(u, []))
        outputs.append(np.asarray(jax.device_get(list(o)), np.float32)
                       .reshape(len(o), o_dim))
    outputs += [np.zeros((0, o_dim), np.float32)] * (n_streams - len(uids))
    return Run(hop=hop, t0=t0, n_pre=n_pre, tick_due=due, tick_start=start,
               tick_done=done, tick_cpu=cpu, window=(t0 + n_pre * hop, t0 + n * hop),
               arrival_tick=arr, adm_tick=adm_tick, served=served,
               outputs=outputs)


# -- the router over a sharded fleet ------------------------------------------

def warm_router(router, frames: np.ndarray, lengths) -> None:
    fleet = router.fleet
    for _ in range(fleet.n_streams + 1):
        router.submit(frames[:2])
    router.run_until_drained()
    y = fleet.step(np.zeros((fleet.n_streams, *frames.shape[1:]),
                            frames.dtype))
    for sh in range(fleet.n_shards):
        _warm_harvest(y[fleet.shard_slots(sh)[0]], lengths)


def router_loop(router, sched, *, hop: float, n_pre: int, seconds: float,
                spans: Spans, tracer: Tracer) -> Run:
    n = n_pre + int(round(seconds / hop))
    live = router._slot_rec     # the router's in-flight book (read only)
    frames, arr = sched.frames, sched.arrival_tick
    wait = spans.timed("bench.output", jax.block_until_ready)
    uid2s, rejected = {}, []
    nxt = [0]
    tick0 = router.tick_no

    def tick(k):
        while nxt[0] < len(frames) and arr[nxt[0]] <= k:
            uid, ok = router.submit(frames[nxt[0]])
            uid2s[uid] = nxt[0]
            if not ok:
                rejected.append(nxt[0])
            nxt[0] += 1
        router.tick()
        wait([r.outputs[-1] for r in live.values()])

    t0, due, start, done, cpu = _paced(n, n_pre, hop, spans, tracer, tick)
    n_streams = len(frames)
    adm_tick = np.full(n_streams, -1, np.int64)
    adm_tick[rejected] = -2
    served = np.zeros(n_streams, np.int64)
    o_dim = router.fleet.task.output_size
    outputs = [np.zeros((0, o_dim), np.float32)] * n_streams
    for res in router.results:
        s = uid2s.get(res.uid)
        if s is None or res.status != "ok":
            continue
        served[s] = len(res.outputs)
        adm_tick[s] = res.done_tick - tick0 - served[s] + 1
        outputs[s] = np.asarray(res.outputs, np.float32).reshape(
            served[s], o_dim)
    for rec in live.values():
        s = uid2s[rec.uid]
        served[s] = rec.cursor
        adm_tick[s] = n - rec.cursor
        outputs[s] = np.asarray(jax.device_get(list(rec.outputs)),
                                np.float32).reshape(rec.cursor, o_dim)
    return Run(hop=hop, t0=t0, n_pre=n_pre, tick_due=due, tick_start=start,
               tick_done=done, tick_cpu=cpu, window=(t0 + n_pre * hop, t0 + n * hop),
               arrival_tick=arr, adm_tick=adm_tick, served=served,
               outputs=outputs, rejected=len(rejected))
