"""Host time of the batcher's own work per tick: the span around
``DeltaStreamBatcher.step`` less the span around the engine step inside
it (admission, frame staging, per-stream output slices, harvest), summed
over the window and divided by the window's ticks."""
LAYER = "batcher (serve/scheduler.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms.batched"


def read(r):
    b, e = r.spans.get("batcher.step"), r.spans.get("engine.step")
    if b is None or not len(b) or e is None:
        return None
    own = (b[:, 1] - b[:, 0]).sum() - (e[:, 1] - e[:, 0]).sum()
    return 1e3 * float(own) / len(r.run.window_ticks())
