"""Host time of the engine tick: the span around ``DeltaStreamEngine.step``
(or the fleet's step) until its dispatch returns, summed over the window
and divided by the window's ticks."""
LAYER = "engine tick (serve/engine.py, dist/serving.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms"


def read(r):
    s = r.spans.get("engine.step")
    if s is None or not len(s):
        s = r.spans.get("fleet.step")
    if s is None or not len(s):
        return None
    return 1e3 * float((s[:, 1] - s[:, 0]).sum()) / len(r.run.window_ticks())
