"""Host time of the engine's input hand-off per tick: the span
``engine.h2d`` in ``DeltaStreamEngine.step`` (the numpy snapshot, the
copy to the device and the shape check; a single-stream engine reshapes
``[I]`` -> ``[1, I]`` inside its jitted step, under ``engine.launch``)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import ms_per_tick  # noqa: E402

LAYER = "engine tick (serve/engine.py, dist/serving.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms"


def read(r):
    return ms_per_tick(r, "engine.h2d")
