"""Host time of the single-stream engine's ``out[0]`` slice per tick:
the span ``engine.unpack`` in ``DeltaStreamEngine.step``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import ms_per_tick  # noqa: E402

LAYER = "engine tick (serve/engine.py, dist/serving.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms"


def read(r):
    return ms_per_tick(r, "engine.unpack")
