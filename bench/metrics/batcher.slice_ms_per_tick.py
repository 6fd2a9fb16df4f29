"""Host time of the batcher's output slicing per tick: the span
``batcher.slice`` in ``DeltaStreamBatcher.step`` (the reshape of the
engine's output and one ``out[sid]`` slice per live stream)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import ms_per_tick  # noqa: E402

LAYER = "batcher (serve/scheduler.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms.batched"


def read(r):
    return ms_per_tick(r, "batcher.slice")
