"""Host time of the batcher's harvests per tick: the span
``batcher.harvest`` in ``DeltaStreamBatcher.step``, on the ticks where a
stream finishes (the carry's copy to the host, ``close_stream``, the
stack of the stream's outputs and its copy to the host). A traced window
in which no stream finished reads 0.0, where the batcher's
``batcher.slice`` spans show that the program writes them."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import ms_per_tick  # noqa: E402

LAYER = "batcher (serve/scheduler.py)"
UNIT = "ms"
MOVES = "frame_latency_p50_ms.batched"


def read(r):
    return ms_per_tick(r, "batcher.harvest", zero_beside="batcher.slice")
