"""``engine.launch_ms_per_tick`` in the cells that serve many streams
through the batcher, where it moves their own median frame latency."""
from harness.spec import load_reader

_base = load_reader("engine.launch_ms_per_tick")
LAYER, UNIT, read = _base.LAYER, _base.UNIT, _base.read
MOVES = "frame_latency_p50_ms.batched"
