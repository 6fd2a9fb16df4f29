"""``delta_q8_roofline`` in the cells that serve many streams through the batcher,
where it moves their own median frame latency."""
from harness.spec import load_reader

_base = load_reader("delta_q8_roofline")
LAYER, UNIT, read = _base.LAYER, _base.UNIT, _base.read
DEVICE_ONLY = _base.DEVICE_ONLY
MOVES = "frame_latency_p50_ms.batched"
