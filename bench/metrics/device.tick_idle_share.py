"""Share of the ticks' own time (the union of each traced tick's span from
when it was due to when its outputs were ready) in which no operation ran
on the device: what the host path costs each frame."""
LAYER = "device"
UNIT = "%"
MOVES = "frame_latency_p50_ms"


def read(r):
    if r.trace is None or r.trace["tick_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["tick_busy_s"] / r.trace["tick_s"])
