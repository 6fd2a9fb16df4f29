"""Share of the traced window in which no operation ran on the device
(1 - the union of device-op intervals over the window), averaged over
the chips used."""
LAYER = "device"
UNIT = "%"
MOVES = "frame_latency_p50_ms"


def read(r):
    if r.trace is None or r.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
