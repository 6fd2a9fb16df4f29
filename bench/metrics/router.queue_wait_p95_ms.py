"""95th percentile, over the streams admitted in the window, of the wait
from a stream's arrival (when its first frame was due) to the start of
the tick that served its first frame."""
import numpy as np

LAYER = "router + fleet (serve/router.py, dist/serving.py)"
UNIT = "ms"
MOVES = "frame_latency_p95_ms"


def read(r):
    run = r.run
    if r.cell.traffic["entry"] != "router":
        return None
    s = np.nonzero(run.adm_tick >= run.n_pre)[0]
    if not len(s):
        return None
    due = run.t0 + run.arrival_tick[s] * run.hop
    return 1e3 * float(np.percentile(run.tick_start[run.adm_tick[s]] - due,
                                     95))
