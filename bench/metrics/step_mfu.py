"""The whole step's share of the chip's bf16 peak: dense-equivalent
operations of the network for the stream-frames served in the window
(2 x the unpadded stack and head weights per stream-frame, the paper's
"effective throughput") over the summed tick time (tick start to output
ready) times the peak times the chips."""
import numpy as np

LAYER = "program step (core/program.py, core/deltagru.py)"
UNIT = "%"
MOVES = "frame_latency_p50_ms"


def read(r):
    run = r.run
    w = run.window_ticks()
    if not len(w):
        return None
    frames = sum(int(np.sum(run.serve_ticks(s) >= run.n_pre))
                 for s in run.streams_in_window())
    busy = float(np.sum(run.tick_done[w] - run.tick_start[w]))
    ops = 2.0 * r.dense_weights * frames
    return 100.0 * ops / (busy * r.peaks["bf16_flops_per_s"] * r.chips)
