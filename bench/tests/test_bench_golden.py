"""What the cells read does not move when the harness is reorganised:
SHA-256 digests, recorded with the harness as it stood before the model
families and frame kinds became files, of each cell's weights (seed 7,
full size), its schedule (seed 7, a 1-s window: arrival ticks and every
stream's frames), and at the CPU rehearsal's width the reference's
outputs over the first 256 frames of the first eight streams and the
program's outputs over the first 64 frames of stream 0, one stream on the
engine."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from harness import spec, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "golden.json")
with open(DATA) as f:
    GOLDEN = json.load(f)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_cell_reads_what_it_read(shrink, name):
    from repro.serve.engine import DeltaStreamEngine
    cell = spec.load_cell(name)
    fam, cfg, t = cell.family(), cell.config, cell.traffic
    s = traffic.build(t, cfg, 7, 1.0, t["hop_ms"] * 1e-3)
    tiny = shrink(cell).config
    w = fam.weights(tiny, 7)
    ref = cell.reference().replay(w, [f[:256] for f in s.frames[:8]],
                                  **fam.reference_kwargs(tiny))
    task, theta = fam.task(tiny)
    eng = DeltaStreamEngine(fam.program(tiny, w), task, thresholds=theta)
    ys = [np.stack([np.asarray(eng.step(x)) for x in s.frames[0][:64]])]
    got = {"weights": _digest(jax.tree_util.tree_leaves(
               fam.weights(cfg, 7))),
           "schedule": _digest([s.arrival_tick, *s.frames]),
           "reference": _digest(ref), "program": _digest(ys)}
    assert got == GOLDEN[name]
