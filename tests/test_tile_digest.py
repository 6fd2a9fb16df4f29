"""A tile of streams reads what it read: SHA-256 digests of a 3-stream
engine's outputs over 32 seeded frames, with ``report()``'s tile fields
as float32, for every fused backend of both cells in both kernel modes
(the jnp oracle and the Pallas interpreter). The digests in
``data/tile_digests.json`` were recorded when the stream tile was still a
separate ``*_batch`` backend; the engine now prices the tile from
``n_streams`` and must run the same kernels on the same shapes. This is
the multi-stream twin of ``bench/tests/test_bench_golden.py``, which runs
one stream."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from repro.core.program import compile_delta_program
from repro.models.gru_rnn import GruTaskConfig, init_gru_model, init_lstm_model
from repro.serve.engine import DeltaStreamEngine

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tile_digests.json")
TILE_FIELDS = ("union_gamma_dx", "union_gamma_dh", "tile_est_latency_us",
               "tile_weight_bytes_per_step",
               "weight_bytes_per_stream_per_step")
CASES = [(cell, backend, interpret) for cell in ("gru", "lstm")
         for backend in ("fused", "fused_q8", "fused_q4")
         for interpret in (None, True)]


def _case_id(cell, backend, interpret) -> str:
    return f"{cell}-{backend}-{'interpret' if interpret else 'auto'}"


def _observe(cell, backend, interpret) -> dict:
    """Digest of the outputs of three streams over 32 frames: one quiet
    random walk and two loud companions, so the tile's union firing
    differs from each stream's own."""
    task = GruTaskConfig(12, 32, 2, 3, task="regression",
                         theta_x=0.05, theta_h=0.1)
    init = init_gru_model if cell == "gru" else init_lstm_model
    params = init(jax.random.PRNGKey(19), task)
    prog = compile_delta_program(params, backend=backend, cell=cell,
                                 interpret=interpret)
    rng = np.random.default_rng(19)
    quiet = np.cumsum(rng.normal(size=(32, 1, 12)) * 0.05, axis=0)
    loud = rng.normal(size=(32, 2, 12))
    xs = np.concatenate([quiet, loud], axis=1).astype(np.float32)
    eng = DeltaStreamEngine(prog, task, n_streams=3)
    ys = np.ascontiguousarray(np.asarray(eng.step_many(xs)))
    h = hashlib.sha256()
    h.update(f"{ys.dtype.str}{ys.shape}".encode())
    h.update(ys.tobytes())
    rep = eng.report()
    return {"outputs": h.hexdigest(), "weight_fetch": rep["weight_fetch"],
            **{k: float(np.float32(rep[k])) for k in TILE_FIELDS}}


@pytest.mark.parametrize("cell,backend,interpret", CASES,
                         ids=[_case_id(*c) for c in CASES])
def test_tile_reads_what_it_read(cell, backend, interpret):
    with open(DATA) as f:
        want = json.load(f)[_case_id(cell, backend, interpret)]
    assert _observe(cell, backend, interpret) == want
