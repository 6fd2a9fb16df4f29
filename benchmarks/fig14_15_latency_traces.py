"""Paper Figs. 14/15: per-frame latency traces.

Fig. 14: EdgeDRNN latency per frame over a spoken-digit stream — latency
drops during silence (slowly-changing inputs fire few deltas). We stream a
synthetic utterance (digits + silence gaps) through the batch-1 engine and
report active-vs-silent estimated latency.

Fig. 15: the AMPRO prosthetic 2L-128H network — EdgeDRNN-model latency vs a
measured dense-GRU CPU step on THIS host (the paper's ARM comparison,
rescaled to whatever CPU we're on).

Both engines run compiled ``fused_q8`` programs
(``quantize_gru_model(params)`` -> ``GruStreamEngine(program, task)``):
the Eq. 7 latency model prices the streamed weight width per backend
(``spec_for_backend``), and the paper's figures are about the INT8
hardware — the quantized program is its operating point (K=8 PEs on the
64-bit bus) *and* its actual fixed-point arithmetic.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.deltagru import gru_step, init_gru_stack
from repro.data.synthetic import digit_batch
from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.quant.export import quantize_gru_model
from repro.serve.engine import GruStreamEngine


def run() -> list[str]:
    lines = []

    # ---- Fig. 14: digit stream with silence ----
    task = GruTaskConfig(40, 128, 2, 12, task="ctc",
                         theta_x=16 / 256, theta_h=16 / 256)
    params = init_gru_model(jax.random.PRNGKey(0), task)
    eng = GruStreamEngine(quantize_gru_model(params), task)
    batch = digit_batch(jax.random.PRNGKey(1), batch=1, max_t=96, max_l=4)
    feats = np.asarray(batch["features"][:, 0])            # [T, 40]
    active_mask = np.abs(feats).sum(-1) > 0.5 * np.abs(feats).sum(-1).mean()
    lat = []
    for f in feats:
        before = eng.stats.est_latency_s
        eng.step(f)
        lat.append((eng.stats.est_latency_s - before) * 1e6)
    lat = np.asarray(lat)
    lines.append(
        f"fig14.active_us,{lat[active_mask].mean():.2f},"
        f"silent_us={lat[~active_mask].mean():.2f} "
        f"ratio={lat[active_mask].mean() / max(lat[~active_mask].mean(), 1e-9):.2f} "
        f"(paper: latency drops in quiet periods)")

    # ---- Fig. 15: AMPRO 2L-128H, EdgeDRNN model vs this-host dense GRU ----
    task_a = GruTaskConfig(8, 128, 2, 4, task="regression",
                           theta_x=16 / 256, theta_h=16 / 256)
    params_a = init_gru_model(jax.random.PRNGKey(2), task_a)
    eng_a = GruStreamEngine(quantize_gru_model(params_a), task_a)
    for t in range(200):
        eng_a.step(np.sin(np.arange(8) * 0.7 + t * 0.1))
    rep = eng_a.report()

    # dense batch-1 GRU step wall time on this CPU (jitted, after warmup)
    gp = init_gru_stack(jax.random.PRNGKey(3), 8, 128, 2)

    @jax.jit
    def dense_step(hs, x):
        inp = x
        out = []
        for p, h in zip(gp, hs):
            h = gru_step(p, h, inp)
            out.append(h)
            inp = h
        return tuple(out)

    hs = tuple(jnp.zeros((1, 128)) for _ in range(2))
    x = jnp.ones((1, 8))
    dense_step(hs, x)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(300):
        hs = dense_step(hs, x)
    jax.block_until_ready(hs)
    host_us = (time.perf_counter() - t0) / 300 * 1e6
    lines.append(
        f"fig15.ampro,{rep['mean_est_latency_us']:.2f},"
        f"edgedrnn_model_us={rep['mean_est_latency_us']:.2f} "
        f"host_dense_gru_us={host_us:.1f} "
        f"speedup={host_us / max(rep['mean_est_latency_us'], 1e-9):.0f}x "
        f"(paper: 27x vs ARM A9 w/ sparsity, 16us vs 428us)")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
