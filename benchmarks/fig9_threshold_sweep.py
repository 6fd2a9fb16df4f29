"""Paper Fig. 9: throughput & accuracy vs delta threshold Θ.

Trains a small DeltaGRU-CTC digit classifier at each Θ (Θ_x = Θ_h, as in
the paper's Fig. 9) and reports measured temporal sparsity, Eq. 7 effective
throughput, and greedy token error rate. The paper's qualitative claims to
reproduce: ~2x speedup from natural sparsity at Θ=0, rising throughput and
(eventually) rising error with Θ, with a knee near Θ=64 (0.25).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perf_model import estimate_stack
from repro.core.sparsity import GruDims
from repro.data.synthetic import batch_stream, digit_batch
from repro.models.gru_rnn import GruTaskConfig, gru_model_forward, \
    init_gru_model
from repro.train.ctc import ctc_greedy_decode, edit_distance
from repro.train.optim import AdamConfig, constant_schedule
from repro.train.trainer import init_train_state, make_gru_train_step, \
    train_loop

THETAS_Q88 = [0, 8, 32, 64, 128]
H, L, STEPS = 96, 2, 400


def _token_error_rate(params, task, key, n_batches=3):
    ter_num = ter_den = 0
    for i in range(n_batches):
        batch = digit_batch(jax.random.fold_in(key, i), batch=8, max_t=64,
                            max_l=4)
        out, stats = gru_model_forward(params, task, batch["features"],
                                       collect_sparsity=True)
        lp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        dec = np.asarray(ctc_greedy_decode(lp, batch["in_lens"]))
        labs = np.asarray(batch["labels"])
        lens = np.asarray(batch["lab_lens"])
        for b in range(dec.shape[0]):
            hyp = [int(x) for x in dec[b] if x >= 0]
            refl = [int(x) for x in labs[b, :lens[b]]]
            ter_num += edit_distance(hyp, refl)
            ter_den += len(refl)
    return ter_num / max(ter_den, 1), stats


def run() -> list[str]:
    lines = []
    for theta_int in THETAS_Q88:
        theta = theta_int / 256.0
        task = GruTaskConfig(40, H, L, 12, task="ctc",
                             theta_x=theta, theta_h=theta)
        params = init_gru_model(jax.random.PRNGKey(0), task)
        step = make_gru_train_step(
            task, AdamConfig(schedule=constant_schedule(3e-3)))
        state = init_train_state(params)
        stream = batch_stream(digit_batch, jax.random.PRNGKey(1), batch=16,
                              max_t=64, max_l=4)
        t0 = time.perf_counter()
        state, hist = train_loop(step, state, stream, STEPS)
        train_s = time.perf_counter() - t0
        ter, stats = _token_error_rate(state.params, task,
                                       jax.random.PRNGKey(2))
        gdx = float(stats["gamma_dx"])
        gdh = float(stats["gamma_dh"])
        est = estimate_stack(GruDims(40, H, L), gdx, gdh)
        lines.append(
            f"fig9.theta_{theta_int},{est.latency_s * 1e6:.2f},"
            f"TER={ter:.3f} gamma_dx={gdx:.3f} gamma_dh={gdh:.3f} "
            f"eff_tput={est.throughput_ops / 1e9:.2f}GOp/s "
            f"train_s={train_s:.0f}")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
