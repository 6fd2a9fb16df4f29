"""Paper Figs. 10/11 + contribution #2: dual thresholds (Θ_x, Θ_h).

Trains one DeltaGRU on the SensorsGas-like regression, then sweeps the
(Θ_x, Θ_h) grid at inference, reporting RMSE / R^2 / Γ_Δx / Γ_Δh per cell.
Claims reproduced:
  * Γ_Δx responds chiefly to Θ_x and Γ_Δh to Θ_h (weak cross-coupling),
  * accuracy degrades faster in Θ_x than Θ_h,
  * the best dual point beats the best global point on hidden sparsity at
    iso-accuracy (paper: +16 %).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import batch_stream, gas_batch
from repro.models.gru_rnn import GruTaskConfig, gru_model_forward, \
    init_gru_model
from repro.train.losses import r_squared
from repro.train.optim import AdamConfig, constant_schedule
from repro.train.trainer import init_train_state, make_gru_train_step, \
    train_loop

GRID_Q88 = [0, 4, 8, 16, 32]
H, L, STEPS = 48, 2, 150


def _eval(params, tx, th, key):
    task = GruTaskConfig(14, H, L, 1, task="regression",
                         theta_x=tx, theta_h=th)
    batch = gas_batch(key, batch=8, t_len=96)
    out, stats = gru_model_forward(params, task, batch["features"],
                                   collect_sparsity=True)
    rmse = float(jnp.sqrt(jnp.mean((out - batch["targets"]) ** 2)))
    r2 = float(r_squared(out, batch["targets"]))
    return rmse, r2, float(stats["gamma_dx"]), float(stats["gamma_dh"])


def run() -> list[str]:
    # train once with small dual thresholds (the paper's retrain stage)
    task = GruTaskConfig(14, H, L, 1, task="regression",
                         theta_x=4 / 256, theta_h=8 / 256)
    params = init_gru_model(jax.random.PRNGKey(0), task)
    step = make_gru_train_step(
        task, AdamConfig(schedule=constant_schedule(3e-3)))
    state = init_train_state(params)
    stream = batch_stream(gas_batch, jax.random.PRNGKey(1), batch=8,
                          t_len=96)
    state, _ = train_loop(step, state, stream, STEPS)

    lines = []
    cells = {}
    key = jax.random.PRNGKey(9)
    for tx_i in GRID_Q88:
        for th_i in GRID_Q88:
            rmse, r2, gdx, gdh = _eval(state.params, tx_i / 256, th_i / 256,
                                       key)
            cells[(tx_i, th_i)] = (rmse, r2, gdx, gdh)
            lines.append(
                f"fig10_11.tx{tx_i}_th{th_i},{rmse * 1000:.1f},"
                f"R2={r2:.3f} gamma_dx={gdx:.3f} gamma_dh={gdh:.3f}")

    # dual-threshold headline: best hidden sparsity at iso-accuracy vs global
    base_rmse = cells[(0, 0)][0]
    tol = base_rmse * 1.10
    glob = [(g, cells[(g, g)]) for g in GRID_Q88
            if cells[(g, g)][0] <= tol]
    dual = [(tx, th, v) for (tx, th), v in cells.items() if v[0] <= tol]
    if glob and dual:
        best_glob = max(glob, key=lambda kv: kv[1][3])
        best_dual = max(dual, key=lambda kv: kv[2][3])
        gain = (best_dual[2][3] - best_glob[1][3]) * 100
        lines.append(
            f"fig10_11.dual_gain,0,"
            f"best_global=th{best_glob[0]} gdh={best_glob[1][3]:.3f} "
            f"best_dual=(tx{best_dual[0]} th{best_dual[1]}) "
            f"gdh={best_dual[2][3]:.3f} hidden_sparsity_gain={gain:+.1f}pp "
            f"(paper: +16%)")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
