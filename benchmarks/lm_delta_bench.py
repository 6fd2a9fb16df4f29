"""Fig. 9-style threshold sweep for the delta-ized LM cells (RWKV6, RG-LRU).

Runs the reduced ``rwkv6-1.6b`` / ``recurrentgemma-9b`` recipes through
compiled programs (``compile_delta_program``) on the
``DeltaStreamEngine`` over a temporally-smooth input stream, sweeping the
Q8.8 threshold grid on both registered backends, and records per row:

* measured temporal sparsity (``gamma_dx`` / ``gamma_dh``, UNROUNDED —
  the bytes gate recomputes the Eq. 7 pricing from them),
* ``bytes_per_step`` — the modeled weight traffic
  :func:`repro.core.perf_model.dram_traffic_bytes_per_timestep` at the
  measured gammas, evaluated host-side in float64 so
  ``check_regression`` can reproduce it EXACTLY on any machine from the
  recorded gammas (the engine's own f32 running sum is recorded
  separately as ``engine_bytes_per_step``),
* wall time per step of the jitted streaming path, and
* output drift vs the dense theta=0 run at matched inputs.

Hard assertions folded into every record (the CI gate re-runs this, so a
completed fresh record certifies them on the gating machine):

* theta=0 BITWISE: the per-step delta entry points
  (``rwkv_time_mix_delta`` / ``rglru_block_decode_delta``) reproduce the
  exact dense decode bit-for-bit at theta=0;
* theta=0 rows measure gamma == 0.0 exactly and price exactly the dense
  projection volume;
* the theta=0.25 operating point reaches > ``MIN_REDUCTION`` (2x)
  modeled projection-byte reduction at drift <= ``DRIFT_LIMIT`` on BOTH
  cells.

Usage::

    PYTHONPATH=src python -m benchmarks.lm_delta_bench            # writes
    PYTHONPATH=src python -m benchmarks.lm_delta_bench --quick    # no write
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

BENCH_LM_DELTA_JSON = os.path.join(os.path.dirname(__file__),
                                   "BENCH_lm_delta.json")

CELLS = ("rwkv6", "rglru")
BACKENDS = ("dense", "fused")
THETAS_Q88 = (0, 16, 64)
OP_THETA_Q88 = 64          # the gated >2x operating point (theta=0.25)
MIN_REDUCTION = 2.0
DRIFT_LIMIT = 0.75         # max-abs logits drift at the operating point
T_FULL, T_QUICK = 96, 40
OUTPUT_SIZE = 48


def _recipe(cell, key):
    if cell == "rwkv6":
        from repro.configs.rwkv6_1_6b import reduced_delta_recipe
    else:
        from repro.configs.recurrentgemma_9b import reduced_delta_recipe
    return reduced_delta_recipe(key, output_size=OUTPUT_SIZE)


def _stream(key, t, d):
    """Temporally-smooth stream: first-order low-pass over white noise
    (the paper's premise — real sensor/activation streams change slowly)."""
    noise = jax.random.normal(key, (t, d))

    def step(c, n):
        c = 0.9 * c + 0.35 * n
        return c, c

    _, xs = jax.lax.scan(step, jnp.zeros((d,)), noise)
    return np.asarray(xs, np.float32)


def _assert_theta0_bitwise(cell, model, t=8):
    """The acceptance criterion: at theta=0 the delta step entry points
    are BITWISE identical to the exact dense decode, step by step."""
    d = model[cell][0].input_size
    b = 2
    xs = jax.random.normal(jax.random.PRNGKey(3), (t, b, d))
    if cell == "rwkv6":
        from repro.core.deltarwkv import rwkv_layer_dict
        from repro.models import rwkv as m
        pd = rwkv_layer_dict(model[cell][0])
        st_m = m.init_rwkv_state(b, d)
        st_d = m.init_rwkv_delta_state(pd, (b,))
        for i in range(t):
            y, new_last, wkv = m.rwkv_time_mix(pd, xs[i][:, None], st_m)
            st_m = m.RwkvState(tm_shift=new_last, cm_shift=st_m.cm_shift,
                               wkv=wkv)
            out = m.rwkv_time_mix_delta(pd, xs[i], st_d, 0.0, 0.0)
            st_d = out.state
            assert jnp.array_equal(out.h, y[:, 0]), \
                f"rwkv6 theta=0 decode is not bitwise at step {i}"
    else:
        from repro.core.deltarglru import rglru_layer_dict
        from repro.models import rglru as m
        pd = rglru_layer_dict(model[cell][0])
        st_m = m.init_rglru_state(b, d)
        st_d = m.init_rglru_delta_state(pd, (b,))
        for i in range(t):
            y, st_m = m.rglru_block_decode(pd, xs[i][:, None], st_m)
            out = m.rglru_block_decode_delta(pd, xs[i], st_d, 0.0, 0.0)
            st_d = out.state
            assert jnp.array_equal(out.h, y[:, 0]), \
                f"rglru theta=0 decode is not bitwise at step {i}"
    return True


def bench_lm_delta_record(t: int = T_FULL,
                          thetas=THETAS_Q88) -> tuple[list, dict]:
    """Measure the full (cell x backend x theta) grid; returns
    ``(csv_lines, record)`` and hard-fails on any in-record invariant."""
    from benchmarks.kernel_bench import record_meta
    from repro.core.perf_model import dram_traffic_bytes_per_timestep
    from repro.core.program import compile_delta_program
    from repro.core.sparsity import cell_dims
    from repro.core.thresholds import ThresholdPolicy
    from repro.serve.engine import DeltaStreamEngine

    lines, rows, cell_cfg = [], [], {}
    for cell in CELLS:
        cfg, model, task = _recipe(cell, jax.random.PRNGKey(0))
        _assert_theta0_bitwise(cell, model)
        xs = _stream(jax.random.PRNGKey(1), t, cfg.d_model)[:, None, :]
        dims = cell_dims(cell, task.input_size, task.hidden_size,
                         task.num_layers)
        dense_bytes = float(dram_traffic_bytes_per_timestep(
            dims, 0.0, 0.0, w_weight_bits=32))
        cell_cfg[cell] = {"input": task.input_size,
                          "hidden": task.hidden_size,
                          "layers": task.num_layers,
                          "dense_bytes": dense_bytes,
                          "theta0_bitwise": True}
        ref = None
        for backend in BACKENDS:
            prog = compile_delta_program(model, backend=backend, cell=cell)
            for theta_int in thetas:
                theta = theta_int / 256.0
                eng = DeltaStreamEngine(
                    prog, task, thresholds=ThresholdPolicy(theta, theta))
                # warm (compiles the scan), then reset and time the real run
                eng.step_many(xs[:2])
                eng.reset()
                t0 = time.perf_counter()
                outs = eng.step_many(xs)
                jax.block_until_ready(outs)
                wall = time.perf_counter() - t0
                rep = eng.report()
                if ref is None:           # dense theta=0: the exact decode
                    ref = outs
                drift = float(jnp.max(jnp.abs(outs - ref)))
                gdx, gdh = rep["gamma_dx"], rep["gamma_dh"]
                model_bytes = float(dram_traffic_bytes_per_timestep(
                    dims, gdx, gdh, w_weight_bits=32))
                if theta_int == 0:
                    assert gdx == 0.0 and gdh == 0.0, \
                        f"{cell}/{backend} theta=0 measured firing " \
                        f"gamma=({gdx}, {gdh}) != 0"
                    assert model_bytes == dense_bytes, \
                        f"{cell}/{backend} theta=0 prices {model_bytes} " \
                        f"B/step != dense volume {dense_bytes}"
                rows.append({
                    "cell": cell, "backend": backend,
                    "theta": theta, "theta_q88": theta_int,
                    "gamma_dx": gdx, "gamma_dh": gdh,
                    "bytes_per_step": model_bytes,
                    "engine_bytes_per_step":
                        rep["mean_weight_bytes_per_step"],
                    "reduction": dense_bytes / max(model_bytes, 1e-9),
                    "drift": drift,
                    "us_per_step": wall / t * 1e6,
                })
                lines.append(
                    f"lm_delta.{cell}.{backend}.theta_{theta_int},"
                    f"{wall / t * 1e6:.1f},"
                    f"gamma_dx={gdx:.3f} gamma_dh={gdh:.3f} "
                    f"bytes={model_bytes:.0f} "
                    f"red={dense_bytes / max(model_bytes, 1e-9):.2f}x "
                    f"drift={drift:.4f}")
        # the gated operating point: >2x modeled byte reduction at
        # bounded drift, on every backend that measured it
        for backend in BACKENDS:
            op = [r for r in rows
                  if r["cell"] == cell and r["backend"] == backend
                  and r["theta_q88"] == OP_THETA_Q88]
            for r in op:
                assert r["reduction"] > MIN_REDUCTION, \
                    f"{cell}/{backend} theta={OP_THETA_Q88}/256 reaches " \
                    f"only {r['reduction']:.2f}x byte reduction " \
                    f"(need > {MIN_REDUCTION}x)"
                assert r["drift"] <= DRIFT_LIMIT, \
                    f"{cell}/{backend} theta={OP_THETA_Q88}/256 drift " \
                    f"{r['drift']:.3f} exceeds {DRIFT_LIMIT}"
    record = {
        "config": {**record_meta(), "t": t, "output": OUTPUT_SIZE,
                   "weight_bits": 32, "op_theta_q88": OP_THETA_Q88,
                   "min_reduction": MIN_REDUCTION,
                   "drift_limit": DRIFT_LIMIT, "cells": cell_cfg},
        "rows": rows,
    }
    return lines, record


def run() -> list[str]:
    lines, record = bench_lm_delta_record()
    with open(BENCH_LM_DELTA_JSON, "w") as f:
        json.dump(record, f, indent=1)
    return lines


def run_quick() -> list[str]:
    lines, _ = bench_lm_delta_record(t=T_QUICK, thetas=(0, OP_THETA_Q88))
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced pass, hard asserts only (no JSON write)")
    args = ap.parse_args()
    print("\n".join(run_quick() if args.quick else run()))
