"""Paper Fig. 13: throughput & latency vs batch size — now MEASURED.

The paper measured a GTX 1080 climbing toward its compute roofline with
batch (weight reuse) while latency grows; EdgeDRNN's premise is that
batch-1 edge inference never amortizes the weight stream. Our batched
tile backends (``fused_batch`` / ``fused_q8_batch``) recover the GPU's
weight-reuse economics *without* giving up delta skipping: one weight
pass per step serves the whole ``[B, ...]`` stream tile, compacted on
the **union** of fired columns across the tile.

This module runs the measured sweep over the batch list and writes
``BENCH_batch_sweep.json``:

* wall µs/step and GOp/s per (backend, batch) — measured on independent
  random-walk streams, interleaved timing;
* modeled tile weight bytes/step from the MEASURED union fired-block
  counts (the same bytes model ``kernel_bench`` uses), plus
  bytes/stream/step — the quantity that must fall sublinearly with B;
* **matched-firing** rows: one walk replicated across the tile, so the
  union firing equals the single stream's firing and the tile fetch is
  *exactly* the batch-1 fetch — ``tile_bytes_matched / B`` is then an
  exact invariant (``check_regression`` asserts bytes/stream at B=8 is
  strictly below the batch-1 baseline, with no float-threshold slop);
* the knee batch (smallest B within 90% of the sweep's peak GOp/s);
* the analytic curves alongside: the EDGEDRNN Eq. 7 batched tile model
  (:func:`repro.core.perf_model.estimate_batched_tile`, independent-
  streams union ``gamma**B``) and the historical v5e roofline
  :func:`repro.core.perf_model.batch_sweep`, for model-vs-measured
  comparison.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.kernel_bench import (_bytes_per_step, _mean_fired_blocks,
                                     _time_calls, _walk_inputs, record_meta)
from repro.core.perf_model import (EDGEDRNN, V5E, batch_sweep,
                                   estimate_batched_tile, spec_for_backend)
from repro.core.sparsity import GruDims

BENCH_BATCH_JSON = os.path.join(os.path.dirname(__file__),
                                "BENCH_batch_sweep.json")

BATCHES = (1, 2, 4, 8)
BATCH_BACKENDS = ("fused_batch", "fused_q8_batch")


def _progs(params, qparams, layouts_q8):
    """Compiled programs (+ the stacks their firing is measured on)."""
    return {
        "fused_batch": (params, None),
        "fused_q8_batch": (qparams, layouts_q8),
    }


def bench_batch_record(t=48, i=64, h=128, layers=2, theta=0.1,
                       batches=BATCHES):
    """Measured batched-tile sweep -> (printable lines, JSON record)."""
    from repro.core.deltagru import deltagru_sequence, init_gru_stack
    from repro.core.program import compile_delta_program
    from repro.quant.export import quantize_stack

    key = jax.random.PRNGKey(0)
    params = init_gru_stack(key, i, h, layers)
    qparams, layouts_q8 = quantize_stack(params)
    dims = GruDims(i, h, layers)
    ops_per_step = dims.params_per_timestep_ops
    stacks = _progs(params, qparams, layouts_q8)
    # the matched-firing walk: ONE stream, replicated across the tile so
    # the union firing is exactly this stream's firing at every batch
    xs1 = _walk_inputs(jax.random.fold_in(key, 999), t, 1, i)
    # batch-1 per-stream gammas feed the analytic union model
    _, _, st1 = deltagru_sequence(params, xs1, theta, theta)
    gdx1, gdh1 = float(st1["gamma_dx"]), float(st1["gamma_dh"])

    lines, rows = [], []
    for be in BATCH_BACKENDS:
        stack, layouts = stacks[be]
        prog = compile_delta_program(params if layouts is None else stack,
                                     backend=be, layouts=layouts)
        spec = spec_for_backend(EDGEDRNN, be)
        # exact matched-firing baseline: the tile fetch of the replicated
        # tile at ANY batch equals this batch-1 fetch (unrounded)
        counts_m1 = _mean_fired_blocks(stack, xs1, theta, backend=be,
                                       layouts=layouts)
        batch1_bytes_matched = _bytes_per_step(params, counts_m1, be)
        walls, per_b = {}, {}
        for b in batches:
            xs = _walk_inputs(jax.random.fold_in(key, b), t, b, i)
            xs_m = jnp.tile(xs1, (1, b, 1))
            fn = jax.jit(lambda xs, p=prog: p.sequence(
                xs, theta, theta, collect_sparsity=False)[0])
            (wall,) = _time_calls([lambda f=fn, x=xs: f(x)], reps=20)
            # union fired blocks across the tile, measured on the actual
            # delta stream of this backend (q8 fires on the rounded grid)
            counts = _mean_fired_blocks(stack, xs, theta, backend=be,
                                        layouts=layouts)
            counts_m = _mean_fired_blocks(stack, xs_m, theta, backend=be,
                                          layouts=layouts)
            tile_bytes = _bytes_per_step(params, counts, be)
            tile_bytes_matched = _bytes_per_step(params, counts_m, be)
            us = wall / t * 1e6
            gops = ops_per_step * b / (wall / t) / 1e9
            ana = estimate_batched_tile(dims, gdx1, gdh1, b, spec)
            per_b[b] = gops
            walls[b] = wall
            rows.append({
                "backend": be, "batch": b, "theta": theta,
                "us_per_step": round(us, 2),
                "gops": round(gops, 4),
                "tile_bytes_per_step": round(tile_bytes, 1),
                "bytes_per_stream_per_step": round(tile_bytes / b, 1),
                # UNROUNDED: check_regression asserts exact equality with
                # the batch-1 matched baseline and the strict /B descent
                "tile_bytes_matched": tile_bytes_matched,
                "batch1_bytes_matched": batch1_bytes_matched,
                "bytes_per_stream_matched": tile_bytes_matched / b,
                "analytic_tile_bytes": round(ana["tile_weight_bytes"], 1),
                "analytic_bytes_per_stream": round(
                    ana["weight_bytes_per_stream"], 1),
            })
            lines.append(
                f"fig13.meas_{be}_b{b},{us:.1f},"
                f"tile_bytes={tile_bytes:.0f} "
                f"bytes/stream={tile_bytes / b:.0f} gops={gops:.3f}")
        peak = max(per_b.values())
        knee = next(b for b in batches if per_b[b] >= 0.9 * peak)
        lines.append(f"fig13.meas_{be}_knee,0,"
                     f"within 90% of peak from batch~{knee}")
        for row in rows:
            if row["backend"] == be:
                row["knee_batch"] = knee

    record = {
        "bench": "batch_sweep",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "theta": theta, "batches": list(batches), "block": 128,
                   "ops_per_step": ops_per_step,
                   "gamma_dx_batch1": round(gdx1, 4),
                   "gamma_dh_batch1": round(gdh1, 4),
                   **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
        # the historical v5e analytic curve, kept for model-vs-measured
        "analytic_v5e": batch_sweep(GruDims(40, 768, 2), list(BATCHES),
                                    gamma_eff=0.9, chip=V5E),
    }
    return lines, record


def run(write=True) -> list[str]:
    """Measured batched sweep (writes ``BENCH_batch_sweep.json``) plus the
    analytic v5e roofline lines the suite always printed."""
    lines, record = bench_batch_record()
    if write:
        with open(BENCH_BATCH_JSON, "w") as f:
            json.dump(record, f, indent=1)
        lines.append(
            f"fig13.batch_bench_json,0,"
            f"wrote {os.path.basename(BENCH_BATCH_JSON)}")
    dims = GruDims(40, 768, 2)
    for geff, tag in [(0.0, "dense"), (0.9, "delta90")]:
        rows = batch_sweep(dims, list(BATCHES) + [16, 32, 64, 128, 256],
                           gamma_eff=geff, chip=V5E)
        for r in rows:
            lines.append(
                f"fig13.{tag}_b{r['batch']},{r['latency_s'] * 1e6:.2f},"
                f"tput={r['throughput_ops'] / 1e9:.1f}GOp/s")
        knee = next((r["batch"] for r in rows
                     if r["throughput_ops"] >= 0.99 * rows[-1]["throughput_ops"]),
                    256)
        lines.append(f"fig13.{tag}_knee,0,compute-bound from batch~{knee}")
    return lines


def run_quick(t=12) -> list[str]:
    """Reduced CI pass (`make bench-batch-quick`): exercises the measured
    batched sweep end to end — every batch, both tile backends, the exact
    matched-firing invariant — without touching the committed baseline."""
    lines, record = bench_batch_record(t=t)
    for row in record["rows"]:
        if row["batch"] > 1:
            assert row["bytes_per_stream_matched"] < \
                row["batch1_bytes_matched"], (
                    f"tile economics inverted: {row['backend']} B="
                    f"{row['batch']} pays {row['bytes_per_stream_matched']} "
                    f"bytes/stream vs {row['batch1_bytes_matched']} at B=1")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced CI pass (short walks, no baseline write)")
    args = ap.parse_args()
    print("\n".join(run_quick() if args.quick else run()))
