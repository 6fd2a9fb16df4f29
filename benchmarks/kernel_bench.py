"""Kernel-level benchmark: delta_spmv block-skip efficiency + the
sequence-level backend shootout.

Part 1 reports the modeled HBM weight traffic of the Pallas block-sparse
matvec across sparsity levels (the Eq. 8 law at 128-wide block granularity)
and wall-time of the interpret-mode kernel as a correctness smoke.
Structured (burst) sparsity keeps block skipping near the element-level
ideal; unstructured sparsity shows the block-granularity gap.

Part 2 (``run_seq``) times whole-sequence DeltaGRU execution per backend —
the seed's per-step Python dispatch loop (one jit call + host sync per
timestep, what ``GruStreamEngine.step`` used to do) against the scanned
``dense`` / ``fused`` / ``fused_q8`` paths — at several
temporal sparsity levels, and writes a ``BENCH_deltagru_seq.json`` record
(with device/platform/dtype metadata) so the perf trajectory is
machine-readable and comparable across PRs and machines.

Part 3 (``run_q8``) is the bandwidth story: per-backend **bytes streamed
per timestep** (fired k-blocks x block width x fetched rows x weight
bytes — the quantity EdgeDRNN's Eq. 8 is about) and effective GOp/s
(nominal dense Op over measured wall clock), written to
``BENCH_deltagru_q8.json``. ``benchmarks/roofline.py::run_deltagru`` turns
those rows into arithmetic-intensity / roofline-bound lines, and
``benchmarks/check_regression.py`` gates fresh runs against the committed
records.

Part 4 (``run_lstm``) is the cell-parity trajectory: every DeltaLSTM
backend registered for ``cell="lstm"`` (the sweep list is derived from the
backend registry, so new backends are auto-benched) against the per-step
dispatch loop, with a hard fused-vs-dense parity assertion, written to
``BENCH_deltalstm_seq.json``. ``python -m benchmarks.kernel_bench --lstm
--quick`` is the CI spelling (``make ci`` chains it).

Part 5 (``run_lstm_q8``) is the quantized 4-gate bandwidth story: the
LSTM analogue of Part 3 — bytes streamed + effective GOp/s per backend,
plus two HARD gates (fused_q8 Pallas kernel bit-identical to its jnp
oracle; fused_q8 within the quantization budget of the fp32 dense
reference) — written to ``BENCH_deltalstm_q8.json`` with the
matched-firing 0.25x bytes invariant the regression gate checks exactly.
``python -m benchmarks.kernel_bench --lstm-q8 --quick`` is the CI
spelling (``make bench-lstm-q8-quick``).

Part 6 (``run_q4``) is the int4 nibble-packed story for BOTH cells: the
``dense`` -> ``fused_q8`` -> ``fused_q4`` weight-width ladder (4 B -> 1 B
-> 0.5 B per streamed weight), with hard gates (fused_q4 Pallas kernel
bit-identical to its jnp oracle; drift vs fp32 dense within 2x the int8
budget) and the UNROUNDED matched-firing fields the regression gate uses
to assert the exact ``q4 == 0.5x q8 == 0.125x fused`` bytes ladder on any
machine — written to ``BENCH_deltagru_q4.json`` /
``BENCH_deltalstm_q4.json``. ``python -m benchmarks.kernel_bench --q4
--quick`` is the CI spelling (``make bench-q4-quick``).
"""
from __future__ import annotations

import json
import os
import platform as _platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import list_backends
from repro.kernels import ops

O, I = 2048, 2048

BENCH_JSON = os.path.join(os.path.dirname(__file__),
                          "BENCH_deltagru_seq.json")
BENCH_Q8_JSON = os.path.join(os.path.dirname(__file__),
                             "BENCH_deltagru_q8.json")
BENCH_LSTM_JSON = os.path.join(os.path.dirname(__file__),
                               "BENCH_deltalstm_seq.json")
BENCH_LSTM_Q8_JSON = os.path.join(os.path.dirname(__file__),
                                  "BENCH_deltalstm_q8.json")
BENCH_Q4_JSON = os.path.join(os.path.dirname(__file__),
                             "BENCH_deltagru_q4.json")
BENCH_LSTM_Q4_JSON = os.path.join(os.path.dirname(__file__),
                                  "BENCH_deltalstm_q4.json")

# Derived from the backend registry (the single source of truth): a newly
# registered backend is automatically swept, benched, and regression-gated
# instead of silently skipped by a stale hand-maintained tuple. The
# ``*_batch`` tile backends are excluded here — at the batch-1 config of
# these records they are the identical compute (same kernels, tile
# contract only); their economics only show up with many streams, which
# is what ``benchmarks/fig13_batch_sweep.py`` measures into its own
# ``BENCH_batch_sweep.json`` record.
SEQ_BACKENDS = tuple(b for b in list_backends("gru")
                     if not b.endswith("_batch"))
LSTM_BACKENDS = tuple(b for b in list_backends("lstm")
                      if not b.endswith("_batch"))


def record_meta() -> dict:
    """Per-record environment metadata: bench numbers are only comparable
    across runs when these match (check_regression keys off them)."""
    return {
        "device": jax.default_backend(),
        "platform": _platform.platform(),
        "machine": _platform.machine(),
        "python": _platform.python_version(),
        "jax_version": jax.__version__,
        "dtype": "float32",
    }


def _traffic(dx):
    dense = O * I * 2
    got = float(ops.delta_spmv_hbm_bytes((O, I), dx))
    return got / dense


def run() -> list[str]:
    lines = []
    key = jax.random.PRNGKey(0)
    for gamma in [0.0, 0.5, 0.9, 0.96]:
        # structured: fire whole 128-blocks (trained delta nets cluster)
        nb = I // 128
        fired_blocks = max(1, int(round(nb * (1 - gamma))))
        dx_s = jnp.zeros((1, I)).at[:, :fired_blocks * 128].set(1.0)
        # unstructured: uniform random elements
        dx_u = (jax.random.uniform(key, (1, I)) < (1 - gamma)).astype(
            jnp.float32)
        lines.append(
            f"kernel.delta_spmv_g{int(gamma * 100)},0,"
            f"traffic_frac_structured={_traffic(dx_s):.3f} "
            f"unstructured={_traffic(dx_u):.3f} ideal={1 - gamma:.3f}")

    # interpret-mode wall time (correctness-path smoke, not TPU perf)
    w = jax.random.normal(key, (512, 512))
    dx = jax.random.normal(jax.random.fold_in(key, 1), (1, 512))
    dx = dx * (jax.random.uniform(jax.random.fold_in(key, 2), (1, 512)) < 0.2)
    out = ops.delta_spmv(w, dx, interpret=True)
    t0 = time.perf_counter()
    for _ in range(3):
        out = ops.delta_spmv(w, dx, interpret=True)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 3 * 1e6
    lines.append(f"kernel.delta_spmv_interpret_512,{us:.0f},"
                 "interpret-mode (CPU correctness path)")
    # run the seq shootout once and feed its walls to the q8 bytes/GOp/s
    # record — same configs, no point timing every backend twice
    seq_lines, seq_record = bench_seq_record()
    lines.extend(seq_lines)
    with open(BENCH_JSON, "w") as f:
        json.dump(seq_record, f, indent=1)
    lines.append(
        f"kernel.seq_bench_json,0,wrote {os.path.basename(BENCH_JSON)}")
    lines.extend(run_q8(times_by_theta=_times_from_record(seq_record)))
    # LSTM shootout + the quantized-LSTM bytes record, reusing the walls
    # the LSTM pass already measured (same configs, no double timing)
    lstm_lines, lstm_record = bench_lstm_record()
    lines.extend(lstm_lines)
    with open(BENCH_LSTM_JSON, "w") as f:
        json.dump(lstm_record, f, indent=1)
    lines.append(
        f"kernel.lstm_bench_json,0,wrote {os.path.basename(BENCH_LSTM_JSON)}")
    lines.extend(run_lstm_q8(times_by_theta=_times_from_record(
        lstm_record, LSTM_BACKENDS)))
    return lines


def _times_from_record(seq_record, backends=None) -> dict:
    """{theta: {backend: wall_s}} from a bench_*_record result."""
    t = seq_record["config"]["t"]
    backends = SEQ_BACKENDS if backends is None else backends
    times: dict = {}
    for row in seq_record["rows"]:
        if row["backend"] in backends:
            times.setdefault(row["theta"], {})[row["backend"]] = \
                row["us_per_step"] * t / 1e6
    return times


def _walk_inputs(key, t, b, i, scale=0.08):
    """Slowly-varying random walk: the temporally-sparse input regime the
    delta network exploits (speech features between phoneme boundaries)."""
    steps = jax.random.normal(key, (t, b, i)) * scale
    return jnp.cumsum(steps, axis=0)


def _time_call(fn, reps=5):
    """Best-of-reps wall time (min is the stable estimator under CPU
    scheduling noise; the regression gate compares these numbers)."""
    return _time_calls([fn], reps)[0]


def _time_calls(fns, reps=5):
    """Time several callables *interleaved* (round-robin), best-of-reps.

    Backend shootouts are comparisons: interleaving the candidates inside
    one measurement window means slow machine-load drift biases every
    backend equally instead of penalizing whichever ran last.
    """
    for fn in fns:
        jax.block_until_ready(fn())  # warmup / compile, fully drained
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for k, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def _seq_fn(params, xs, theta, backend, layouts=None):
    from repro.core.program import compile_deltagru
    prog = compile_deltagru(params, backend=backend, layouts=layouts)
    return jax.jit(lambda xs: prog.sequence(
        xs, theta, theta, collect_sparsity=False)[0])


def _time_backends(params, qparams, layouts_q8, xs, theta):
    """Wall time per scanned backend at one theta, fully interleaved
    (round-robin reps so machine-load drift biases every backend equally).
    Every swept backend is a fast scanned path now that the ~50x-slower
    interpret-mode ``blocksparse`` was retired from the registry."""
    seqs = [_seq_fn(qparams, xs, theta, be, layouts=layouts_q8)
            if be == "fused_q8" else _seq_fn(params, xs, theta, be)
            for be in SEQ_BACKENDS]
    walls = _time_calls([(lambda s=s: s(xs)) for s in seqs], reps=60)
    return dict(zip(SEQ_BACKENDS, walls))


def run_seq(t=64, i=128, h=256, layers=2,
            thetas=(0.0, 0.05, 0.2), write=True) -> list[str]:
    """Sequence-level wall time: seed dispatch loop vs scanned backends."""
    lines, record = bench_seq_record(t=t, i=i, h=h, layers=layers,
                                     thetas=thetas)
    if write:
        with open(BENCH_JSON, "w") as f:
            json.dump(record, f, indent=1)
        lines.append(
            f"kernel.seq_bench_json,0,wrote {os.path.basename(BENCH_JSON)}")
    return lines


def bench_seq_record(t=64, i=128, h=256, layers=2,
                     thetas=(0.0, 0.05, 0.2)):
    from repro.core.deltagru import (deltagru_sequence, deltagru_stack_step,
                                     init_deltagru_stack_state,
                                     init_gru_stack)
    from repro.quant.export import quantize_stack
    key = jax.random.PRNGKey(0)
    params = init_gru_stack(key, i, h, layers)
    qparams, layouts_q8 = quantize_stack(params)
    xs = _walk_inputs(jax.random.fold_in(key, 1), t, 1, i)
    lines, rows = [], []

    for theta in thetas:
        # measured gamma at this theta (from the dense reference run)
        _, _, st = deltagru_sequence(params, xs, theta, theta)
        gdx, gdh = float(st["gamma_dx"]), float(st["gamma_dh"])

        # seed path: one jitted step per timestep + a host sync per step
        step = jax.jit(lambda s, x: deltagru_stack_step(
            params, s, x, theta, theta))

        def per_step_loop():
            s = init_deltagru_stack_state(params, (1,))
            y = None
            for x in xs:
                y, s, deltas = step(s, x)
                float(jnp.mean(deltas[0][0]))   # the seed's per-step sync
            return y

        times = {"per_step_dispatch": _time_call(per_step_loop)}
        times.update(_time_backends(params, qparams, layouts_q8, xs, theta))

        for name, wall in times.items():
            us = wall / t * 1e6
            rows.append({"theta": theta, "gamma_dx": round(gdx, 4),
                         "gamma_dh": round(gdh, 4), "backend": name,
                         "us_per_step": round(us, 2),
                         "steps_per_s": round(t / wall, 1)})
            lines.append(
                f"kernel.seq_{name}_th{theta},{us:.1f},"
                f"gamma_dh={gdh:.3f} steps/s={t / wall:.0f}")

    record = {
        "bench": "deltagru_seq_backends",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "batch": 1,
                   # off-TPU the kernel backends auto-route per kernels/ops
                   # conventions (fused/fused_q8 -> jnp ref)
                   **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
    }
    return lines, record


# ---------------------------------------------------------------------------
# Part 3: bytes-streamed + effective GOp/s per backend (the Eq. 8 story)
# ---------------------------------------------------------------------------

def _backend_weight_bytes(cell="gru") -> dict:
    """Bytes per streamed weight, derived from the single source of truth
    (the backend registry, surfaced through the Eq. 6/7 model) so bench
    and engine cannot drift."""
    from repro.core.perf_model import backend_weight_bits
    # float division: sub-byte widths (fused_q4's 4-bit nibbles) must map to
    # fractional bytes-per-weight (0.5), not truncate to 0.
    return {be: bits / 8.0 for be, bits in backend_weight_bits(cell).items()}


def _mean_fired_blocks(params, xs, theta, backend="dense", layouts=None,
                       block=128, cell="gru"):
    """Mean fired k-block counts per step per layer, ``[L, 2]`` (x, h).

    Measured on the actual delta stream of the given backend (the
    quantized paths fire on the Q8.8-rounded stream, which can differ
    slightly from the fp32 one). Cell-agnostic: the stack is compiled
    into a program of the given cell family and scanned step by step.
    """
    from repro.core.program import compile_delta_program
    prog = compile_delta_program(params, backend=backend, cell=cell,
                                 layouts=layouts)

    def blocks(d):
        b, k = d.shape
        pad = (-k) % block
        dp = jnp.pad(d, ((0, 0), (0, pad)))
        nb = dp.shape[-1] // block
        fired = jnp.any(dp.reshape(b, nb, block) != 0, axis=(0, 2))
        return jnp.sum(fired.astype(jnp.float32))

    def run_counts(xs):
        state = prog.init_state((xs.shape[1],))

        def body(s, x):
            _, s2, deltas = prog.step(s, x, theta, theta)
            cnt = jnp.stack([jnp.stack([blocks(dx), blocks(dh)])
                             for dx, dh in deltas])
            return s2, cnt

        _, cnts = jax.lax.scan(body, state, xs)
        return jnp.mean(cnts, axis=0)                      # [L, 2]

    return np.asarray(jax.jit(run_counts)(xs))


def _bytes_per_step(params, counts, backend, block=128, cell="gru"):
    """Modeled weight HBM bytes per timestep for a backend.

    dense reads the whole (unpadded) weight set every step; the kernel
    backends fetch ``fired_blocks * block`` columns of their padded row
    extent (``gates`` rows per column — 3 for GRU, 4 for LSTM); fused_q8
    fetches the same columns at 1 byte/element (the int8 volume is the
    kernel's only weight-sized operand).
    """
    from repro.core.sparsity import CELL_GATES
    g = CELL_GATES[cell]
    wb = _backend_weight_bytes(cell)[backend]
    total = 0.0
    for li, p in enumerate(params):
        i_dim, h_dim = p.input_size, p.hidden_size
        if backend == "dense":
            total += g * h_dim * (i_dim + h_dim) * wb
            continue
        fbx, fbh = counts[li]
        # fused family (incl. the *_batch tile variants): fired k-blocks
        # of the concatenated volume, g rows per padded column
        hp = h_dim + (-h_dim) % block
        total += (fbx + fbh) * block * g * hp * wb
    return float(total)


def run_q8(t=64, i=128, h=256, layers=2,
           thetas=(0.0, 0.05, 0.2), write=True,
           times_by_theta=None) -> list[str]:
    """Bytes-streamed + effective-GOp/s shootout across the batch-1
    backends."""
    lines, record = bench_q8_record(t=t, i=i, h=h, layers=layers,
                                    thetas=thetas,
                                    times_by_theta=times_by_theta)
    if write:
        with open(BENCH_Q8_JSON, "w") as f:
            json.dump(record, f, indent=1)
        lines.append(
            f"kernel.q8_bench_json,0,wrote {os.path.basename(BENCH_Q8_JSON)}")
    return lines


def bench_q8_record(t=64, i=128, h=256, layers=2,
                    thetas=(0.0, 0.05, 0.2), times_by_theta=None):
    """``times_by_theta`` ({theta: {backend: wall_s}}) reuses walls already
    measured by :func:`bench_seq_record` on the same config; backends are
    (re-)timed here only when absent."""
    from repro.core.deltagru import deltagru_sequence, init_gru_stack
    from repro.core.sparsity import GruDims
    from repro.quant.export import quantize_stack

    key = jax.random.PRNGKey(0)
    params = init_gru_stack(key, i, h, layers)
    qparams, layouts_q8 = quantize_stack(params)
    xs = _walk_inputs(jax.random.fold_in(key, 1), t, 1, i)
    ops_per_step = GruDims(i, h, layers).params_per_timestep_ops
    lines, rows = [], []

    for theta in thetas:
        counts_fp = _mean_fired_blocks(params, xs, theta, backend="dense")
        counts_q8 = _mean_fired_blocks(qparams, xs, theta,
                                       backend="fused_q8",
                                       layouts=layouts_q8)
        _, _, st = deltagru_sequence(params, xs, theta, theta)
        _, _, st_q = deltagru_sequence(qparams, xs, theta, theta,
                                       backend="fused_q8",
                                       layouts=layouts_q8)
        times = (times_by_theta or {}).get(theta)
        if times is None or any(be not in times for be in SEQ_BACKENDS):
            times = _time_backends(params, qparams, layouts_q8, xs, theta)
        for be in SEQ_BACKENDS:
            wall = times[be]
            counts, stats = ((counts_q8, st_q) if be == "fused_q8"
                             else (counts_fp, st))
            us = wall / t * 1e6
            nbytes = _bytes_per_step(params, counts, be)
            eff_gops = ops_per_step / (wall / t) / 1e9
            rows.append({
                "theta": theta, "backend": be,
                "gamma_dx": round(float(stats["gamma_dx"]), 4),
                "gamma_dh": round(float(stats["gamma_dh"]), 4),
                "us_per_step": round(us, 2),
                "bytes_per_step": round(nbytes, 1),
                "eff_gops": round(eff_gops, 4),
            })
            lines.append(
                f"kernel.q8_{be}_th{theta},{us:.1f},"
                f"bytes/step={nbytes:.0f} eff_gops={eff_gops:.3f}")

    record = {
        "bench": "deltagru_q8_backends",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "batch": 1, "block": 128,
                   "ops_per_step": ops_per_step,
                   "weight_bytes": _backend_weight_bytes(),
                   **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
    }
    return lines, record


def run_quick(t=16, i=64, h=128, layers=2, thetas=(0.0, 0.2)) -> list[str]:
    """Reduced-size CI pass: exercises every backend + the bytes model
    without touching the committed BENCH_*.json baselines."""
    lines, record = bench_seq_record(t=t, i=i, h=h, layers=layers,
                                     thetas=thetas)
    lines += run_q8(t=t, i=i, h=h, layers=layers, thetas=thetas, write=False,
                    times_by_theta=_times_from_record(record))
    return lines


# ---------------------------------------------------------------------------
# Part 4: DeltaLSTM sequence shootout (the cell-parity trajectory)
# ---------------------------------------------------------------------------

def bench_lstm_record(t=64, i=128, h=256, layers=2,
                      thetas=(0.0, 0.05, 0.2)):
    """Wall time + fused-vs-dense parity for the DeltaLSTM backends.

    Mirrors :func:`bench_seq_record` on ``cell="lstm"`` programs: the
    seed-style per-step dispatch loop against every backend registered for
    the cell (``LSTM_BACKENDS`` — registry-derived, so ``fused_q8`` is
    swept automatically), plus a max-abs-error parity row (the fused
    kernel must track the dense reference — the quick CI pass fails hard
    on drift instead of silently recording it; the quantized path's own
    parity gates live in :func:`bench_lstm_q8_record`).
    """
    from repro.core.deltalstm import (deltalstm_sequence,
                                      deltalstm_stack_step,
                                      init_deltalstm_stack_state,
                                      init_lstm_stack)
    from repro.core.program import compile_delta_program
    key = jax.random.PRNGKey(0)
    params = init_lstm_stack(key, i, h, layers)
    xs = _walk_inputs(jax.random.fold_in(key, 1), t, 1, i)
    lines, rows = [], []

    def _lstm_seq_fn(backend):
        prog = compile_delta_program(params, backend=backend, cell="lstm")
        return jax.jit(lambda xs: prog.sequence(
            xs, theta, theta, collect_sparsity=False)[0])

    for theta in thetas:
        ys_d, _, st = deltalstm_sequence(params, xs, theta, theta)
        gdx, gdh = float(st["gamma_dx"]), float(st["gamma_dh"])
        ys_f, _, _ = deltalstm_sequence(params, xs, theta, theta,
                                        backend="fused")
        parity = float(jnp.max(jnp.abs(ys_f - ys_d)))
        if not (parity < 1e-4):
            raise AssertionError(
                f"fused DeltaLSTM drifted from dense at theta={theta}: "
                f"max|fused - dense| = {parity}")

        step = jax.jit(lambda s, x: deltalstm_stack_step(
            params, s, x, theta, theta))

        def per_step_loop():
            s = init_deltalstm_stack_state(params, (1,))
            y = None
            for x in xs:
                y, s, deltas = step(s, x)
                float(jnp.mean(deltas[0][0]))   # the seed's per-step sync
            return y

        seqs = [_lstm_seq_fn(be) for be in LSTM_BACKENDS]
        walls = _time_calls([(lambda s=s: s(xs)) for s in seqs], reps=30)
        times = {"per_step_dispatch": _time_call(per_step_loop)}
        times.update(dict(zip(LSTM_BACKENDS, walls)))

        for name, wall in times.items():
            us = wall / t * 1e6
            rows.append({"theta": theta, "gamma_dx": round(gdx, 4),
                         "gamma_dh": round(gdh, 4), "backend": name,
                         "us_per_step": round(us, 2),
                         "steps_per_s": round(t / wall, 1),
                         "fused_dense_maxerr": parity})
            lines.append(
                f"kernel.lstm_{name}_th{theta},{us:.1f},"
                f"gamma_dh={gdh:.3f} steps/s={t / wall:.0f} "
                f"parity={parity:.1e}")

    record = {
        "bench": "deltalstm_seq_backends",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "batch": 1, **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
    }
    return lines, record


def run_lstm(t=64, i=128, h=256, layers=2,
             thetas=(0.0, 0.05, 0.2), write=True) -> list[str]:
    """DeltaLSTM sequence wall time + parity; writes
    ``BENCH_deltalstm_seq.json`` (gated by ``check_regression``)."""
    lines, record = bench_lstm_record(t=t, i=i, h=h, layers=layers,
                                      thetas=thetas)
    if write:
        with open(BENCH_LSTM_JSON, "w") as f:
            json.dump(record, f, indent=1)
        lines.append(
            f"kernel.lstm_bench_json,0,wrote "
            f"{os.path.basename(BENCH_LSTM_JSON)}")
    return lines


def run_lstm_quick(t=16, i=64, h=128, layers=2,
                   thetas=(0.0, 0.2)) -> list[str]:
    """Reduced LSTM parity/bench pass for CI (no baseline writes)."""
    return run_lstm(t=t, i=i, h=h, layers=layers, thetas=thetas, write=False)


# ---------------------------------------------------------------------------
# Part 5: quantized DeltaLSTM bytes/GOp/s record (the 4-gate Eq. 8 story)
# ---------------------------------------------------------------------------

def bench_lstm_q8_record(t=64, i=128, h=256, layers=2,
                         thetas=(0.0, 0.05, 0.2), times_by_theta=None):
    """Bytes-streamed + effective-GOp/s shootout for the LSTM backends,
    with the quantized path's hard parity gates.

    Mirrors :func:`bench_q8_record` on ``cell="lstm"``. Two assertions
    fail the record (and therefore CI) instead of silently recording
    drift:

    * **kernel parity** — the ``fused_q8`` Pallas kernel (interpret mode)
      must be *bit-identical* to its jnp oracle on a sequence prefix (the
      code-domain accumulator makes any mismatch a real kernel bug, not
      rounding);
    * **quantization drift** — ``fused_q8`` must track the fp32 dense
      reference within the Q8.8/LUT quantization budget (a generous 0.25
      rail; real drift is layout/seam corruption, which lands far outside
      it).

    Each theta also records ``q8_bytes_matched_fp32`` — the fused_q8 bytes
    model evaluated at the *fp32 firing counts* — so the regression gate
    can assert the exact 0.25x invariant (int8 streams a quarter of the
    fp32 fused bytes at matched firing) without float-threshold noise.
    """
    from repro.core.deltalstm import deltalstm_sequence, init_lstm_stack
    from repro.core.sparsity import lstm_dims
    from repro.quant.export import quantize_delta_stack

    key = jax.random.PRNGKey(0)
    params = init_lstm_stack(key, i, h, layers)
    qparams, layouts_q8 = quantize_delta_stack(params, cell="lstm")
    xs = _walk_inputs(jax.random.fold_in(key, 1), t, 1, i)
    ops_per_step = lstm_dims(i, h, layers).params_per_timestep_ops
    lines, rows = [], []

    def _lstm_seq_fn(backend):
        from repro.core.program import compile_delta_program
        prog = compile_delta_program(
            qparams if backend == "fused_q8" else params, backend=backend,
            cell="lstm",
            layouts=layouts_q8 if backend == "fused_q8" else None)
        return jax.jit(lambda xs: prog.sequence(
            xs, theta, theta, collect_sparsity=False)[0])

    for theta in thetas:
        counts_fp = _mean_fired_blocks(params, xs, theta, backend="dense",
                                       cell="lstm")
        counts_q8 = _mean_fired_blocks(qparams, xs, theta,
                                       backend="fused_q8",
                                       layouts=layouts_q8, cell="lstm")
        ys_d, _, st = deltalstm_sequence(params, xs, theta, theta)
        ys_q, _, st_q = deltalstm_sequence(qparams, xs, theta, theta,
                                           backend="fused_q8",
                                           layouts=layouts_q8)
        # kernel parity on a prefix (interpret mode is the slow
        # correctness path; a prefix certifies the kernel all the same)
        tp = min(t, 12)
        ys_qk, _, _ = deltalstm_sequence(qparams, xs[:tp], theta, theta,
                                         backend="fused_q8",
                                         layouts=layouts_q8, interpret=True)
        kparity = float(jnp.max(jnp.abs(ys_q[:tp] - ys_qk)))
        if kparity != 0.0:
            raise AssertionError(
                f"fused_q8 LSTM Pallas kernel drifted from its jnp oracle "
                f"at theta={theta}: max|kernel - ref| = {kparity} "
                "(the code-domain accumulator makes this exact by "
                "construction — a nonzero gap is a kernel bug)")
        drift = float(jnp.max(jnp.abs(ys_q - ys_d)))
        if not (drift < 0.25):
            raise AssertionError(
                f"fused_q8 LSTM drifted from the fp32 dense reference at "
                f"theta={theta}: max|q8 - dense| = {drift} (beyond the "
                "Q8.8/LUT quantization budget)")

        times = (times_by_theta or {}).get(theta)
        if times is None or any(be not in times for be in LSTM_BACKENDS):
            seqs = [_lstm_seq_fn(be) for be in LSTM_BACKENDS]
            walls = _time_calls([(lambda s=s: s(xs)) for s in seqs],
                                reps=30)
            times = dict(zip(LSTM_BACKENDS, walls))

        fused_bytes = _bytes_per_step(params, counts_fp, "fused",
                                      cell="lstm")
        q8_bytes_matched = _bytes_per_step(params, counts_fp, "fused_q8",
                                           cell="lstm")
        for be in LSTM_BACKENDS:
            wall = times[be]
            counts, stats = ((counts_q8, st_q) if be == "fused_q8"
                             else (counts_fp, st))
            us = wall / t * 1e6
            nbytes = _bytes_per_step(params, counts, be, cell="lstm")
            eff_gops = ops_per_step / (wall / t) / 1e9
            row = {
                "theta": theta, "backend": be,
                "gamma_dx": round(float(stats["gamma_dx"]), 4),
                "gamma_dh": round(float(stats["gamma_dh"]), 4),
                "us_per_step": round(us, 2),
                "bytes_per_step": round(nbytes, 1),
                "eff_gops": round(eff_gops, 4),
            }
            if be == "fused_q8":
                # UNROUNDED: the regression gate asserts the exact 0.25x
                # ratio on these (scaling a float sum by a power of two
                # is exact; independent rounding would break equality for
                # non-integral bytes/step)
                row["q8_bytes_matched_fp32"] = q8_bytes_matched
                row["fused_bytes_matched_fp32"] = fused_bytes
                row["dense_drift"] = round(drift, 5)
            rows.append(row)
            lines.append(
                f"kernel.lstm_q8_{be}_th{theta},{us:.1f},"
                f"bytes/step={nbytes:.0f} eff_gops={eff_gops:.3f}")

    record = {
        "bench": "deltalstm_q8_backends",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "batch": 1, "block": 128, "gates": 4,
                   "ops_per_step": ops_per_step,
                   "weight_bytes": _backend_weight_bytes("lstm"),
                   **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
    }
    return lines, record


def run_lstm_q8(t=64, i=128, h=256, layers=2,
                thetas=(0.0, 0.05, 0.2), write=True,
                times_by_theta=None) -> list[str]:
    """Quantized-LSTM bytes/GOp/s shootout + parity gates; writes
    ``BENCH_deltalstm_q8.json`` (gated by ``check_regression``)."""
    lines, record = bench_lstm_q8_record(t=t, i=i, h=h, layers=layers,
                                         thetas=thetas,
                                         times_by_theta=times_by_theta)
    if write:
        with open(BENCH_LSTM_Q8_JSON, "w") as f:
            json.dump(record, f, indent=1)
        lines.append(
            f"kernel.lstm_q8_bench_json,0,wrote "
            f"{os.path.basename(BENCH_LSTM_Q8_JSON)}")
    return lines


def run_lstm_q8_quick(t=16, i=64, h=128, layers=2,
                      thetas=(0.0, 0.2)) -> list[str]:
    """Reduced quantized-LSTM parity/bytes pass for CI (hard fused_q8
    parity assertions, no baseline writes) — the `make bench-lstm-q8-quick`
    entry."""
    return run_lstm_q8(t=t, i=i, h=h, layers=layers, thetas=thetas,
                       write=False)


# ---------------------------------------------------------------------------
# Part 6: int4 nibble-packed bytes/GOp/s record (the 0.5x-of-q8 story)
# ---------------------------------------------------------------------------

def bench_q4_record(t=64, i=128, h=256, layers=2,
                    thetas=(0.0, 0.05, 0.2), cell="gru"):
    """Bytes-streamed + effective-GOp/s record for the ``fused_q4``
    nibble-packed backend, with its hard parity gates.

    One function serves both cell families (``cell="gru"`` / ``"lstm"``);
    the swept backends are the quantized-width ladder ``dense`` (fp32,
    4 B/weight) -> ``fused_q8`` (1 B) -> ``fused_q4`` (0.5 B — two codes
    per streamed byte). Three assertions fail the record (and CI) instead
    of silently recording drift:

    * **kernel parity** — the ``fused_q4`` Pallas kernel (interpret mode)
      must be *bit-identical* to its jnp oracle on a sequence prefix: the
      code-domain accumulator makes the in-register nibble unpack exact,
      so any mismatch is a real kernel/packing bug, not rounding;
    * **quantization drift** — ``fused_q4`` must track the fp32 dense
      reference within 2x the int8 budget (a 0.5 rail vs fused_q8's
      0.25): int4's coarser Q0.3 weight grid costs accuracy, but layout /
      nibble-order corruption lands far outside the rail;
    * the ``fused_q8`` path re-asserts its own 0.25 rail, so the record
      always carries a valid q8 reference for the 0.5x bytes gate.

    Each theta records UNROUNDED ``q4_bytes_matched_fp32`` /
    ``q8_bytes_matched_fp32`` / ``fused_bytes_matched_fp32`` — the bytes
    model evaluated at the *fp32 firing counts* — so the regression gate
    can assert the exact ladder (q4 = 0.5x q8 = 0.125x fp32 fused bytes
    at matched firing) on any machine without float-threshold noise.
    """
    from repro.core.program import compile_delta_program
    from repro.quant.export import quantize_delta_stack
    if cell == "gru":
        from repro.core.deltagru import deltagru_sequence as sequence
        from repro.core.deltagru import init_gru_stack as init_stack
        from repro.core.sparsity import GruDims
        ops_per_step = GruDims(i, h, layers).params_per_timestep_ops
    else:
        from repro.core.deltalstm import deltalstm_sequence as sequence
        from repro.core.deltalstm import init_lstm_stack as init_stack
        from repro.core.sparsity import lstm_dims
        ops_per_step = lstm_dims(i, h, layers).params_per_timestep_ops

    key = jax.random.PRNGKey(0)
    params = init_stack(key, i, h, layers)
    qp8, lay8 = quantize_delta_stack(params, cell=cell)
    qp4, lay4 = quantize_delta_stack(params, cell=cell, bits=4)
    xs = _walk_inputs(jax.random.fold_in(key, 1), t, 1, i)
    sweep = ("dense", "fused_q8", "fused_q4")
    variants = {"dense": (params, None), "fused_q8": (qp8, lay8),
                "fused_q4": (qp4, lay4)}
    lines, rows = [], []

    def _seq_fn(backend):
        p, lay = variants[backend]
        prog = compile_delta_program(p, backend=backend, cell=cell,
                                     layouts=lay)
        return jax.jit(lambda xs: prog.sequence(
            xs, theta, theta, collect_sparsity=False)[0])

    for theta in thetas:
        counts_fp = _mean_fired_blocks(params, xs, theta, backend="dense",
                                       cell=cell)
        counts_q8 = _mean_fired_blocks(qp8, xs, theta, backend="fused_q8",
                                       layouts=lay8, cell=cell)
        counts_q4 = _mean_fired_blocks(qp4, xs, theta, backend="fused_q4",
                                       layouts=lay4, cell=cell)
        counts = {"dense": counts_fp, "fused_q8": counts_q8,
                  "fused_q4": counts_q4}
        ys_d, _, st = sequence(params, xs, theta, theta)
        ys_q8, _, st8 = sequence(qp8, xs, theta, theta, backend="fused_q8",
                                 layouts=lay8)
        ys_q4, _, st4 = sequence(qp4, xs, theta, theta, backend="fused_q4",
                                 layouts=lay4)
        stats = {"dense": st, "fused_q8": st8, "fused_q4": st4}
        # kernel parity on a prefix (interpret mode is the slow
        # correctness path; a prefix certifies the kernel all the same)
        tp = min(t, 12)
        ys_q4k, _, _ = sequence(qp4, xs[:tp], theta, theta,
                                backend="fused_q4", layouts=lay4,
                                interpret=True)
        kparity = float(jnp.max(jnp.abs(ys_q4[:tp] - ys_q4k)))
        if kparity != 0.0:
            raise AssertionError(
                f"fused_q4 {cell} Pallas kernel drifted from its jnp "
                f"oracle at theta={theta}: max|kernel - ref| = {kparity} "
                "(the code-domain accumulator makes the nibble unpack "
                "exact by construction — a nonzero gap is a kernel or "
                "packing bug)")
        drift8 = float(jnp.max(jnp.abs(ys_q8 - ys_d)))
        drift4 = float(jnp.max(jnp.abs(ys_q4 - ys_d)))
        if not (drift8 < 0.25):
            raise AssertionError(
                f"fused_q8 {cell} drifted from the fp32 dense reference "
                f"at theta={theta}: max|q8 - dense| = {drift8} (beyond "
                "the Q8.8/LUT quantization budget)")
        if not (drift4 < 0.5):
            raise AssertionError(
                f"fused_q4 {cell} drifted from the fp32 dense reference "
                f"at theta={theta}: max|q4 - dense| = {drift4} (beyond "
                "2x the int8 budget — the int4 grid is coarser, but "
                "drift past the 0.5 rail means layout/nibble corruption, "
                "not quantization)")

        seqs = [_seq_fn(be) for be in sweep]
        walls = _time_calls([(lambda s=s: s(xs)) for s in seqs], reps=30)
        times = dict(zip(sweep, walls))

        matched = {be: _bytes_per_step(params, counts_fp, be, cell=cell)
                   for be in ("fused", "fused_q8", "fused_q4")}
        drift = {"dense": 0.0, "fused_q8": drift8, "fused_q4": drift4}
        for be in sweep:
            wall = times[be]
            us = wall / t * 1e6
            nbytes = _bytes_per_step(params, counts[be], be, cell=cell)
            eff_gops = ops_per_step / (wall / t) / 1e9
            row = {
                "theta": theta, "backend": be,
                "gamma_dx": round(float(stats[be]["gamma_dx"]), 4),
                "gamma_dh": round(float(stats[be]["gamma_dh"]), 4),
                "us_per_step": round(us, 2),
                "bytes_per_step": round(nbytes, 1),
                "eff_gops": round(eff_gops, 4),
                "dense_drift": round(drift[be], 5),
            }
            if be == "fused_q4":
                # UNROUNDED: the regression gate asserts the exact
                # 0.5x-of-q8 / 0.125x-of-fused ladder on these (scaling
                # a float sum by a power of two is exact; independently
                # rounded copies need not satisfy the ratios)
                row["q4_bytes_matched_fp32"] = matched["fused_q4"]
                row["q8_bytes_matched_fp32"] = matched["fused_q8"]
                row["fused_bytes_matched_fp32"] = matched["fused"]
            rows.append(row)
            lines.append(
                f"kernel.{cell}_q4_{be}_th{theta},{us:.1f},"
                f"bytes/step={nbytes:.0f} eff_gops={eff_gops:.3f} "
                f"drift={drift[be]:.4f}")

    record = {
        "bench": f"delta{cell}_q4_backends",
        "unit": "us_per_step",
        "config": {"t": t, "input": i, "hidden": h, "layers": layers,
                   "batch": 1, "block": 128, "cell": cell,
                   "ops_per_step": ops_per_step,
                   "weight_bytes": _backend_weight_bytes(cell),
                   **record_meta()},
        "created_unix": int(time.time()),
        "rows": rows,
    }
    return lines, record


def run_q4(t=64, i=128, h=256, layers=2,
           thetas=(0.0, 0.05, 0.2), write=True) -> list[str]:
    """int4 bytes/GOp/s records for BOTH cell families; writes
    ``BENCH_deltagru_q4.json`` + ``BENCH_deltalstm_q4.json`` (gated by
    ``check_regression``)."""
    lines = []
    for cell, path in (("gru", BENCH_Q4_JSON), ("lstm", BENCH_LSTM_Q4_JSON)):
        ls, record = bench_q4_record(t=t, i=i, h=h, layers=layers,
                                     thetas=thetas, cell=cell)
        lines += ls
        if write:
            with open(path, "w") as f:
                json.dump(record, f, indent=1)
            lines.append(f"kernel.{cell}_q4_bench_json,0,wrote "
                         f"{os.path.basename(path)}")
    return lines


def run_q4_quick(t=16, i=64, h=128, layers=2,
                 thetas=(0.0, 0.2)) -> list[str]:
    """Reduced int4 parity/bytes pass for CI (hard fused_q4 kernel-parity
    + drift assertions on both cells, no baseline writes) — the
    ``make bench-q4-quick`` entry."""
    return run_q4(t=t, i=i, h=h, layers=layers, thetas=thetas, write=False)


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="kernel benches (delta_spmv + DeltaGRU/DeltaLSTM "
                    "sequence + quantized shootouts)")
    ap.add_argument("--lstm", action="store_true",
                    help="run only the DeltaLSTM parity/bench suite")
    ap.add_argument("--lstm-q8", action="store_true",
                    help="run only the quantized-DeltaLSTM parity/bytes "
                         "suite")
    ap.add_argument("--q4", action="store_true",
                    help="run only the int4 nibble-packed parity/bytes "
                         "suite (both cells)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced CI pass (small dims, no baseline writes)")
    args = ap.parse_args(argv)
    if args.q4:
        print("\n".join(run_q4_quick() if args.quick else run_q4()))
    elif args.lstm_q8:
        print("\n".join(run_lstm_q8_quick() if args.quick
                        else run_lstm_q8()))
    elif args.lstm:
        print("\n".join(run_lstm_quick() if args.quick else run_lstm()))
    elif args.quick:
        print("\n".join(run_quick()))
    else:
        print("\n".join(run()))


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
