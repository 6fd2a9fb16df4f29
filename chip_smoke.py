"""Run the EdgeDRNN serving path once on a TPU, at the width of 2L-768H.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --fleet [--seed N]  # four chips: the fabric only

The model is the paper's 2L-768H GRU (``PAPER_NETWORKS["2L-768H"]``: 40
inputs, 768 hidden, 2 layers, 12 outputs) with random weights from
``--seed``, served through the calls a user makes:
``compile_delta_program`` -> ``DeltaStreamEngine`` -> ``DeltaStreamBatcher``,
at the paper's Q8.8 thresholds (Θ = 64, i.e. 0.25, on inputs and hidden).

One chip runs two phases:

(a) one stream of ``FRAMES`` frames through ``DeltaStreamEngine.step`` and
    ``step_many``, for each of ``fused``, ``fused_q8`` and ``fused_q4``;
(b) ``N_STREAMS_B`` seeded streams of 20-200 frames through a
    ``DeltaStreamBatcher`` on an ``n_streams=8`` ``fused_q8`` engine, which
    routes itself onto the ``fused_q8_batch`` tile kernel.

``--fleet`` runs only (c): a ``ShardedStreamFleet`` of ``n_streams=32``
``fused_q8`` slots on a 4-way ``data`` mesh behind a ``StreamRouter``,
fed ``N_STREAMS_C`` seeded streams.

Every phase is compared with the repository's reference, run on the same
chip: ``fused`` with the ``dense`` program at ``highest`` matmul
precision, the quantized backends with their jnp oracles (the ``use_ref``
route), which they match bit for bit, and every fleet stream with a
one-device engine at the fleet's tile width, also bit for bit. Each phase
prints one JSON line, including whether the compiled step holds a Pallas
kernel (``tpu_custom_call``), which is required. The last line is the
verdict, ``{"ok": true, "device": {...}}``. Any failure exits non-zero.
Off a TPU the script refuses to run and exits non-zero.

The phases are plain functions of the model size, so the test suite runs
them at a tiny width on CPU (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.program import compile_delta_program  # noqa: E402
from repro.core.thresholds import ThresholdPolicy  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.gru_rnn import PAPER_NETWORKS, init_gru_model  # noqa: E402
from repro.serve.engine import DeltaStreamEngine  # noqa: E402
from repro.serve.scheduler import DeltaStreamBatcher  # noqa: E402

NETWORK = "2L-768H"
PAPER_THETA = ThresholdPolicy.global_q88(64)
FRAMES = 100                 # phase (a): frames per backend
STEP_FRAMES = 10             # of which one at a time through step()
N_STREAMS_B = 16             # phase (b): streams through the batcher
N_STREAMS_C = 64             # phase (c): streams through the router
STREAM_LEN = (20, 200)
FLEET_DEVICES = 4
FLEET_STREAMS_PER_SHARD = 8
# fp32 ``fused`` against ``dense``: the same sums in another order, so the
# outputs agree to rounding; the quantized paths must agree exactly
FP32_TOL = 1e-3


def speech_like_frames(rng: np.random.Generator, t: int, i_dim: int):
    """``[t, i_dim]`` frames of an AR(1) process: neighbouring frames are
    close, as filterbank frames of speech are, so Θ leaves some silent."""
    x = np.empty((t, i_dim), np.float32)
    x[0] = rng.standard_normal(i_dim)
    for k in range(1, t):
        x[k] = 0.9 * x[k - 1] + 0.45 * rng.standard_normal(i_dim)
    return x


@contextlib.contextmanager
def oracle_route():
    """Trace the kernel backends as their jnp oracles (``use_ref``)."""
    ops.set_force_ref(True)
    try:
        yield
    finally:
        ops.set_force_ref(False)


def _has_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _stream_once(program, task, frames, thresholds):
    """One engine, one stream: ``STEP_FRAMES`` frames through ``step``,
    the rest in one ``step_many``. Returns outputs ``[T, O]``, the first
    calls' wall time (compilation included) and the engine."""
    eng = DeltaStreamEngine(program, task, thresholds=thresholds)
    n = min(STEP_FRAMES, len(frames) - 1)
    first, s_step = _timed(eng.step, frames[0])
    outs = [np.asarray(first)[None]]
    for x in frames[1:n]:
        outs.append(np.asarray(eng.step(x))[None])
    rest, s_many = _timed(eng.step_many, frames[n:])
    outs.append(np.asarray(rest))
    return np.concatenate(outs), s_step + s_many, eng


def phase_stream(params, task, backend: str, frames,
                 thresholds=PAPER_THETA, interpret=None) -> dict:
    """Phase (a): one stream through ``backend`` against its reference.
    ``interpret`` is the Pallas mode (``None``: compiled on a TPU)."""
    prog = compile_delta_program(params, backend=backend,
                                 interpret=interpret)
    got, compile_s, eng = _stream_once(prog, task, frames, thresholds)
    kernel = _has_kernel(eng._step1, eng._whole, eng._bufs,
                         np.asarray(frames[0]))
    if backend == "fused":
        dense = compile_delta_program(params, backend="dense")
        with jax.default_matmul_precision("highest"):
            want, _, _ = _stream_once(dense, task, frames, thresholds)
        tol, ref = FP32_TOL, "dense@highest"
    else:
        with oracle_route():
            want, _, _ = _stream_once(prog, task, frames, thresholds)
        tol, ref = 0.0, "jnp oracle"
    diff = float(np.max(np.abs(got - want)))
    return {"phase": "stream", "backend": eng.backend, "frames": len(frames),
            "compile_s": compile_s, "reference": ref, "max_abs_diff": diff,
            "tol": tol, "finite": bool(np.isfinite(got).all()),
            "tpu_custom_call": kernel,
            "ok": bool(np.isfinite(got).all() and diff <= tol)}


def _drain(program, task, streams, n_streams, thresholds):
    eng = DeltaStreamEngine(program, task, thresholds=thresholds,
                            n_streams=n_streams)
    batcher = DeltaStreamBatcher(eng)
    uids = [batcher.submit(s) for s in streams]
    t0 = time.perf_counter()
    done = batcher.step()
    jax.block_until_ready(eng.state)
    first_s = time.perf_counter() - t0
    done += batcher.run_until_drained()
    outs = {r.uid: np.stack(r.outputs) for r in done}
    return [outs[u] for u in uids], first_s, eng


def phase_batcher(params, task, streams, n_streams: int = 8,
                  thresholds=PAPER_THETA, interpret=None) -> dict:
    """Phase (b): streams through a ``DeltaStreamBatcher`` on a
    ``fused_q8`` engine (tile path) against the oracle route."""
    prog = compile_delta_program(params, backend="fused_q8",
                                 interpret=interpret)
    got, compile_s, eng = _drain(prog, task, streams, n_streams, thresholds)
    kernel = _has_kernel(eng._step, eng._whole, eng._bufs,
                         np.zeros((n_streams, task.input_size), np.float32))
    with oracle_route():
        want, _, _ = _drain(prog, task, streams, n_streams, thresholds)
    diff = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    finite = all(np.isfinite(g).all() for g in got)
    return {"phase": "batcher", "backend": eng.backend,
            "n_streams": n_streams, "streams": len(streams),
            "frames": int(sum(len(s) for s in streams)),
            "compile_s": compile_s, "reference": "jnp oracle",
            "max_abs_diff": diff, "tol": 0.0, "finite": bool(finite),
            "tpu_custom_call": kernel,
            "ok": bool(finite and diff == 0.0
                       and eng.backend == "fused_q8_batch")}


def phase_fleet(params, task, arrivals, n_devices: int = FLEET_DEVICES,
                streams_per_shard: int = FLEET_STREAMS_PER_SHARD,
                thresholds=PAPER_THETA, interpret=None) -> dict:
    """Phase (c): ``arrivals`` (``[(tick, frames), ...]``) through a
    router in front of a ``fused_q8`` fleet on an ``n_devices``-way data
    mesh; every completed stream is checked bitwise against a one-device
    engine at the fleet's tile width."""
    from repro.dist.elastic import best_mesh
    from repro.dist.serving import ShardedStreamFleet
    from repro.serve.loadgen import fabric_parity, run_fabric_load
    from repro.serve.router import StreamRouter
    prog = compile_delta_program(params, backend="fused_q8",
                                 interpret=interpret)
    fleet = ShardedStreamFleet(
        prog, task, n_streams=n_devices * streams_per_shard,
        mesh=best_mesh(n_devices, model_parallel=1), thresholds=thresholds)
    router = StreamRouter(fleet)
    t0 = time.perf_counter()
    summary = run_fabric_load(router, arrivals)
    wall_s = time.perf_counter() - t0
    cons = router.conservation()
    parity = fabric_parity(arrivals, summary.results, fleet)
    x0 = np.zeros((fleet.n_streams, task.input_size), np.float32)
    kernel = _has_kernel(fleet._fleet_step, fleet.state, fleet._carry, x0)
    return {"phase": "fleet", "backend": fleet.backend,
            "mesh": dict(fleet.mesh.shape), "n_streams": fleet.n_streams,
            "streams": len(arrivals), "completed": cons["completed"],
            "parity_ok": parity, "frames": cons["frames_out"],
            "ticks": summary.ticks, "run_s": wall_s,
            "reference": "one-device engine, same tile width",
            "tpu_custom_call": kernel,
            "ok": bool(cons["conserved"] and cons["frames_conserved"]
                       and cons["completed"] == len(arrivals)
                       and parity == len(arrivals))}


def _streams(rng, n, i_dim):
    lo, hi = STREAM_LEN
    return [speech_like_frames(rng, int(rng.integers(lo, hi + 1)), i_dim)
            for _ in range(n)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", action="store_true",
                    help=f"run only the {FLEET_DEVICES}-chip serving fabric")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {platform!r} "
              f"({len(devices)} device(s)); nothing was run",
              file=sys.stderr)
        return 2
    if args.fleet and len(devices) < FLEET_DEVICES:
        print(f"chip_smoke --fleet: needs {FLEET_DEVICES} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(json.dumps({"compile_cache": cache, "warm": warm,
                      "network": NETWORK}), flush=True)

    task = PAPER_NETWORKS[NETWORK]
    params = init_gru_model(jax.random.PRNGKey(args.seed), task)
    rng = np.random.default_rng(args.seed)
    results = []
    if args.fleet:
        from repro.serve.loadgen import poisson_arrivals
        arrivals = poisson_arrivals(
            N_STREAMS_C, rate_per_tick=4.0, min_len=STREAM_LEN[0],
            max_len=STREAM_LEN[1], input_size=task.input_size,
            seed=args.seed)
        results.append(phase_fleet(params, task, arrivals))
        print(json.dumps(results[-1]), flush=True)
    else:
        frames = speech_like_frames(rng, FRAMES, task.input_size)
        for backend in ("fused", "fused_q8", "fused_q4"):
            results.append(phase_stream(params, task, backend, frames))
            print(json.dumps(results[-1]), flush=True)
        streams = _streams(rng, N_STREAMS_B, task.input_size)
        results.append(phase_batcher(params, task, streams))
        print(json.dumps(results[-1]), flush=True)
    failed = [r for r in results if not (r["ok"] and r["tpu_custom_call"])]
    if failed:
        print(f"chip_smoke: {len(failed)} phase(s) failed: "
              f"{[r['phase'] + ':' + r['backend'] for r in failed]}",
              file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
