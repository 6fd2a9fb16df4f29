"""One run of one cell: set-up, the measured window, the check, the line.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from
host spans, the profiler's trace of the first seconds of the window, and
the reference's fired-column counts for the traced ticks.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

from harness import serve, spec, traffic
from harness import trace as tr

TRACE_SPAN_S = 3.0          # the trace covers the window's first seconds


@dataclass
class Reading:
    """What a per-layer reader gets."""

    cell: spec.Cell
    run: serve.Run
    spans: dict                    # name -> [[t0, t1], ...] (host clock, s)
    peaks: dict
    chips: int
    dense_weights: int
    trace: dict | None = None      # see reduce_trace
    work: dict | None = None       # reference counts of the traced ticks


def _compile_counter():
    """Count compilations and cache loads from now on."""
    import jax
    box = {"n": 0, "on": False}

    def dur(event, *a, **k):
        if box["on"] and event in ("/jax/core/compile/backend_compile_duration",
                                   "/jax/core/compile/jaxpr_trace_duration"):
            box["n"] += 1

    def ev(event, *a, **k):
        if box["on"] and event == "/jax/compilation_cache/cache_hits":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(dur)
    jax.monitoring.register_event_listener(ev)
    return box


def _cache_dir() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        spec.ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _front(cell: spec.Cell, prog, sched, spans: serve.Spans):
    """Build, warm and instrument the cell's entry point."""
    from repro.serve.engine import DeltaStreamEngine
    t = cell.traffic
    task, theta = cell.family().task(cell.config)
    f0 = sched.frames[0]
    if t["entry"] == "engine":
        eng = DeltaStreamEngine(prog, task, thresholds=theta)
        serve.warm_engine(eng, f0[0])
        spans.wrap(eng, "step", "engine.step")
        return eng
    if t["entry"] == "batcher":
        from repro.serve.scheduler import DeltaStreamBatcher
        eng = DeltaStreamEngine(prog, task, thresholds=theta,
                                n_streams=int(t["slots"]))
        b = DeltaStreamBatcher(eng)
        serve.warm_batcher(b, f0, sched.lengths)
        spans.wrap(b, "step", "batcher.step")
        spans.wrap(eng, "step", "engine.step")
        return b
    if t["entry"] == "router":
        import jax
        from repro.dist.serving import ShardedStreamFleet
        from repro.dist.sharding import make_mesh
        from repro.serve.router import RouterPolicy, StreamRouter
        shards = int(t["shards"])
        mesh = make_mesh((shards, 1), ("data", "model"),
                         devices=jax.devices()[:shards])
        fleet = ShardedStreamFleet(prog, task, n_streams=int(t["slots"]),
                                   mesh=mesh, thresholds=theta)
        router = StreamRouter(fleet, RouterPolicy(
            max_queue=int(t["max_queue"])))
        serve.warm_router(router, f0, sched.lengths)
        spans.wrap(router, "tick", "router.tick")
        spans.wrap(fleet, "step", "fleet.step")
        return router
    raise ValueError(f"unknown entry {t['entry']!r}")


def _loop(cell, front, sched, *, hop, n_pre, seconds, spans, tracer):
    t = cell.traffic
    kw = dict(hop=hop, n_pre=n_pre, seconds=seconds, spans=spans,
              tracer=tracer)
    if t["entry"] == "engine":
        return serve.engine_loop(front, sched.frames[0],
                                 closed=t["loop"] == "closed", **kw)
    if t["entry"] == "batcher":
        return serve.batcher_loop(front, sched, **kw)
    return serve.router_loop(front, sched, **kw)


def _peak_memory(devs) -> int | None:
    peaks = []
    for d in devs:
        try:
            st = d.memory_stats() or {}
        except Exception:   # backends without memory stats
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def check(cell: spec.Cell, w: dict, sched, run: serve.Run, *,
          traced=None) -> tuple[dict, dict | None]:
    """Compare every frame the window's streams were served with the plain
    reference replayed over the same frames from the same start. Returns
    the compared numbers and, for a traced run, the reference's fired
    column counts of the traced ticks."""
    ref = cell.reference()
    streams = run.streams_in_window()
    frames = [sched.frames[s][:run.served[s]] for s in streams]
    kw = {}
    if traced is not None:
        ka, kb = traced
        st = []
        for s in streams:
            k = run.serve_ticks(s) - ka
            st.append(np.where((k >= 0) & (k < kb - ka), k, -1))
        kw = dict(serve_ticks=st, n_ticks=kb - ka)
    out = ref.replay(w, frames, **cell.family().reference_kwargs(cell.config),
                     **kw)
    want, work = (out[0], {"union": out[1], "counts": out[2]}) \
        if traced is not None else (out, None)
    missing, n, worst = 0, 0, 0.0
    sq = 0.0
    for s, y in zip(streams, want):
        got = run.outputs[s]
        missing += int(run.served[s]) - len(got)
        m = min(len(got), len(y))
        d = np.abs(got[:m] - y[:m])
        worst = max(worst, float(np.max(d)) if m else 0.0)
        if m and not np.all(np.isfinite(got[:m])):
            worst = math.inf
        sq += float(np.sum(np.square(y)))
        n += int(y.size)
    rms = math.sqrt(sq / max(n, 1)) or 1.0
    return {"out_gap": worst / rms, "missing_outputs": missing,
            "frames_checked": sum(len(f) for f in frames)}, work


def control_run(cell: spec.Cell, w: dict, sched, run: serve.Run,
                control: str) -> serve.Run:
    """The run with the reference, at the family's control ``control``,
    put in the program's place: its outputs for every frame the program
    served."""
    fam = cell.family()
    streams = run.streams_in_window()
    frames = [sched.frames[s][:run.served[s]] for s in streams]
    kw = {**fam.reference_kwargs(cell.config), **fam.controls[control]}
    outs = cell.reference().replay(w, frames, **kw)
    new = list(run.outputs)
    for s, y in zip(streams, outs):
        new[s] = y
    return dataclasses.replace(run, outputs=new)


def _limits(cell) -> dict:
    return {"out_gap": cell.config["limits"]["out_gap"],
            "missing_outputs": 0}


def _end_to_end(cell, run: serve.Run, setup_s: float) -> dict:
    """Every end-to-end number the harness knows; the line carries those
    the cell lists in ``BENCHMARK.json``."""
    lat = run.frame_latency() * 1e3
    w = run.window_ticks()
    if run.closed:
        w0, w1 = run.window
    else:                        # until the window's last tick was served
        w0, w1 = run.window[0], run.tick_done[w[-1]]
    p50 = float(np.percentile(lat, 50))
    vals = {"frame_latency_p50_ms": p50,
            # the same median in the batched cells, bounded apart: their
            # runs spread wider, as harvests of long streams fall
            "frame_latency_p50_ms.batched": p50,
            "frame_latency_p95_ms": float(np.percentile(lat, 95)),
            "frame_latency_p99_ms": float(np.percentile(lat, 99)),
            "frames_on_time_share": 100.0 * float(np.mean(
                lat <= run.hop * 1e3)),
            "frames_per_s": len(lat) / (w1 - w0),
            "setup_s": setup_s}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in vals}


def reduce_trace(ev: dict, run: serve.Run, traced, chips: int) -> dict:
    """Device busy time, op seconds and named idle gaps of the traced
    window ticks (``ev`` as :func:`trace.extract` gives it), on the
    trace's clock."""
    ticks = sorted((a for a in ev["host"] if a[0] == "bench.tick"),
                   key=lambda a: a[1])
    ka, kb = traced
    first = kb - len(ticks)
    # the trace clock against the host clock, from the tick annotations
    k = np.arange(max(first, ka), kb)
    starts = np.array([ticks[i - first][1] for i in k])
    off = float(np.median(starts - run.tick_start[k] * 1e9))
    lo = off + run.tick_start[ka] * 1e9
    hi = off + run.tick_done[kb - 1] * 1e9
    tick_spans = tr.merge(np.stack([run.tick_due[ka:kb],
                                    run.tick_done[ka:kb]], 1) * 1e9 + off)
    devs = sorted(ev["devices"])[:chips]
    if not devs:
        raise RuntimeError("the trace holds no TPU device plane")
    busy = {d: tr.busy(ev["devices"][d]) for d in devs}
    ops, idle = {}, {}
    for d in devs:
        for n, s in tr.op_seconds(ev["devices"][d], lo, hi).items():
            ops[n] = ops.get(n, 0.0) + s
        for n, s in tr.name_gaps(tr.gaps(busy[d], lo, hi),
                                 ev["host"]).items():
            idle[n] = idle.get(n, 0.0) + s
    return {
        "events": {d: ev["devices"][d] for d in devs}, "host": ev["host"],
        "lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean([tr.clipped(busy[d], lo, hi)
                                 for d in devs])) * 1e-9,
        "tick_busy_s": float(np.mean([tr.intersect(busy[d], tick_spans)
                                      for d in devs])) * 1e-9,
        "tick_s": float(np.sum(tick_spans[:, 1] - tick_spans[:, 0])) * 1e-9,
        "ops": ops, "idle": idle, "chips": len(devs),
    }


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, *, workdir: str | None = None,
             peaks: dict | None = None, controls=(), log=print) -> dict:
    """Everything after the device check; returns the result line.
    ``peaks`` defaults to the table's row for the device's kind. For each
    of the family's controls named in ``controls`` the line also carries,
    under ``control``, the numbers compared when that control stands in
    for the program, and whether they pass (a control has to come out as
    not correct)."""
    import jax
    cfg, t = cell.config, cell.traffic
    hop = t["hop_ms"] * 1e-3
    n_pre = int(round(t["pre_window_s"] / hop))
    phases = {"start": time.perf_counter() - t_process}
    fam = cell.family()
    w = fam.weights(cfg, seed)
    phases["weights"] = time.perf_counter() - t_process
    prog = fam.program(cfg, w)
    phases["program"] = time.perf_counter() - t_process
    sched = traffic.build(t, cfg, seed, seconds, hop)
    phases["traffic"] = time.perf_counter() - t_process
    spans = serve.Spans(trace)
    workdir = workdir or os.path.join(spec.ROOT, ".bench_work")
    trace_dir = os.path.join(workdir, "trace", cell.name) if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = serve.Tracer(trace_dir,
                          stop=n_pre + int(round(TRACE_SPAN_S / hop)),
                          span_s=TRACE_SPAN_S)
    counter = _compile_counter()
    front = _front(cell, prog, sched, spans)
    phases["warm"] = time.perf_counter() - t_process
    counter["on"] = True
    # no garbage collection inside the window: what set-up left is frozen
    gc.collect()
    gc.freeze()
    gc.disable()
    tracer.on()
    try:
        run = _loop(cell, front, sched, hop=hop, n_pre=n_pre,
                    seconds=seconds, spans=spans, tracer=tracer)
    finally:
        gc.enable()
        gc.unfreeze()
    counter["on"] = False
    setup_s = run.window[0] - t_process
    devs = jax.devices()[:cell.chips]
    mem = _peak_memory(devs)
    wt = run.window_ticks()
    late = (run.tick_start - run.tick_due)[wt] * 1e3
    slow = int(wt[np.argmax((run.tick_done - run.tick_start)[wt])])
    gen = {"generator_late_ms_p50": float(np.percentile(late, 50)),
           "generator_late_ms_p95": float(np.percentile(late, 95)),
           "generator_late_ms_max": float(np.max(late)),
           # the window's longest tick, and the CPU time the process spent
           # in it: far less CPU than wall time means the process waited
           "slowest_tick": slow - run.n_pre,
           "slowest_tick_ms": float(run.tick_done[slow]
                                    - run.tick_start[slow]) * 1e3,
           "slowest_tick_cpu_ms": float(run.tick_cpu[slow]) * 1e3,
           "compiles_in_loop": counter["n"],
           "setup_s_at": phases}
    log(json.dumps({"run": cell.name, "seed": seed, **gen}), file=sys.stderr)
    del front, prog
    gc.collect()
    traced = tracer.traced
    numbers, work = check(cell, w, sched, run, traced=traced)
    limits = _limits(cell)
    ctrl = {c: check(cell, w, sched, control_run(cell, w, sched, run, c))[0]
            for c in controls}
    correct = all(numbers[k] <= v for k, v in limits.items())
    attempted = int(sum(np.sum(run.serve_ticks(s) >= run.n_pre)
                        for s in run.streams_in_window()))
    kind = devs[0].device_kind
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(run.rejected),
              "metrics": {},
              "device": {"platform": devs[0].platform, "kind": kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem}}
    if not trace:
        result["metrics"] = _end_to_end(cell, run, setup_s)
    else:
        red = reduce_trace(tr.extract(trace_dir), run, traced, len(devs))
        reading = Reading(cell=cell, run=run, spans=spans.arrays(),
                          peaks=peaks or spec.peaks(kind), chips=len(devs),
                          dense_weights=fam.dense_weights(cfg),
                          trace=red, work={**work, "traced": traced})
        readers = cell.readers()
        for m in cell.per_layer:
            v = readers[m["name"]].read(reading)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": tr.top(red["ops"]),
                               "idle_gaps": tr.top(red["idle"])}
        shutil.rmtree(trace_dir, ignore_errors=True)
    record = {"cell": cell.name, "seed": seed, "seconds": seconds,
              "trace": trace, **gen, "setup_s": setup_s}
    os.makedirs(os.path.join(workdir, "runs"), exist_ok=True)
    with open(os.path.join(workdir, "runs",
                           f"{cell.name}-{seed}-{int(trace)}.json"),
              "w") as f:
        json.dump(record, f)
    if not run.closed:
        result["queued"] = {"window_open": run.queued(run.n_pre),
                            "window_close": run.queued(run.n_ticks - 1)}
    result["frames_checked"] = numbers["frames_checked"]
    if ctrl:
        result["control"] = {
            c: {**v, "correct": all(v[k] <= lim for k, lim in limits.items())}
            for c, v in ctrl.items()}
    result["compared"] = {k: {"value": numbers[k], "limit": limits[k]}
                          for k in limits}
    return result


def main(argv, t_process: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import repro.serve.engine  # noqa: F401  (the system under test)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 2
    spec.peaks(devices[0].device_kind)
    _cache_dir()
    prec = cell.config.get("matmul_precision")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_process)
    for k, v in res["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0
