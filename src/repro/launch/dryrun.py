import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

For every (architecture x input-shape) cell this lowers + compiles the real
step function (train_step / prefill_step / serve_step) against the
production mesh with ShapeDtypeStruct inputs — no tensor is ever allocated —
and records:

* ``compiled.memory_analysis()``  — per-device bytes (proves it fits HBM),
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes for §Roofline,
* a collective-bytes sweep over ``compiled.as_text()`` (conventions in
  DESIGN.md §10).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
        --shape train_4k [--multi-pod] [--out .bench_work/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The XLA_FLAGS line above MUST stay the first statement: jax freezes the
device count on first init. Do not import this module from tests.
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import ShapeConfig, ALL_SHAPES
from repro.configs.registry import ARCH_IDS, get_config, grid, shapes_for
from repro.dist.sharding import AxisRules
from repro.kernels import ops as kops
from repro.launch import hlo_cost, specs
from repro.launch.mesh import make_production_mesh
from repro.models.lm import lm_decode, lm_prefill
from repro.train.optim import AdamConfig, warmup_cosine_schedule
from repro.train.trainer import make_lm_train_step_fn

# Pallas-interpret HLO is not meaningfully partitionable at 512 devices; the
# dry-run lowers the jnp reference path (identical math; see kernels.ops).
kops.set_force_ref(True)

DEFAULT_OUT = ".bench_work/dryrun"

# ---------------------------------------------------------------------------
# Collective-bytes accounting (conventions: DESIGN.md §10)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective byte model from the partitioned module."""
    per_op = {op: {"count": 0, "bytes": 0.0} for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        for op in _COLL_OPS:
            marker_plain = f" {op}(" in line
            marker_start = f" {op}-start(" in line
            if not (marker_plain or marker_start):
                continue
            lhs = line.split(f"{op}(")[0] if marker_plain else \
                line.split(f"{op}-start(")[0]
            lhs = lhs.split("=")[-2] if lhs.count("=") > 1 else \
                lhs.split("=")[0]
            # output shape(s) sit between '=' and the op name
            seg = line.split("=", 1)[1]
            seg = seg.split(f"{op}(")[0] if marker_plain else \
                seg.split(f"{op}-start(")[0]
            out_bytes = _shape_bytes(seg)
            m = _GROUPS_RE.search(line)
            if m:
                group_size = int(m.group(2))
            else:
                m2 = _GROUPS_LIST_RE.search(line)
                group_size = len(m2.group(1).split(",")) if m2 else 1
            if op == "all-reduce":
                wire = 2.0 * out_bytes * (group_size - 1) / max(group_size, 1)
            elif op == "all-gather":
                wire = out_bytes * (group_size - 1) / max(group_size, 1)
            elif op == "reduce-scatter":
                wire = out_bytes * (group_size - 1)
            elif op == "all-to-all":
                wire = out_bytes * (group_size - 1) / max(group_size, 1)
            else:  # collective-permute
                wire = out_bytes
            per_op[op]["count"] += 1
            per_op[op]["bytes"] += wire
            break
    total = sum(v["bytes"] for v in per_op.values())
    return {"per_op": per_op, "per_device_bytes": total}


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def _grad_accum_for(cfg, shape, dp_extent: int = 16) -> int:
    """Microbatch so per-device live activations stay within HBM, while the
    microbatch batch dim still covers the DP extent (else activations stop
    sharding and per-device work replicates)."""
    tokens = shape.global_batch * shape.seq_len
    # heuristic: keep ~64k tokens per microbatch globally for d_model>=4096,
    # 256k otherwise; clamp to divisors of global_batch. (Validated against
    # memory_analysis: qwen-32b needs 16 microbatches to sit under 16 GB.)
    target = 65536 if cfg.d_model >= 4096 else 262144
    accum = max(1, min(tokens // target, shape.global_batch // dp_extent))
    while shape.global_batch % accum:
        accum -= 1
    return accum


RULE_VARIANTS = {
    # §Perf hillclimb sharding variants (see EXPERIMENTS.md §Perf)
    "baseline": {},
    # ZeRO-1: params replicated over data (no per-microbatch FSDP gathers);
    # optimizer state + grad accumulator stay data-sharded
    "zero1": {"embed_fsdp": None},
    # serving: expert weights resident in pure-EP layout (no per-step
    # ZeRO gathers on the decode path)
    "ep_resident": {"experts_fsdp": None},
}


def build_lowering(arch: str, shape: ShapeConfig, multi_pod: bool,
                   rules: AxisRules | None = None, grad_accum: int | None = None,
                   variant: str = "baseline"):
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules or AxisRules()
    opt_rules = rules
    if variant != "baseline":
        rules = rules.with_overrides(**RULE_VARIANTS[variant])
    batch_sds = specs.input_specs(cfg, shape)
    b_sh = specs.batch_sharding(batch_sds, mesh, rules)

    # use_mesh installs the (mesh, rules) context so the models' shard()
    # activation constraints are live during tracing — without it they are
    # no-ops and GSPMD propagation alone picks (often bad) shardings.
    from repro.dist.sharding import use_mesh

    if shape.kind == "train":
        dp_extent = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        ga = grad_accum if grad_accum is not None else _grad_accum_for(
            cfg, shape, dp_extent)
        opt = AdamConfig(schedule=warmup_cosine_schedule(3e-4, 100, 10000),
                         weight_decay=0.1)
        step_fn = make_lm_train_step_fn(
            cfg, opt, grad_accum=ga,
            accum_rules=opt_rules if variant == "zero1" else None)
        state_sds = specs.abstract_train_state(cfg)
        st_sh = specs.train_state_sharding(state_sds, mesh, rules,
                                           opt_rules=opt_rules)
        jf = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=(0,))
        with use_mesh(mesh, rules):
            lowered = jf.lower(state_sds, batch_sds)
        meta = {"grad_accum": ga}
    elif shape.kind == "prefill":
        params_sds = specs.abstract_params(cfg)
        p_sh = specs.param_sharding(params_sds, mesh, rules)
        caches_sds = specs.abstract_caches(cfg, shape.global_batch,
                                           shape.seq_len)
        c_sh = specs.cache_sharding(cfg, caches_sds, mesh, rules)

        def prefill_step(params, batch, caches):
            return lm_prefill(params, cfg, batch["tokens"], caches,
                              image_embeds=batch.get("image_embeds"),
                              audio_frames=batch.get("audio_frames"))

        jf = jax.jit(prefill_step, in_shardings=(p_sh, b_sh, c_sh),
                     out_shardings=(specs.logits_sharding(mesh, rules, shape.global_batch, cfg.vocab), c_sh),
                     donate_argnums=(2,))
        with use_mesh(mesh, rules):
            lowered = jf.lower(params_sds, batch_sds, caches_sds)
        meta = {}
    else:  # decode
        params_sds = specs.abstract_params(cfg)
        p_sh = specs.param_sharding(params_sds, mesh, rules)
        caches_sds = specs.abstract_caches(cfg, shape.global_batch,
                                           shape.seq_len)
        c_sh = specs.cache_sharding(cfg, caches_sds, mesh, rules)

        def serve_step(params, batch, caches):
            return lm_decode(params, cfg, batch["token"], caches)

        jf = jax.jit(serve_step, in_shardings=(p_sh, b_sh, c_sh),
                     out_shardings=(specs.logits_sharding(
                         mesh, rules, shape.global_batch, cfg.vocab), c_sh),
                     donate_argnums=(2,))
        with use_mesh(mesh, rules):
            lowered = jf.lower(params_sds, batch_sds, caches_sds)
        meta = {}
    return lowered, mesh, meta


def run_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
             rules: AxisRules | None = None,
             grad_accum: int | None = None,
             variant: str = "baseline") -> dict:
    t0 = time.time()
    mesh_name = "2x16x16" if multi_pod else "16x16"
    record = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
              "kind": shape.kind, "ok": False, "variant": variant}
    try:
        lowered, mesh, meta = build_lowering(arch, shape, multi_pod, rules,
                                             grad_accum, variant)
        record.update(meta)
        record["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 1)

        ma = compiled.memory_analysis()
        hlo_txt = compiled.as_text()
        upcast = hlo_cost.cpu_bf16_upcast_bytes(hlo_txt)
        record["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_device_bytes": int(ma.argument_size_in_bytes
                                     + ma.output_size_in_bytes
                                     + ma.temp_size_in_bytes
                                     - ma.alias_size_in_bytes),
            # f32 copies of bf16 loop operands inserted by XLA:CPU's bf16-
            # dot legalization; absent on TPU (native bf16 MXU inputs).
            "cpu_bf16_upcast_bytes": int(upcast),
        }
        ca = compiled.cost_analysis()
        record["cost"] = {"flops_per_device": float(ca.get("flops", 0.0)),
                          "bytes_per_device": float(ca.get("bytes accessed", 0.0))}
        # trip-count-aware walk (cost_analysis counts loop bodies once)
        walk = hlo_cost.module_costs(hlo_txt)
        record["hlo_walk"] = walk
        record["n_devices"] = int(mesh.size)
        record["ok"] = True
        print(f"  memory_analysis: {ma}")
        print(f"  cost_analysis (loop-body-once): flops={ca.get('flops', 0):.3e}")
        print(f"  hlo_walk: flops/dev={walk['flops_per_device']:.3e} "
              f"hbm/dev={walk['hbm_traffic_core_per_device']:.3e} "
              f"coll/dev={walk['collective_bytes_per_device']:.3e}")
        print(f"  collective counts: "
              f"{ {k: v['count'] for k, v in walk['collectives'].items() if v['count']} }")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    record["total_s"] = round(time.time() - t0, 1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(RULE_VARIANTS))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    cells = []
    for arch, shape, skip in grid():
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape.name != args.shape:
            continue
        cells.append((arch, shape, skip))
    if not cells:
        raise SystemExit("no cells selected")

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    results = []
    for arch, shape, skip in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            suffix = "" if args.variant == "baseline" else f"__{args.variant}"
            fn = os.path.join(args.out,
                              f"{arch}__{shape.name}__{mesh_name}{suffix}.json")
            if args.skip_existing and os.path.exists(fn):
                print(f"[skip existing] {fn}")
                continue
            if skip:
                rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                       "ok": True, "skipped": True, "skip_reason": skip}
                print(f"[SKIP] {arch} x {shape.name}: {skip}")
            else:
                print(f"[cell] {arch} x {shape.name} @ {mesh_name}")
                rec = run_cell(arch, shape, mp, grad_accum=args.grad_accum,
                               variant=args.variant)
                status = "OK" if rec["ok"] else f"FAIL: {rec.get('error')}"
                print(f"  -> {status} ({rec['total_s']}s)")
            with open(fn, "w") as f:
                json.dump(rec, f, indent=1)
            results.append(rec)

    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells OK")
    if n_ok < len(results):
        for r in results:
            if not r["ok"]:
                print(f"  FAIL {r['arch']} x {r['shape']} @ {r['mesh']}: "
                      f"{r.get('error')}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
