"""§Roofline: three-term analysis per (arch x shape x mesh) from the
dry-run artifacts, plus the DeltaGRU kernel-bench roofline
(:func:`run_deltagru`), which turns the measured bytes-streamed /
effective-GOp/s rows of ``BENCH_deltagru_q8.json`` into arithmetic
intensity and memory/compute-bound terms — the Eq. 8 story at the
backend level (int8 streaming quadruples the arithmetic intensity of
every fired column).

    compute term    = HLO_FLOPs_global / (chips x 197 TFLOP/s)
    memory term     = HLO_bytes_global / (chips x 819 GB/s)
    collective term = collective_bytes_global / (chips x 50 GB/s/link)

All walker numbers are per-device (the artifact's ``hlo_walk``), so the
per-chip division cancels: term = per_device_quantity / per_chip_rate.
MODEL_FLOPS uses the 6ND/2ND conventions on *active* matmul parameters plus
ideal (causally-halved) attention; the MODEL/HLO ratio surfaces remat,
padding, capacity-factor and replication waste. The achieved roofline
fraction is  MODEL_FLOPS_time / dominant_term  (an MFU-style upper bound on
useful utilization for the compiled program).
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs.base import ALL_SHAPES, ModelConfig
from repro.configs.registry import get_config
from repro.core.perf_model import V5E

ART_DIR = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")
OUT_MD = os.path.join(os.path.dirname(__file__), "artifacts", "roofline.md")

LINK_BW = 50e9


def active_matmul_params(cfg: ModelConfig) -> float:
    """Active matmul parameters per token (forward), incl. output head."""
    d = cfg.d_model
    per_layer = {}
    # attention projections
    if cfg.use_mla:
        attn = (d * cfg.n_heads * (cfg.qk_nope + cfg.qk_rope)
                + d * (cfg.kv_lora + cfg.qk_rope)
                + cfg.kv_lora * cfg.n_heads * (cfg.qk_nope + cfg.v_head_dim)
                + cfg.n_heads * cfg.v_head_dim * d)
    else:
        attn = d * cfg.head_dim * (cfg.n_heads + 2 * cfg.n_kv_heads) \
            + cfg.n_heads * cfg.head_dim * d
    ffn = 3 * d * cfg.d_ff
    if cfg.n_experts:
        ffn = cfg.top_k * 3 * d * cfg.expert_d_ff
        if cfg.n_shared_experts:
            ffn += 3 * d * cfg.n_shared_experts * cfg.expert_d_ff
    rwkv = 5 * d * d + d * (5 * 32) + 64 * d + d * cfg.d_ff + cfg.d_ff * d \
        + d * d  # time-mix projections + loras + channel-mix
    rglru = 2 * d * d + 2 * d * d + d * d  # in/gate + rg/ig gates + out

    total = 0.0
    from repro.models.blocks import make_schedule
    for pattern, count in make_schedule(cfg):
        for kind in pattern:
            if kind == "rwkv":
                total += count * rwkv
            elif kind == "rglru":
                total += count * (rglru + 3 * d * cfg.d_ff)
            elif kind == "cross":
                total += count * (2 * attn + 3 * d * cfg.d_ff)
            else:  # attn / local_attn / enc
                total += count * (attn + ffn)
    total += d * cfg.vocab  # output head (tied or not, the matmul runs)
    return total


def encoder_matmul_params(cfg: ModelConfig) -> float:
    """Encoder-side params (run over the audio-frame stream, not text)."""
    if not cfg.encdec:
        return 0.0
    d = cfg.d_model
    attn = d * cfg.head_dim * (cfg.n_heads + 2 * cfg.n_kv_heads) \
        + cfg.n_heads * cfg.head_dim * d
    return cfg.n_encoder_layers * (attn + 3 * d * cfg.d_ff)


def model_flops(cfg: ModelConfig, shape, grad_accum: int = 1) -> float:
    tokens = shape.global_batch * shape.seq_len
    n = active_matmul_params(cfg)
    d = cfg.d_model
    if shape.kind == "decode":
        tokens = shape.global_batch
        # per-step attention over the cache
        attn_ctx = 0.0
        if not cfg.rwkv:
            window = cfg.attn_window or shape.seq_len
            ctx = min(window, shape.seq_len) if cfg.attn_window else shape.seq_len
            attn_ctx = 4.0 * tokens * ctx * cfg.n_heads * cfg.head_dim
        return 2.0 * n * tokens + attn_ctx
    # full-sequence attention flops (causally halved ideal)
    window = cfg.attn_window or shape.seq_len
    ctx = min(window, shape.seq_len)
    attn = 2.0 * tokens * ctx * cfg.n_heads * cfg.head_dim  # scores+pv halved
    if cfg.rwkv:
        attn = 2.0 * tokens * 64 * d  # wkv state updates
    mult = 2.0 if shape.kind == "prefill" else 6.0
    remat = 1.0 if shape.kind == "prefill" else 4.0 / 3.0  # full remat ~ +fwd
    enc = encoder_matmul_params(cfg) * shape.global_batch * cfg.n_audio_frames
    return (mult * n * tokens + mult / 2 * attn + mult / 2 * enc) * remat


def analyse(rec: dict) -> dict | None:
    if rec.get("skipped") or not rec.get("ok"):
        return None
    cfg = get_config(rec["arch"])
    shape = next(s for s in ALL_SHAPES if s.name == rec["shape"])
    w = rec["hlo_walk"]
    chips = rec["n_devices"]
    t_compute = w["flops_per_device"] / V5E.peak_bf16_flops
    t_memory = w["hbm_traffic_core_per_device"] / V5E.hbm_bw
    t_coll = w["collective_bytes_per_device"] / LINK_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])
    mf = model_flops(cfg, shape, rec.get("grad_accum", 1))
    hlo_global = w["flops_per_device"] * chips
    t_model = mf / (chips * V5E.peak_bf16_flops)
    frac = t_model / max(dominant[1], 1e-30)
    hints = {
        "compute": "cut redundant/padded FLOPs (head-count-aware TP, tighter"
                   " capacity factor, less remat recompute)",
        "memory": "raise arithmetic intensity (fuse pointwise chains, wider"
                  " microbatch, keep weights resident across microbatches)",
        "collective": "reduce wire bytes (bf16/compressed grad reduce, "
                      "reduce-scatter instead of all-gather+all-reduce, "
                      "overlap with compute)",
    }
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant[0],
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "model_over_hlo": mf / max(hlo_global, 1e-30),
        "roofline_fraction": frac,
        "peak_device_gb": rec["memory"]["peak_device_bytes"] / 2**30,
        "cpu_upcast_gb": rec["memory"].get("cpu_bf16_upcast_bytes", 0) / 2**30,
        "hint": hints[dominant[0]],
    }


def run(mesh_filter: str = "16x16") -> list[str]:
    rows = []
    for fn in sorted(glob.glob(os.path.join(ART_DIR, "*.json"))):
        rec = json.load(open(fn))
        if mesh_filter and rec.get("mesh") != mesh_filter:
            continue
        a = analyse(rec)
        if a:
            rows.append(a)

    md = ["| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | bound | "
          "MODEL/HLO | roofline frac | GB/dev |",
          "|---|---|---|---|---|---|---|---|---|"]
    lines = []
    for a in rows:
        md.append(
            f"| {a['arch']} | {a['shape']} | {a['t_compute_s']:.3g} | "
            f"{a['t_memory_s']:.3g} | {a['t_collective_s']:.3g} | "
            f"{a['dominant']} | {a['model_over_hlo']:.2f} | "
            f"{a['roofline_fraction']:.2f} | {a['peak_device_gb']:.1f} |")
        lines.append(
            f"roofline.{a['arch']}.{a['shape']},"
            f"{max(a['t_compute_s'], a['t_memory_s'], a['t_collective_s']) * 1e6:.0f},"
            f"bound={a['dominant']} frac={a['roofline_fraction']:.2f} "
            f"model/hlo={a['model_over_hlo']:.2f}")
    os.makedirs(os.path.dirname(OUT_MD), exist_ok=True)
    with open(OUT_MD, "w") as f:
        f.write("\n".join(md) + "\n")
    with open(OUT_MD.replace(".md", ".json"), "w") as f:
        json.dump(rows, f, indent=1)
    return lines


# ---------------------------------------------------------------------------
# DeltaGRU backend roofline from the kernel-bench bytes/GOp/s record
# ---------------------------------------------------------------------------

OUT_DELTAGRU_MD = os.path.join(os.path.dirname(__file__), "artifacts",
                               "roofline_deltagru.md")


def run_deltagru(bench_json: str | None = None,
                 out_md: str | None = None,
                 label: str = "deltagru") -> list[str]:
    """Roofline terms per (backend, theta) from a kernel-bench bytes
    record (``BENCH_deltagru_q8.json`` by default; pass
    ``BENCH_deltalstm_q8.json`` / ``label="deltalstm"`` for the 4-gate
    record — :func:`run_deltalstm` is that spelling).

    arithmetic intensity = nominal Op / streamed weight bytes per step;
    memory term          = bytes / HBM bandwidth (V5E constants);
    compute term         = Op / peak.

    Batch-1 delta-RNN decode is deep in memory-bound territory, so the
    modeled speedup of a backend is ~the reduction in bytes: delta
    skipping divides bytes by 1/(1-Gamma_block), int8 divides them 4x
    again — multiplicative, which is the paper's whole point. The law is
    identical for both cell families; the LSTM's 4-gate volume only moves
    the constants.
    """
    from benchmarks.kernel_bench import BENCH_Q8_JSON
    path = bench_json or BENCH_Q8_JSON
    if not os.path.exists(path):
        return []
    rec = json.load(open(path))
    ops_step = rec["config"]["ops_per_step"]
    md = ["| backend | theta | bytes/step | AI (Op/B) | t_mem (us) | "
          "t_comp (us) | bound | modeled GOp/s | measured GOp/s |",
          "|---|---|---|---|---|---|---|---|---|"]
    lines = []
    for row in rec["rows"]:
        nbytes = row["bytes_per_step"]
        ai = ops_step / max(nbytes, 1e-30)
        t_mem = nbytes / V5E.hbm_bw
        t_comp = ops_step / V5E.peak_bf16_flops
        bound = "memory" if t_mem >= t_comp else "compute"
        modeled = ops_step / max(t_mem, t_comp) / 1e9
        md.append(
            f"| {row['backend']} | {row['theta']} | {nbytes:.0f} | "
            f"{ai:.2f} | {t_mem * 1e6:.3f} | {t_comp * 1e6:.3f} | {bound} | "
            f"{modeled:.1f} | {row['eff_gops']:.2f} |")
        lines.append(
            f"roofline.{label}.{row['backend']}_th{row['theta']},"
            f"{t_mem * 1e6:.2f},AI={ai:.2f} bound={bound} "
            f"modeled_gops={modeled:.1f} measured_gops={row['eff_gops']:.2f}")
    out = out_md or OUT_DELTAGRU_MD
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(md) + "\n")
    return lines


OUT_DELTALSTM_MD = os.path.join(os.path.dirname(__file__), "artifacts",
                                "roofline_deltalstm.md")


def run_deltalstm(bench_json: str | None = None,
                  out_md: str | None = None) -> list[str]:
    """The 4-gate spelling of :func:`run_deltagru`: roofline terms per
    (backend, theta) from ``BENCH_deltalstm_q8.json``."""
    from benchmarks.kernel_bench import BENCH_LSTM_Q8_JSON
    return run_deltagru(bench_json=bench_json or BENCH_LSTM_Q8_JSON,
                        out_md=out_md or OUT_DELTALSTM_MD,
                        label="deltalstm")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run() + run_deltagru() + run_deltalstm()))
