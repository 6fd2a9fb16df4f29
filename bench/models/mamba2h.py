"""The Mamba-2 / attention hybrid family (Granite-4.0-H, served by
``core/deltamamba2h.py``): weights from the seed, and the system under
test built from them.

The weights are made on the device in one jitted call, in bfloat16 (the
configuration's weight type), as a dict of stacks: ``embed`` (tied with
the head), ``norm_f``, and ``mamba`` / ``attn`` / ``mlp`` groups whose
arrays lead with their layers (``core/deltamamba2h.py`` names each). The
same arrays are handed to the program, which uses them as they are, and
to the plain reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32
STD = 0.02          # projections and embedding (config "assumed")

# The controls: the plain reference, put in the program's place, with
# per-row int8 weights, with the activation side of every product in one
# bfloat16 part (a plain bfloat16 deployment's rounding, where the
# configuration states three parts), with the SSM state and the conv
# history kept in bfloat16, or with the KV cache in float8 e4m3 — each
# below what the configuration states.
controls = {"int8": {"weight_bits": 8},
            "act_bf16": {"act_parts": 1},
            "state_bf16": {"state_dtype": "bfloat16"},
            "kv_fp8": {"kv_dtype": "float8_e4m3fn"}}

# The CPU rehearsal's limit. On the CPU a batch-1 decode and the
# reference's whole-sequence forward sum their products in another order
# (XLA's matrix-vector and matrix-matrix kernels), so the float32 values
# of a product's input differ in the last place, and the bfloat16 parts
# of the two can round apart: the tiny-width logits part by 1.5e-6 to
# 2.5e-6 of their RMS. Every control reads 8e-4 or more there (state_bf16,
# the weakest, over the first 200 tokens; act_bf16 1.0e-2), and the
# planted faults above 1.
CPU_OUT_GAP = 1e-4


def arch(config: dict) -> dict:
    """The stack's shape from the configuration's published keys."""
    d = config["hidden_size"]
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * dh != config["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * "
                         "hidden_size")
    if config["num_hidden_layers"] != len(config["layer_types"]):
        raise ValueError("num_hidden_layers != len(layer_types)")
    return dict(
        layer_types=tuple(config["layer_types"]), d_model=d,
        vocab=config["vocab_size"], mamba_heads=heads, mamba_head_dim=dh,
        d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
        d_conv=config["mamba_d_conv"],
        attn_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        attn_head_dim=d // config["num_attention_heads"],
        intermediate=config["shared_intermediate_size"],
        max_context=config["max_context"], eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]))


def _sizes(a: dict) -> dict:
    din = a["mamba_heads"] * a["mamba_head_dim"]
    gs = a["n_groups"] * a["d_state"]
    return dict(din=din, conv=din + 2 * gs,
                proj=2 * din + 2 * gs + a["mamba_heads"],
                lm=a["layer_types"].count("mamba"),
                la=a["layer_types"].count("attention"),
                l=len(a["layer_types"]), q=a["attn_heads"] * a["attn_head_dim"],
                kv=a["kv_heads"] * a["attn_head_dim"])


def _normal(key, n: int, shape: tuple):
    """``n`` stacked bfloat16 normal(0, STD) arrays, made one at a time so
    the float32 draw of only one is alive."""
    return jax.lax.map(
        lambda k: (STD * jax.random.normal(k, shape, F32)).astype(BF16),
        jax.random.split(key, n))


@functools.partial(jax.jit, static_argnames=("akey",))
def _weights(key, *, akey: tuple) -> dict:
    """The configuration's ``assumed`` distributions, the ones
    ``repro.core.deltamamba2h.init_mamba2h_params`` draws for the tests,
    made here one stacked array at a time (:func:`_normal`) so that a
    6.4-GB model's float32 draws are never alive at once."""
    a = dict(akey)
    s = _sizes(a)
    d, h, lm = a["d_model"], a["mamba_heads"], s["lm"]
    ks = iter(jax.random.split(key, 12))
    ones = lambda *shape: jnp.ones(shape, BF16)
    dt = jax.random.uniform(next(ks), (lm, h), F32, 1e-3, 0.1)
    return {
        "embed": _normal(next(ks), 1, (a["vocab"], d))[0],
        "norm_f": ones(d),
        "mamba": {
            "norm": ones(lm, d),
            "in_proj": _normal(next(ks), lm, (d, s["proj"])),
            "conv_w": (jax.random.uniform(
                next(ks), (lm, a["d_conv"], s["conv"]), F32, -1.0, 1.0)
                / a["d_conv"] ** 0.5).astype(BF16),
            "conv_b": jnp.zeros((lm, s["conv"]), BF16),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
            "a_log": jnp.log(jax.random.uniform(
                next(ks), (lm, h), F32, 1.0, 16.0)).astype(BF16),
            "d": ones(lm, h),
            "gnorm": ones(lm, s["din"]),
            "out_proj": _normal(next(ks), lm, (s["din"], d))},
        "attn": {
            "norm": ones(s["la"], d),
            "wq": _normal(next(ks), s["la"], (d, s["q"])),
            "wk": _normal(next(ks), s["la"], (d, s["kv"])),
            "wv": _normal(next(ks), s["la"], (d, s["kv"])),
            "wo": _normal(next(ks), s["la"], (s["q"], d))},
        "mlp": {
            "norm": ones(s["l"], d),
            "w_in": _normal(next(ks), s["l"], (d, 2 * a["intermediate"])),
            "w_out": _normal(next(ks), s["l"], (a["intermediate"], d))},
    }


def weights(config: dict, seed: int) -> dict:
    return jax.block_until_ready(_weights(
        jax.random.PRNGKey(seed), akey=tuple(sorted(arch(config).items()))))


def dense_weights(config: dict) -> int:
    """Every weight of the model, the tied embedding once (it is read
    whole by the head): 3,191,396,096 for granite-4.0-h-micro."""
    a = arch(config)
    s = _sizes(a)
    d, h, f = a["d_model"], a["mamba_heads"], a["intermediate"]
    mamba = (d + d * s["proj"] + a["d_conv"] * s["conv"] + s["conv"]
             + 3 * h + s["din"] + s["din"] * d)
    attn = d + d * (s["q"] + 2 * s["kv"]) + s["q"] * d
    mlp = d + 2 * d * f + f * d
    return (a["vocab"] * d + d + s["lm"] * mamba + s["la"] * attn
            + s["l"] * mlp)


def program(config: dict, w: dict):
    """The compiled program of the system under test."""
    from repro.core.deltamamba2h import Mamba2hConfig, mamba2h_params
    from repro.core.program import compile_delta_program
    params = mamba2h_params(Mamba2hConfig(**arch(config)), w)
    return compile_delta_program({"mamba2h": [params]},
                                 backend=config["backend"], cell="mamba2h")


def task(config: dict):
    """The engine's task record. ``GruTaskConfig`` is the task type of
    ``DeltaStreamEngine`` for every family (the rwkv6 and recurrentgemma
    configurations build theirs from it too): one input per frame (a token
    id), the residual width, the depth, and the logits' width."""
    from repro.core.thresholds import ThresholdPolicy
    from repro.models.gru_rnn import GruTaskConfig
    t = GruTaskConfig(1, config["hidden_size"], config["num_hidden_layers"],
                      config["vocab_size"], task="lm",
                      theta_x=config["theta_x"], theta_h=config["theta_h"])
    return t, ThresholdPolicy(config["theta_x"], config["theta_h"])


def frame_spec(config: dict):
    """One stream-frame: one int32 token id."""
    return (1,), np.int32


def reference_kwargs(config: dict) -> dict:
    """What the reference's ``replay`` takes from the configuration."""
    return {"arch": arch(config)}


def tiny(config: dict) -> dict:
    """The configuration at the CPU rehearsal's size: 32 wide, a vocabulary
    of 256, one whole period of layer kinds, and a cache longer than the
    rehearsal's closed loop can fill (4,000 frames/s for 2 s)."""
    return {**config, "hidden_size": 32, "vocab_size": 256,
            "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "shared_intermediate_size": 64, "intermediate_size": 64,
            "layer_types": ["mamba", "attention", "mamba"],
            "num_hidden_layers": 3, "max_context": 8192,
            "limits": {"out_gap": CPU_OUT_GAP}}
