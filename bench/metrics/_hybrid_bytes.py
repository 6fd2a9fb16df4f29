"""The bytes a batch-1 decode tick of a hybrid Mamba-2 / attention LM
needs, counted from the configuration: the algorithm's own traffic, not
an implementation's.

Per tick: every weight once, in bfloat16 (the tied embedding counted once,
as the head that reads it whole; the embedding row a token reads is not
counted again). Per stream served in the tick: the SSM state and the conv
history read and written (float32), the KV cache of the live context read
(keys and values of every attention layer, bfloat16, for the ``kv_len``
positions cached after the tick's write) and the new position written, and
the float32 logits written. At ``kv_len`` 3,000 this is 6.56 GB for
granite-4.0-h-micro.
"""
from __future__ import annotations

import numpy as np

from harness import spec

F32, BF16 = 4, 2


def stream_bytes(config: dict) -> tuple[int, int, int]:
    """``(state, kv per position, logits)`` bytes of one stream: the SSM
    state and conv history (one copy), the keys and values of one cached
    position over all attention layers, and one tick's logits."""
    types = config["layer_types"]
    lm, la = types.count("mamba"), types.count("attention")
    heads, dh = config["mamba_n_heads"], config["mamba_d_head"]
    n, g = config["mamba_d_state"], config["mamba_n_groups"]
    conv_dim = heads * dh + 2 * g * n
    ssm = lm * heads * dh * n * F32
    conv = lm * (config["mamba_d_conv"] - 1) * conv_dim * F32
    hd = config["hidden_size"] // config["num_attention_heads"]
    kv = la * 2 * config["num_key_value_heads"] * hd * BF16
    return ssm + conv, kv, config["vocab_size"] * F32


def tick_bytes(config: dict, kv_lens) -> float:
    """Bytes of one tick serving streams whose caches hold ``kv_lens``
    positions after the tick's write (one entry per stream served)."""
    kv_lens = np.asarray(kv_lens, np.float64)
    if not kv_lens.size:
        return 0.0
    state, kv, logits = stream_bytes(config)
    weights = BF16 * spec.load_family(config).dense_weights(config)
    return float(weights + np.sum(2 * state + kv * kv_lens + kv + logits))
