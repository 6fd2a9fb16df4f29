"""Plain reference of a Granite-4.0-H hybrid language model: the whole
sequence's forward pass, in float32.

Written from the Hugging Face ``GraniteMoeHybrid`` modelling code and
``config.json`` of ibm-granite/granite-4.0-h-micro, in straightforward
``jax.numpy``, and importing nothing of the system under test. Every
product runs in float32 at ``Precision.HIGHEST``; values are rounded
exactly where the configuration's precision block says the program rounds
them: the weights and the cached keys and values are bfloat16, and the
activation side of every product (each projection's input, the head's
input, the attention query) is three bfloat16 parts (each the rounded
remainder of the ones before), each part multiplied and the products
added in order. Per token ``t`` of a stream, with
the layer input ``x``:

* ``x_0 = embedding_multiplier * E[tok]``; each layer
  ``h = x + residual_multiplier * mixer(rms(x))`` and
  ``x' = h + residual_multiplier * W_out(silu(a) * b)``, ``[a, b] = W_in
  rms(h)``; logits ``rms(x_L) E^T / logits_scaling`` (tied embedding).
* Mamba-2 mixer: ``in_proj`` -> ``z``, ``xBC``, ``dt``; ``xBC`` through the
  causal depthwise conv over the last ``d_conv`` inputs (zeros before the
  stream starts), plus bias, SiLU -> ``x``, ``B``, ``C``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
  ``S_t = exp(dt A) S_{t-1} + dt x_t B_t^T``, ``y = S_t C_t + D x_t``, run
  sequentially over the tokens; ``rms_w(y * silu(z))`` over the whole
  inner width (one group); ``out_proj``.
* Attention: ``softmax(attention_multiplier * q k^T)`` over positions
  ``<= t`` (causal, no position embedding), grouped-query heads (query
  head ``j`` reads key/value head ``j // (Hq / Hkv)``), ``o_proj``.

Departures from the HF module, each on purpose: the residual stream, the
norms, ``S`` and the conv history are float32 where HF keeps the model's
dtype (the configuration states float32 for them); the softmax and its
product with the values are float32; the chunked SSD scan of HF's prefill
is replaced by the sequential recurrence it computes; no routed experts
(``num_local_experts`` 0), so only the shared MLP.

The controls, the precisions below the configuration's: ``weight_bits=8``
(every projection and the embedding quantized symmetrically per output
row), ``act_parts=1`` (the activation side of every product in one
bfloat16 part, as a plain bfloat16 deployment rounds it),
``state_dtype="bfloat16"`` (``S`` and the conv history rounded to
bfloat16 as they are stored) and ``kv_dtype="float8_e4m3fn"`` (the cached
keys and values).

:func:`replay` upcasts one layer's weights at a time and makes the logits
in blocks of positions, so that it fits on the chip beside the program's
weights. With ``serve_ticks`` it also returns, per layer and tick, the
union over the streams served of the columns Eq. 2 at theta 0 fires
(``x_t != x_{t-1}`` of the four delta streams: the two norm outputs and
the two inner streams), and their counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
POS_BLOCK = 512         # positions per block of the sequence's own shape
LOGIT_BLOCK = 256       # positions per block of logits
Q_BLOCK = 256           # queries per block of attention scores


# (exponent, mantissa) bits of each narrower type the reference rounds to
_BITS = {"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}


def _as(v, dtype: str):
    """``v`` rounded to ``dtype`` and kept in float32, by
    ``reduce_precision``: a compiler may keep a round trip through the
    narrower type in float32 (excess precision), which would round nothing."""
    if dtype == "float32":
        return v
    e, m = _BITS[dtype]
    return jax.lax.reduce_precision(v, exponent_bits=e, mantissa_bits=m)


def _bf16(v):
    return _as(v, "bfloat16")


def _parts(u, n: int):
    """``u`` as the program carries a product's input: ``n`` bfloat16
    parts, each the remainder of the ones before it, rounded."""
    out = []
    for _ in range(n - 1):
        out.append(_bf16(u))
        u = u - out[-1]
    return out + [_bf16(u)]


def _dot_parts(spec, u, w, n: int):
    """``einsum(spec, part, w)`` of each of ``n`` parts, added in order."""
    ps = [jnp.einsum(spec, p, w, precision=HIGHEST) for p in _parts(u, n)]
    acc = ps[0]
    for p in ps[1:]:
        acc = acc + p
    return acc


def _mm(u, w, n: int):
    """A projection of the input in ``n`` bfloat16 parts, float32 weights."""
    return _dot_parts("...k,kn->...n", u, w, n)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _quant(w, bits: int | None, axis: int):
    """``w`` (float32) with each output row (reduced over ``axis``, the
    input) quantized to ``bits``-bit symmetric codes, or as it is."""
    if bits is None:
        return w
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax, 1.0) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _changed(v):
    """Eq. 2 at theta 0 over a stream ``[T, W]``: a column fires where it
    differs from the previous token's (zero before the first)."""
    prev = jnp.concatenate([jnp.zeros_like(v[:1]), v[:-1]])
    return v != prev


@functools.partial(jax.jit,
                   static_argnames=("a", "bits", "parts", "state_dtype"))
def _mamba(x, lw, *, a, bits, parts, state_dtype):
    """One Mamba layer (mixer and MLP) over ``x [T, D]``."""
    a = dict(a)
    lw = jax.tree_util.tree_map(lambda v: v.astype(F32), lw)
    t = x.shape[0]
    h, p, n, g = (a["mamba_heads"], a["mamba_head_dim"], a["d_state"],
                  a["n_groups"])
    din = h * p
    u = _rms(x, lw["norm"], a["eps"])
    zxd = _mm(u, _quant(lw["in_proj"], bits, 0), parts)
    z, xbc, dt = jnp.split(zxd, [din, din + din + 2 * g * n], axis=-1)
    k = a["d_conv"]
    hist = _as(xbc, state_dtype)      # earlier inputs, as the history holds them
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), hist])
    conv = sum(lw["conv_w"][i] * pad[i:i + t] for i in range(k - 1)) \
        + lw["conv_w"][k - 1] * xbc + lw["conv_b"]
    xbc = jax.nn.silu(conv)
    xs, b, c = jnp.split(xbc, [din, din + g * n], axis=-1)
    rep = h // g
    b = jnp.repeat(b.reshape(t, g, n), rep, axis=1)
    c = jnp.repeat(c.reshape(t, g, n), rep, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a_ = -jnp.exp(lw["a_log"])
    xs = xs.reshape(t, h, p)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * a_)[:, None, None] * s
             + (dt_t[:, None] * x_t)[..., None] * b_t[:, None, :])
        y = jnp.sum(s * c_t[:, None, :], axis=-1) + lw["d"][:, None] * x_t
        return _as(s, state_dtype), y

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), F32), (xs, dt, b, c))
    y = _rms(y.reshape(t, din) * jax.nn.silu(z), lw["gnorm"], a["eps"])
    x = x + a["residual_multiplier"] * _mm(
        y, _quant(lw["out_proj"], bits, 0), parts)
    return _mlp(x, lw["mlp"], a, bits, parts, u, y)


@functools.partial(jax.jit,
                   static_argnames=("a", "bits", "parts", "kv_dtype"))
def _attention(x, lw, *, a, bits, parts, kv_dtype):
    """One attention layer (mixer and MLP) over ``x [T, D]``."""
    a = dict(a)
    lw = jax.tree_util.tree_map(lambda v: v.astype(F32), lw)
    t = x.shape[0]
    hq, hkv, hd = a["attn_heads"], a["kv_heads"], a["attn_head_dim"]
    u = _rms(x, lw["norm"], a["eps"])
    q = _mm(u, _quant(lw["wq"], bits, 0), parts).reshape(t, hkv, hq // hkv,
                                                         hd)
    kk = _as(_mm(u, _quant(lw["wk"], bits, 0), parts),
             kv_dtype).reshape(t, hkv, hd)
    vv = _as(_mm(u, _quant(lw["wv"], bits, 0), parts),
             kv_dtype).reshape(t, hkv, hd)
    blocks = []
    for q0 in range(0, t, Q_BLOCK):
        q_blk = slice(q0, q0 + Q_BLOCK)
        s = _dot_parts("qkgd,tkd->qkgt", q[q_blk], kk, parts) \
            * a["attention_multiplier"]
        seen = jnp.arange(t)[None, :] <= q0 + jnp.arange(s.shape[0])[:, None]
        pr = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf),
                            axis=-1)
        blocks.append(jnp.einsum("qkgt,tkd->qkgd", pr, vv, precision=HIGHEST))
    o = jnp.concatenate(blocks).reshape(t, hq * hd)
    x = x + a["residual_multiplier"] * _mm(o, _quant(lw["wo"], bits, 0),
                                           parts)
    return _mlp(x, lw["mlp"], a, bits, parts, u, o)


def _mlp(x, lw, a, bits, parts, u_mix, y_mix):
    """The layer's MLP; also the layer's four delta streams' firing."""
    u = _rms(x, lw["norm"], a["eps"])
    ab = _mm(u, _quant(lw["w_in"], bits, 0), parts)
    f = ab.shape[-1] // 2
    m = jax.nn.silu(ab[:, :f]) * ab[:, f:]
    x = x + a["residual_multiplier"] * _mm(m, _quant(lw["w_out"], bits, 0),
                                           parts)
    fired = jnp.concatenate([_changed(u_mix), _changed(u), _changed(y_mix),
                             _changed(m)], axis=-1)
    return x, fired


@functools.partial(jax.jit, static_argnames=("a", "bits", "parts"))
def _logits(x, norm_f, embed, *, a, bits, parts):
    a = dict(a)
    u = _rms(x, norm_f.astype(F32), a["eps"])
    e = _quant(embed.astype(F32), bits, 1)
    return _mm(u, e.T, parts) / a["logits_scaling"]


def _take(group: dict, i: int) -> dict:
    return {k: v[i] for k, v in group.items()}


def _forward(w, toks: np.ndarray, a: tuple, bits, parts, state_dtype,
             kv_dtype, keep=None):
    """Logits ``[T, V]`` (numpy) of one stream and, at the positions
    ``keep`` (a device int array), each layer's fired columns ``[K, W_l]``
    (device bool)."""
    ad = dict(a)
    t = len(toks)
    t_pad = -(-t // POS_BLOCK) * POS_BLOCK
    ids = np.concatenate([toks, np.zeros(t_pad - t, toks.dtype)])
    x = ad["embedding_multiplier"] * w["embed"][jnp.asarray(ids)].astype(F32)
    fired = []
    mi = ai = 0
    for li, kind in enumerate(ad["layer_types"]):
        mlp = _take(w["mlp"], li)
        if kind == "mamba":
            lw = {**_take(w["mamba"], mi), "mlp": mlp}
            x, f = _mamba(x, lw, a=a, bits=bits, parts=parts,
                          state_dtype=state_dtype)
            mi += 1
        else:
            lw = {**_take(w["attn"], ai), "mlp": mlp}
            x, f = _attention(x, lw, a=a, bits=bits, parts=parts,
                              kv_dtype=kv_dtype)
            ai += 1
        if keep is not None:
            fired.append(f[keep])
    out = np.empty((t, ad["vocab"]), np.float32)
    for p0 in range(0, t, LOGIT_BLOCK):
        blk = _logits(x[p0:p0 + LOGIT_BLOCK], w["norm_f"], w["embed"], a=a,
                      bits=bits, parts=parts)
        out[p0:p0 + LOGIT_BLOCK] = np.asarray(blk)[:t - p0]
    return out, fired


def replay(w: dict, streams: list, arch: dict, *, weight_bits=None,
           act_parts: int = 3, state_dtype: str = "float32",
           kv_dtype: str = "bfloat16",
           serve_ticks: list | None = None, n_ticks: int = 0):
    """The logits (``[T_s, V]`` numpy per stream) of every stream of token
    ids (``[T_s, 1]`` int32), each from an empty context.

    With ``serve_ticks`` (one int array per stream, the tick in which each
    token was served, or -1) and ``n_ticks``, also return per layer the
    ``[n_ticks, W_l]`` union of fired columns (0/1) and the ``[n_ticks]``
    count of fired stream-columns.
    """
    a = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                     for k, v in arch.items()))
    track = serve_ticks is not None and n_ticks > 0
    outs, union, counts = [], None, None
    with jax.default_matmul_precision("highest"):
        for s, f in enumerate(streams):
            toks = np.asarray(f, np.int32).reshape(-1)
            st = np.asarray(serve_ticks[s]) if track else None
            keep = np.nonzero(st >= 0)[0] if track else None
            y, fired = _forward(w, toks, a, weight_bits, act_parts,
                                state_dtype, kv_dtype, None if keep is None
                                else jnp.asarray(keep))
            outs.append(y)
            if not track:
                continue
            if union is None:
                union = [np.zeros((n_ticks, fl.shape[1]), np.int32)
                         for fl in fired]
                counts = [np.zeros(n_ticks, np.int32) for _ in fired]
            for li, fl in enumerate(fired):
                hit = np.asarray(fl).astype(np.int32)
                np.maximum.at(union[li], st[keep], hit)
                np.add.at(counts[li], st[keep], hit.sum(axis=1))
    if not track:
        return outs
    if union is None:
        union, counts = [], []
    return outs, union, counts
