"""Delta Mamba-2 / attention hybrid — EdgeDRNN's delta trick on a whole
Granite-4.0-H language-model stack, decoded one token per step.

The stack (HF ``GraniteMoeHybrid`` with no routed experts) is a list of
``layer_types``: Mamba-2 mixers and GQA attention with no position
embedding, each followed by a SwiGLU MLP, on a float32 residual stream::

    x0  = embedding_multiplier * E[tok]
    h   = x + residual_multiplier * mixer(rms(x))
    out = h + residual_multiplier * mlp(rms(h)),  mlp(u) = W_out(silu(a) * b)
    logits = rms(x_L) @ E^T / logits_scaling      (tied embedding)

Mamba-2 mixer (one token): ``in_proj`` splits into ``z``, ``xBC`` and
``dt``; ``xBC`` runs through a causal depthwise conv (the ``d_conv - 1``
previous inputs ride in the state) and SiLU and splits into ``x``, ``B``,
``C``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
``S <- exp(dt A) S + dt (x outer B)``, ``y = S C + D x``; then the gated
RMSNorm ``rms_w(y * silu(z))`` over the whole inner width and
``out_proj``. Attention: ``softmax(attention_multiplier * q k^T)`` over
the cached positions up to the current one, then ``o_proj``.

Delta groups (Eq. 2, as :mod:`repro.core.deltarglru` gates its banks):

* **Δx group** (``theta_x``): the two RMSNorm outputs of every layer, the
  one that feeds ``in_proj`` / ``q, k, v`` and the one that feeds the
  MLP's ``W_in``.
* **Δh group** (``theta_h``): the inner streams that feed ``out_proj`` /
  ``o_proj`` and ``W_out``.

Each projection multiplies the *held* stream. At θ = 0 the held stream is
the raw stream bit for bit, so the ``"dense"`` backend's step is bitwise
equal to :func:`mamba2h_decode`, the plain decode, which runs the same
layer code with the gates left out.

Precision: weights and the KV cache in bfloat16; every product's
activation side (projection inputs, the head's input, the attention query)
carried as three bfloat16 parts (:func:`proj`, float32's 24 bits of
mantissa) with float32 sums; the residual stream, the norms, the SSM state ``S``, the
conv history, the attention probabilities and the logits in float32.

State (:class:`Mamba2hState`) is a few stacked arrays per layer kind, each
leading with the stream axis: ``S`` ``[N, Lm, H, P, d_state]``, the conv
history ``[N, Lm, d_conv - 1, conv_dim]``, the KV cache ``[N, La,
max_context, Hkv * hd]`` with its length ``kv_len [N]``, and the held
delta streams. The Mamba layers between two attention layers run as one
``lax.scan`` over their stacked weights, updating the stacked state in
place; attention layers are unrolled. The cache is full attention with no
window: it never wraps, and the serving engine refuses a token past
``max_context``. A step reads only the cache's live chunks.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.backends import BackendSpec, get_backend, register_backend
from repro.core.delta import DeltaState, delta_encode

Array = jax.Array
BF16 = jnp.bfloat16
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"
KV_CHUNK = 2048          # cache positions an attention step reads at a time
_MASKED = -1e30


@dataclass(frozen=True)
class Mamba2hConfig:
    """Static shape of a hybrid stack (HF ``GraniteMoeHybridConfig`` keys
    in brackets)."""

    layer_types: tuple          # [layer_types] "mamba" | "attention"
    d_model: int                # [hidden_size]
    vocab: int                  # [vocab_size]
    mamba_heads: int            # [mamba_n_heads]
    mamba_head_dim: int         # [mamba_d_head]
    d_state: int                # [mamba_d_state]
    n_groups: int               # [mamba_n_groups]
    d_conv: int                 # [mamba_d_conv]
    attn_heads: int             # [num_attention_heads]
    kv_heads: int               # [num_key_value_heads]
    attn_head_dim: int          # hidden_size / num_attention_heads
    intermediate: int           # [shared_intermediate_size]
    max_context: int            # KV cache positions per stream
    eps: float = 1e-5           # [rms_norm_eps]
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0

    def __post_init__(self):
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if self.mamba_heads % self.n_groups or self.attn_heads % self.kv_heads:
            raise ValueError("heads must divide evenly into groups")
        if self.max_context % self.kv_chunk:
            raise ValueError(f"max_context {self.max_context} is not a "
                             f"multiple of {self.kv_chunk}")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        return 2 * self.d_inner + 2 * self.n_groups * self.d_state \
            + self.mamba_heads

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attn(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def kv_chunk(self) -> int:
        return min(KV_CHUNK, self.max_context)

    def runs(self):
        """``(kind, first global layer, first layer of that kind, count)``
        of each run of consecutive layers of one kind."""
        at = {MAMBA: 0, ATTENTION: 0}
        layer = 0
        for kind, grp in itertools.groupby(self.layer_types):
            n = len(list(grp))
            yield kind, layer, at[kind], n
            at[kind] += n
            layer += n


class MambaStack(NamedTuple):
    """Mixer weights of the Mamba layers, stacked ``[Lm, ...]``."""

    norm: Array        # [Lm, D]      input_layernorm
    in_proj: Array     # [Lm, D, in_proj_dim]   Δx group
    conv_w: Array      # [Lm, d_conv, conv_dim] (tap 0 = oldest input)
    conv_b: Array      # [Lm, conv_dim]
    dt_bias: Array     # [Lm, H]
    a_log: Array       # [Lm, H]
    d: Array           # [Lm, H]
    gnorm: Array       # [Lm, d_inner] gated RMSNorm
    out_proj: Array    # [Lm, d_inner, D]       Δh group


class AttnStack(NamedTuple):
    """Mixer weights of the attention layers, stacked ``[La, ...]``."""

    norm: Array        # [La, D]
    wq: Array          # [La, D, Hq * hd]       Δx group
    wk: Array          # [La, D, Hkv * hd]      Δx group
    wv: Array          # [La, D, Hkv * hd]      Δx group
    wo: Array          # [La, Hq * hd, D]       Δh group


class MlpStack(NamedTuple):
    """SwiGLU MLP of every layer, stacked ``[L, ...]``."""

    norm: Array        # [L, D]       post_attention_layernorm
    w_in: Array        # [L, D, 2F]   Δx group; first half gated
    w_out: Array       # [L, F, D]    Δh group


@dataclass(frozen=True)
class Mamba2hParams:
    """The whole stack as one compile-ready "layer": arrays are pytree
    leaves, the :class:`Mamba2hConfig` is static."""

    cfg: Mamba2hConfig
    embed: Array       # [V, D] tied embedding / head
    norm_f: Array      # [D]
    mamba: MambaStack
    attn: AttnStack
    mlp: MlpStack

    @property
    def input_size(self) -> int:
        return 1                    # one token id per frame

    @property
    def hidden_size(self) -> int:
        return self.cfg.d_model


jax.tree_util.register_pytree_node(
    Mamba2hParams,
    lambda p: ((p.embed, p.norm_f, p.mamba, p.attn, p.mlp), p.cfg),
    lambda cfg, ch: Mamba2hParams(cfg, *ch))


def mamba2h_params(cfg: Mamba2hConfig, w: dict) -> Mamba2hParams:
    """Wrap a weights dict (``embed``, ``norm_f`` and the ``mamba`` /
    ``attn`` / ``mlp`` groups keyed by the stacks' field names); the
    arrays are used as they are, not copied."""
    return Mamba2hParams(cfg, w["embed"], w["norm_f"],
                         MambaStack(**w["mamba"]), AttnStack(**w["attn"]),
                         MlpStack(**w["mlp"]))


def init_mamba2h_params(key: Array, cfg: Mamba2hConfig,
                        std: float = 0.02) -> Mamba2hParams:
    """Random bfloat16 weights: normal(std) projections and embedding,
    ``A_log = log U(1, 16)``, ``dt_bias = softplus^-1(U(1e-3, 0.1))``,
    ``D = 1``, norm weights 1, conv weights ``U(-1, 1) / sqrt(d_conv)``
    (PyTorch's depthwise ``Conv1d`` default), conv bias 0. The benchmark's
    ``bench/models/mamba2h.py`` draws the same distributions one stacked
    array at a time, to keep a full-size model's float32 draws small."""
    c = cfg
    lm, la, l, d = c.n_mamba, c.n_attn, c.n_layers, c.d_model
    ks = iter(jax.random.split(key, 12))
    nrm = lambda shape: (std * jax.random.normal(next(ks), shape, F32)
                         ).astype(BF16)
    ones = lambda shape: jnp.ones(shape, BF16)
    dt = jax.random.uniform(next(ks), (lm, c.mamba_heads), F32, 1e-3, 0.1)
    return Mamba2hParams(
        cfg=c, embed=nrm((c.vocab, d)), norm_f=ones((d,)),
        mamba=MambaStack(
            norm=ones((lm, d)), in_proj=nrm((lm, d, c.in_proj_dim)),
            conv_w=(jax.random.uniform(
                next(ks), (lm, c.d_conv, c.conv_dim), F32, -1.0, 1.0)
                / c.d_conv ** 0.5).astype(BF16),
            conv_b=jnp.zeros((lm, c.conv_dim), BF16),
            dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
            a_log=jnp.log(jax.random.uniform(
                next(ks), (lm, c.mamba_heads), F32, 1.0, 16.0)).astype(BF16),
            d=ones((lm, c.mamba_heads)), gnorm=ones((lm, c.d_inner)),
            out_proj=nrm((lm, c.d_inner, d))),
        attn=AttnStack(
            norm=ones((la, d)), wq=nrm((la, d, c.attn_heads * c.attn_head_dim)),
            wk=nrm((la, d, c.kv_heads * c.attn_head_dim)),
            wv=nrm((la, d, c.kv_heads * c.attn_head_dim)),
            wo=nrm((la, c.attn_heads * c.attn_head_dim, d))),
        mlp=MlpStack(norm=ones((l, d)), w_in=nrm((l, d, 2 * c.intermediate)),
                     w_out=nrm((l, c.intermediate, d))))


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

class Mamba2hState(NamedTuple):
    """Per-stream state of the whole stack; every leaf leads with the
    stream axis ``N``."""

    ssm: Array      # [N, Lm, H, P, d_state] f32
    conv: Array     # [N, Lm, d_conv - 1, conv_dim] f32, oldest first
    k: Array        # [N, La, max_context, Hkv * hd] bf16
    v: Array        # [N, La, max_context, Hkv * hd] bf16
    kv_len: Array   # [N] int32 tokens in the cache
    mix_x: Array    # [N, L, D]   held mixer input (Δx group)
    mlp_x: Array    # [N, L, D]   held MLP input (Δx group)
    mamba_h: Array  # [N, Lm, d_inner] held gated-norm output (Δh group)
    attn_h: Array   # [N, La, Hq * hd] held attention output (Δh group)
    mlp_h: Array    # [N, L, F]   held SwiGLU output (Δh group)


def init_mamba2h_stack_state(params, batch_shape=(), dtype=None,
                             m_init: str = "zero") -> Mamba2hState:
    """A fresh state: zeros everywhere, an empty cache. ``dtype`` and
    ``m_init`` are accepted for registry uniformity (the precisions are
    fixed by the family)."""
    del dtype, m_init
    c = _params(params).cfg
    n = tuple(batch_shape)
    z = lambda *shape, dt=F32: jnp.zeros(n + shape, dt)
    kv = (c.n_attn, c.max_context, c.kv_heads * c.attn_head_dim)
    return Mamba2hState(
        ssm=z(c.n_mamba, c.mamba_heads, c.mamba_head_dim, c.d_state),
        conv=z(c.n_mamba, c.d_conv - 1, c.conv_dim),
        k=z(*kv, dt=BF16), v=z(*kv, dt=BF16), kv_len=z(dt=jnp.int32),
        mix_x=z(c.n_layers, c.d_model), mlp_x=z(c.n_layers, c.d_model),
        mamba_h=z(c.n_mamba, c.d_inner),
        attn_h=z(c.n_attn, c.attn_heads * c.attn_head_dim),
        mlp_h=z(c.n_layers, c.intermediate))


def _params(params) -> Mamba2hParams:
    """The params of a one-entry layer stack (as programs hold them)."""
    return params if isinstance(params, Mamba2hParams) else params[0]


# ---------------------------------------------------------------------------
# Layer math (shared by the delta step and the plain decode)
# ---------------------------------------------------------------------------

def rms(x: Array, w: Array, eps: float) -> Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


PARTS = 3       # bfloat16 parts a product's float32 input is carried in


def bf16_parts(u: Array) -> Array:
    """``u`` (float32) as ``PARTS`` bfloat16 parts stacked ``[PARTS,
    ...]`` whose sum keeps float32's 24 bits of mantissa.

    Each part is rounded with ``reduce_precision``, not by a round trip
    through bfloat16: a TPU compiler may keep a fused ``f32 -> bf16 ->
    f32`` round trip in float32 (excess precision), which would make the
    remainder zero and the parts one bfloat16."""
    parts = []
    for _ in range(PARTS - 1):
        hi = jax.lax.reduce_precision(u, exponent_bits=8, mantissa_bits=7)
        parts.append(hi.astype(BF16))
        u = u - hi
    return jnp.stack(parts + [u.astype(BF16)])


def _sum_parts(out: Array) -> Array:
    """The parts' products added in order: ``(p0 + p1) + p2``."""
    acc = out[0]
    for i in range(1, PARTS):
        acc = acc + out[i]
    return acc


def proj(u: Array, w: Array) -> Array:
    """``u [N, K] @ w`` for float32 ``u`` and bfloat16 ``w``: ``u`` as
    bfloat16 parts (:func:`bf16_parts`), all in one product (``PARTS * N``
    rows, which at batch 1 cost what one row does: the weights are read
    once), float32 sums.

    One bfloat16 rounding of every projection input makes the 40-layer
    stack discontinuous: where two correct programs' float32 sums differ
    in the last place, a value near a rounding boundary parts by a
    bfloat16 unit, which moves every later value and parts more roundings;
    their logits then differ by ~1e-1 of the RMS (two parts: ~1e-3),
    measured on a TPU v5e."""
    n = u.shape[0]
    out = jnp.dot(bf16_parts(u).reshape(PARTS * n, -1), w,
                  preferred_element_type=F32)
    return _sum_parts(out.reshape(PARTS, n, -1))


def _at(tree, i, axis: int = 0):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis, keepdims=False),
        tree)


def _put(a: Array, v: Array, i) -> Array:
    """``a[:, i] = v`` (in place on a loop carry)."""
    return jax.lax.dynamic_update_index_in_dim(a, v.astype(a.dtype), i, 1)


def mamba_mix(cfg: Mamba2hConfig, p: MambaStack, ssm: Array, conv: Array,
              u: Array):
    """One Mamba-2 mixer token on the held input ``u [N, D]`` with one
    layer's weights ``p``, state ``ssm [N, H, P, d_state]`` and history
    ``conv [N, d_conv - 1, conv_dim]``. Returns the gated-norm output
    ``[N, d_inner]`` (what ``out_proj`` multiplies), the new ``ssm`` and
    the new history."""
    c = cfg
    n = u.shape[0]
    zxd = proj(u, p.in_proj)
    z, xbc, dt = jnp.split(zxd, [c.d_inner, c.d_inner + c.conv_dim], axis=-1)
    win = jnp.concatenate([conv, xbc[:, None]], axis=1)       # [N, K, C]
    xbc = jax.nn.silu(jnp.sum(win * p.conv_w.astype(F32), axis=1)
                      + p.conv_b.astype(F32))
    gs = c.n_groups * c.d_state
    x, b, cm = jnp.split(xbc, [c.d_inner, c.d_inner + gs], axis=-1)
    rep = c.mamba_heads // c.n_groups
    b = jnp.repeat(b.reshape(n, c.n_groups, c.d_state), rep, axis=1)
    cm = jnp.repeat(cm.reshape(n, c.n_groups, c.d_state), rep, axis=1)
    dt = jax.nn.softplus(dt + p.dt_bias.astype(F32))          # [N, H]
    da = jnp.exp(dt * -jnp.exp(p.a_log.astype(F32)))
    x = x.reshape(n, c.mamba_heads, c.mamba_head_dim)         # [N, H, P]
    ssm = (ssm * da[:, :, None, None]
           + (dt[:, :, None] * x)[..., None] * b[:, :, None, :])
    y = jnp.sum(ssm * cm[:, :, None, :], axis=-1) \
        + p.d.astype(F32)[:, None] * x
    y = y.reshape(n, c.d_inner) * jax.nn.silu(z)
    return rms(y, p.gnorm, c.eps), ssm, win[:, 1:]


def attend(cfg: Mamba2hConfig, q: Array, kc: Array, vc: Array, layer: int,
           n_pos: Array) -> Array:
    """Softmax attention of ``q [N, Hq * hd]`` (f32) over the first
    ``n_pos [N]`` positions of attention layer ``layer`` of the cache
    ``[N, La, T, Hkv * hd]``, reading only the chunks of ``cfg.kv_chunk``
    positions that hold them (an online softmax over chunks, in float32).

    Each chunk is read as it is stored, ``[positions, Hkv * hd]``: the
    query heads are laid block-diagonally over the ``Hkv * hd`` columns
    (zeros off their KV head), so both products contract a whole stored
    row and the cache needs no relayout."""
    c = cfg
    n = q.shape[0]
    g = c.attn_heads // c.kv_heads
    hkv, hd, ch = c.kv_heads, c.attn_head_dim, c.kv_chunk
    eye = jnp.eye(hkv, dtype=BF16)
    qb = jnp.einsum("pnkgd,kl->pnkgld", bf16_parts(q.reshape(n, hkv, g, hd)),
                    eye).reshape(PARTS, n, c.attn_heads, hkv * hd)

    def body(i, carry):
        m, l, acc = carry
        at, size = (0, layer, i * ch, 0), (n, 1, ch, hkv * hd)
        k = jax.lax.dynamic_slice(kc, at, size)[:, 0]
        v = jax.lax.dynamic_slice(vc, at, size)[:, 0]
        s = jnp.einsum("pnhj,ntj->pnht", qb, k, preferred_element_type=F32)
        s = _sum_parts(s) * c.attention_multiplier
        pos = i * ch + jnp.arange(ch)
        s = jnp.where(pos < n_pos[:, None, None], s, _MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(pr, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "nht,ntj->nhj", pr, v.astype(F32), precision=HIGHEST)
        return m_new, l, acc

    h = c.attn_heads
    init = (jnp.full((n, h), _MASKED, F32), jnp.zeros((n, h), F32),
            jnp.zeros((n, h, hkv * hd), F32))
    chunks = (jnp.max(n_pos) + ch - 1) // ch
    _, l, acc = jax.lax.fori_loop(0, chunks, body, init)
    # each query head's own KV head's columns
    o = jnp.sum(acc.reshape(n, hkv, g, hkv, hd)
                * eye.astype(F32)[None, :, None, :, None], axis=3)
    return (o / l.reshape(n, hkv, g)[..., None]).reshape(n, h * hd)


def mlp_inner(w_in: Array, u: Array) -> Array:
    """The SwiGLU's inner stream ``silu(a) * b``, ``[a, b] = u @ w_in``."""
    a, b = jnp.split(proj(u, w_in), 2, axis=-1)
    return jax.nn.silu(a) * b


# ---------------------------------------------------------------------------
# The stack step
# ---------------------------------------------------------------------------

def _gate(theta_x, theta_h):
    """Eq. 2 on a stream against its held memory: ``(held, memory,
    delta)``; with ``theta_x is None`` the plain decode (no gate)."""
    if theta_x is None:
        return lambda s, m, kind: (s, m, None)

    def gate(s, m, kind):
        enc = delta_encode(s, DeltaState(m), theta_x if kind == "x"
                           else theta_h)
        return enc.state.memory, enc.state.memory, enc.delta
    return gate


def _theta(theta, li):
    """A scalar theta, or the entry of a per-layer tuple for the (possibly
    traced) global layer index ``li``."""
    if isinstance(theta, (tuple, list)):
        return jnp.asarray(theta, F32)[li]
    return theta


# the state a run of Mamba layers updates (the cache stays outside its scan)
_SCANNED = ("ssm", "conv", "mix_x", "mamba_h", "mlp_x", "mlp_h")


def _stack(params, state: Mamba2hState, tok: Array, theta_x, theta_h):
    """One token through the stack: ``(y [N, D], state, deltas)``. With
    ``theta_x is None`` no delta gate runs (the plain decode) and
    ``deltas`` is empty."""
    p = _params(params)
    c = p.cfg
    rm = c.residual_multiplier
    plain = theta_x is None
    x = p.embed[tok.reshape(tok.shape[0])].astype(F32) \
        * c.embedding_multiplier                              # [N, D]
    st = state._asdict()
    deltas = []

    def gate_at(li):
        return _gate(None if plain else _theta(theta_x, li),
                     None if plain else _theta(theta_h, li))

    def mlp(x, st, li, gate):
        mp = _at(p.mlp, li)
        u, mx, dx = gate(rms(x, mp.norm, c.eps), st["mlp_x"][:, li], "x")
        m, mh, dh = gate(mlp_inner(mp.w_in, u), st["mlp_h"][:, li], "h")
        x = x + rm * proj(m, mp.w_out)
        return x, {**st, "mlp_x": _put(st["mlp_x"], mx, li),
                   "mlp_h": _put(st["mlp_h"], mh, li)}, dx, dh

    for kind, first, k0, count in c.runs():
        if kind == MAMBA:
            def body(carry, j, first=first, k0=k0):
                x, st = carry
                li, mi = first + j, k0 + j
                gate = gate_at(li)
                mp = _at(p.mamba, mi)
                u, mx, dx1 = gate(rms(x, mp.norm, c.eps),
                                  _at(st["mix_x"], li, 1), "x")
                yn, ssm, conv = mamba_mix(c, mp, _at(st["ssm"], mi, 1),
                                          _at(st["conv"], mi, 1), u)
                yh, mh, dh1 = gate(yn, _at(st["mamba_h"], mi, 1), "h")
                x = x + rm * proj(yh, mp.out_proj)
                st = {**st, "mix_x": _put(st["mix_x"], mx, li),
                      "ssm": _put(st["ssm"], ssm, mi),
                      "conv": _put(st["conv"], conv, mi),
                      "mamba_h": _put(st["mamba_h"], mh, mi)}
                x, st, dx2, dh2 = mlp(x, st, li, gate)
                ds = () if plain else (jnp.concatenate([dx1, dx2], -1),
                                       jnp.concatenate([dh1, dh2], -1))
                return (x, st), ds

            keep = {k: st[k] for k in _SCANNED}
            (x, keep), ds = jax.lax.scan(body, (x, keep), jnp.arange(count))
            st = {**st, **keep}
            if not plain:          # [count, N, W] -> [N, count * W]
                deltas.append(tuple(
                    jnp.moveaxis(d, 0, 1).reshape(d.shape[1], -1)
                    for d in ds))
            continue
        for j in range(count):
            li, ai = first + j, k0 + j
            gate = gate_at(li)
            ap = _at(p.attn, ai)
            u, mx, dx1 = gate(rms(x, ap.norm, c.eps), st["mix_x"][:, li],
                              "x")
            rows = jnp.arange(x.shape[0])
            pos = st["kv_len"]
            kc = st["k"].at[rows, ai, pos].set(proj(u, ap.wk).astype(BF16))
            vc = st["v"].at[rows, ai, pos].set(proj(u, ap.wv).astype(BF16))
            o = attend(c, proj(u, ap.wq), kc, vc, ai,
                       jnp.minimum(pos + 1, c.max_context))
            oh, mh, dh1 = gate(o, st["attn_h"][:, ai], "h")
            x = x + rm * proj(oh, ap.wo)
            st = {**st, "k": kc, "v": vc,
                  "mix_x": _put(st["mix_x"], mx, li),
                  "attn_h": _put(st["attn_h"], mh, ai)}
            x, st, dx2, dh2 = mlp(x, st, li, gate)
            if not plain:
                deltas.append((jnp.concatenate([dx1, dx2], -1),
                               jnp.concatenate([dh1, dh2], -1)))
    st["kv_len"] = jnp.minimum(st["kv_len"] + 1, c.max_context)
    return x, Mamba2hState(**st), deltas


def mamba2h_head(params, y: Array) -> Array:
    """Logits ``[..., V]`` (float32) of the top residual stream ``y``:
    final RMSNorm, the tied embedding, ``/ logits_scaling``.

    The input is in bfloat16 parts, as every projection's (:func:`proj`)."""
    p = _params(params)
    out = jnp.einsum("k...d,vd->k...v",
                     bf16_parts(rms(y, p.norm_f, p.cfg.eps)), p.embed,
                     preferred_element_type=F32)
    return _sum_parts(out) / p.cfg.logits_scaling


def mamba2h_decode(params, state: Mamba2hState, tok: Array):
    """The plain decode: one token ``tok [N, 1]`` (int32) through the
    stack with no delta gate (the held streams are left as they are).
    Returns ``(logits [N, V], state)``."""
    y, state, _ = _stack(params, state, tok, None, None)
    return mamba2h_head(params, y), state


# -- the registered backend ---------------------------------------------------

def _step_dense(params, state, x, theta_x, theta_h, **_kw):
    return _stack(params, state, x, theta_x, theta_h)


def _pack_none(params, block):
    return params, None, None


register_backend(BackendSpec(
    name="dense", cell="mamba2h", pack=_pack_none, step=_step_dense,
    m_init="zero", weight_bits=16, supports_custom_acts=False))


def deltamamba2h_stack_step(params, state: Mamba2hState, x: Array,
                            theta_x, theta_h, backend: str = "dense",
                            layouts=None, packs=None,
                            interpret: bool | None = None):
    """One token ``x [N, 1]`` (int32 ids) through the whole stack.

    Same contract as the other families' stack steps: returns ``(y,
    new_state, [(delta_x, delta_h), ...])`` with ``y [N, D]`` the top
    residual stream (the program's head makes the logits) and one delta
    pair per run of layers of one kind, each ``[N, W]``.
    """
    del layouts, packs, interpret
    return get_backend(backend, cell="mamba2h").step(
        params, state, x, theta_x, theta_h)


def deltamamba2h_sequence(params, xs: Array, theta_x, theta_h,
                          init_state: Mamba2hState | None = None,
                          collect_sparsity: bool = True,
                          backend: str = "dense", layouts=None, packs=None,
                          interpret: bool | None = None):
    """Run the stack over ``xs: [T, B, 1]`` token ids with ``lax.scan``.

    Returns ``(ys [T, B, D], final_state, stats)`` with the
    ``{"gamma_dx", "gamma_dh", "per_layer"}`` stats contract (one entry
    per run of layers of one kind).
    """
    if init_state is None:
        init_state = init_mamba2h_stack_state(params, xs.shape[1:-1])

    def step(state, x):
        y, state, deltas = deltamamba2h_stack_step(
            params, state, x, theta_x, theta_h, backend=backend)
        stats = tuple((jnp.mean((dx == 0).astype(F32)),
                       jnp.mean((dh == 0).astype(F32)))
                      for dx, dh in deltas) if collect_sparsity else ()
        return state, (y, stats)

    final, (ys, stats) = jax.lax.scan(step, init_state, xs)
    if not collect_sparsity:
        return ys, final, {}
    return ys, final, {
        "gamma_dx": jnp.mean(jnp.stack([jnp.mean(s[0]) for s in stats])),
        "gamma_dh": jnp.mean(jnp.stack([jnp.mean(s[1]) for s in stats])),
        "per_layer": stats}


# -- what the program and the serving engine ask of the family ---------------

def frame_ok(params, x: Array) -> Array:
    """A token id is a valid frame component when it is in the vocabulary."""
    return (x >= 0) & (x < _params(params).cfg.vocab)


def max_context(params) -> int:
    return _params(params).cfg.max_context


def watched(state: Mamba2hState):
    """The leaves the engine scans for non-finite values each step: the
    conv history and the held streams. A non-finite ``S`` or cache entry
    reaches the held output stream of its layer in the same step, so the
    large ``S`` and KV arrays need no pass of their own."""
    return (state.conv, state.mix_x, state.mlp_x, state.mamba_h,
            state.attn_h, state.mlp_h)


def proj_volumes(params) -> tuple[int, int]:
    """Δx- and Δh-gated projection weights of the whole stack (Eq. 7)."""
    c = _params(params).cfg
    d, f = c.d_model, c.intermediate
    hd = c.attn_head_dim
    xw = (c.n_mamba * d * c.in_proj_dim
          + c.n_attn * d * (c.attn_heads + 2 * c.kv_heads) * hd
          + c.n_layers * d * 2 * f)
    hw = (c.n_mamba * c.d_inner * d + c.n_attn * c.attn_heads * hd * d
          + c.n_layers * f * d)
    return xw, hw
