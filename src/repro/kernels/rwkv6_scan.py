"""WKV6 recurrence (RWKV-6 "Finch") as a Pallas TPU kernel.

The state ``S: [D, D]`` (key-dim x value-dim, D = head size = 64) lives in a
VMEM scratch buffer and is carried across sequential time-chunk grid steps —
the TPU analogue of EdgeDRNN's on-chip delta/accumulation memories: state
stays on-chip, only the streamed inputs move HBM->VMEM.

Grid: ``(B*H, T // chunk)``; the time axis is the minormost (sequential)
axis so the scratch carry is well-defined. All per-step math is kept 2D
(``[1, D]`` rows, ``[D, D]`` outers) for TPU vector-layout friendliness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_scratch):
    t_chunk = pl.program_id(1)
    n_chunks = pl.num_programs(1)
    tc = r_ref.shape[2]

    @pl.when(t_chunk == 0)
    def _load_state():
        s_scratch[...] = s0_ref[0, 0]

    u_col = u_ref[0].reshape(-1, 1)  # [D, 1]

    def step(i, s):
        r_t = r_ref[0, 0, i, :].reshape(1, -1)   # [1, D]
        k_t = k_ref[0, 0, i, :].reshape(1, -1)
        v_t = v_ref[0, 0, i, :].reshape(1, -1)
        w_t = w_ref[0, 0, i, :].reshape(-1, 1)   # [D, 1] decay per key dim
        kv = k_t.reshape(-1, 1) * v_t            # [D, D] outer(k, v)
        y = jnp.dot(r_t.astype(jnp.float32), s + u_col * kv,
                    preferred_element_type=jnp.float32)  # [1, D]
        y_ref[0, 0, i, :] = y[0].astype(y_ref.dtype)
        return w_t * s + kv

    s = jax.lax.fori_loop(0, tc, step, s_scratch[...])
    s_scratch[...] = s

    @pl.when(t_chunk == n_chunks - 1)
    def _store_state():
        sT_ref[0, 0] = s.astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: Array, k: Array, v: Array, w: Array, u: Array,
               s0: Array | None = None, *, chunk: int = 64,
               interpret: bool | None = None):
    """WKV6 over ``r,k,v,w: [B, H, T, D]`` with bonus ``u: [H, D]``.

    ``w`` is the per-step decay factor in (0, 1). Returns
    ``(y: [B, H, T, D], s_T: [B, H, D, D])``.

    ``interpret=None`` (default) is platform-aware: compiled Pallas on TPU,
    interpret-mode emulation elsewhere — a real device never silently runs
    the interpreter unless explicitly asked to (``interpret=True``).
    """
    from repro.kernels.ops import _interpret_default  # ops imports us
    interpret = _interpret_default(interpret)
    b, h, t, d = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, d, d), jnp.float32)
    t_pad = (-t) % chunk
    if t_pad:
        pad = ((0, 0), (0, 0), (0, t_pad), (0, 0))
        r, k, v = jnp.pad(r, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        w = jnp.pad(w, pad, constant_values=1.0)  # identity decay on padding
    tp = t + t_pad
    bh = b * h
    # [B*H, 1, D]: a (1, D) block spans the last two dims whole
    u_bh = jnp.tile(u.astype(jnp.float32), (b, 1)).reshape(bh, 1, d)

    def flat(x):
        return x.reshape(bh, 1, tp, d)

    y, s_t = pl.pallas_call(
        _kernel,
        grid=(bh, tp // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda bh_, tc: (bh_, 0, tc, 0)),  # r
            pl.BlockSpec((1, 1, chunk, d), lambda bh_, tc: (bh_, 0, tc, 0)),  # k
            pl.BlockSpec((1, 1, chunk, d), lambda bh_, tc: (bh_, 0, tc, 0)),  # v
            pl.BlockSpec((1, 1, chunk, d), lambda bh_, tc: (bh_, 0, tc, 0)),  # w
            pl.BlockSpec((1, 1, d), lambda bh_, tc: (bh_, 0, 0)),             # u
            pl.BlockSpec((1, 1, d, d), lambda bh_, tc: (bh_, 0, 0, 0)),       # s0
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda bh_, tc: (bh_, 0, tc, 0)),  # y
            pl.BlockSpec((1, 1, d, d), lambda bh_, tc: (bh_, 0, 0, 0)),       # sT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, tp, d), r.dtype),
            jax.ShapeDtypeStruct((bh, 1, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(w), u_bh, s0.reshape(bh, 1, d, d))
    y = y.reshape(b, h, tp, d)[:, :, :t]
    return y, s_t.reshape(b, h, d, d)
