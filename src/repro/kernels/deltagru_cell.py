"""Fused DeltaGRU activation pipeline (paper Fig. 7) as a Pallas VPU kernel.

The FPGA executes the post-MxV pointwise chain (sigmoid/tanh LUTs, the
r*M_hc product, the (1-u)c + u h blend) in an 8-stage pipeline that reuses
the PE multipliers by time-division multiplexing. The TPU analogue is a
single fused VPU kernel over the hidden dimension: one HBM read per operand,
one write per result, no intermediate materialization.

Gate layout: wrappers reshape delta memories to ``[B, 4, H]`` (r, u, xc, hc)
and matvec results to ``[B, 3, H]`` (r, u, c) so each grid step sees one
contiguous ``[B, g, block_h]`` tile per operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(m_ref, zx_ref, zh_ref, h_ref, m_out_ref, h_out_ref):
    m = m_ref[...].astype(jnp.float32)     # [B, 4, BH]
    zx = zx_ref[...].astype(jnp.float32)   # [B, 3, BH]
    zh = zh_ref[...].astype(jnp.float32)   # [B, 3, BH]
    h_prev = h_ref[...].astype(jnp.float32)  # [B, BH]

    m_r = m[:, 0] + zx[:, 0] + zh[:, 0]
    m_u = m[:, 1] + zx[:, 1] + zh[:, 1]
    m_xc = m[:, 2] + zx[:, 2]
    m_hc = m[:, 3] + zh[:, 2]

    r = jax.nn.sigmoid(m_r)
    u = jax.nn.sigmoid(m_u)
    c = jnp.tanh(m_xc + r * m_hc)
    h_new = (1.0 - u) * c + u * h_prev

    m_out_ref[...] = jnp.stack([m_r, m_u, m_xc, m_hc], axis=1).astype(m_out_ref.dtype)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def deltagru_act(m_prev: Array, zx: Array, zh: Array, h_prev: Array, *,
                 block_h: int = 128, interpret: bool | None = None):
    """Fused Eq. 3 pointwise update.

    Args:
      m_prev: ``[B, 4H]`` delta memories (r, u, xc, hc).
      zx: ``[B, 3H]`` = W_x @ dx (r, u, c).
      zh: ``[B, 3H]`` = W_h @ dh (r, u, c).
      h_prev: ``[B, H]``.

    Returns ``(m_new: [B, 4H], h_new: [B, H])``.
    """
    from repro.kernels.ops import _interpret_default  # ops imports us
    interpret = _interpret_default(interpret)
    b, four_h = m_prev.shape
    h_dim = four_h // 4
    h_pad = (-h_dim) % block_h
    hp = h_dim + h_pad

    def pad_gates(x, g):
        x = x.reshape(b, g, h_dim)
        return jnp.pad(x, ((0, 0), (0, 0), (0, h_pad)))

    m4 = pad_gates(m_prev, 4)
    zx3 = pad_gates(zx, 3)
    zh3 = pad_gates(zh, 3)
    hprev = jnp.pad(h_prev, ((0, 0), (0, h_pad)))
    nbh = hp // block_h

    m_new, h_new = pl.pallas_call(
        _kernel,
        grid=(nbh,),
        in_specs=[
            pl.BlockSpec((b, 4, block_h), lambda i: (0, 0, i)),
            pl.BlockSpec((b, 3, block_h), lambda i: (0, 0, i)),
            pl.BlockSpec((b, 3, block_h), lambda i: (0, 0, i)),
            pl.BlockSpec((b, block_h), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((b, 4, block_h), lambda i: (0, 0, i)),
            pl.BlockSpec((b, block_h), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
        ],
        interpret=interpret,
    )(m4, zx3, zh3, hprev)
    return m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim]
