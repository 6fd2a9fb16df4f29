"""RG-LRU diagonal recurrence (RecurrentGemma / Griffin) as a Pallas kernel.

``h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t`` — a purely elementwise
recurrence, so the kernel tiles the channel dim across the grid and carries
the ``[1, block_d]`` state in VMEM scratch across sequential time chunks.
This is the perf-critical inner loop of the ``long_500k`` decode cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(x_ref, a_ref, h0_ref, y_ref, hT_ref, h_scratch):
    t_chunk = pl.program_id(2)
    n_chunks = pl.num_programs(2)
    tc = x_ref.shape[1]

    @pl.when(t_chunk == 0)
    def _load():
        h_scratch[...] = h0_ref[...].astype(jnp.float32)

    def step(i, h):
        x_t = x_ref[0, i, :].reshape(1, -1).astype(jnp.float32)
        a_t = a_ref[0, i, :].reshape(1, -1).astype(jnp.float32)
        h = a_t * h + jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 0.0)) * x_t
        y_ref[0, i, :] = h[0].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, tc, step, h_scratch[...])
    h_scratch[...] = h

    @pl.when(t_chunk == n_chunks - 1)
    def _store():
        hT_ref[...] = h.astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rglru_scan(x: Array, a: Array, h0: Array | None = None, *,
               chunk: int = 128, block_d: int = 128,
               interpret: bool | None = None):
    """RG-LRU over ``x, a: [B, T, D]``; returns ``(h_seq: [B,T,D], h_T: [B,D])``.

    ``interpret=None`` (default) is platform-aware: compiled Pallas on TPU,
    interpret-mode emulation elsewhere — a real device never silently runs
    the interpreter unless explicitly asked to (``interpret=True``).
    """
    from repro.kernels.ops import _interpret_default  # ops imports us
    interpret = _interpret_default(interpret)
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), jnp.float32)
    t_pad = (-t) % chunk
    d_pad = (-d) % block_d
    if t_pad or d_pad:
        x = jnp.pad(x, ((0, 0), (0, t_pad), (0, d_pad)))
        # a=1 on time padding keeps the carried state frozen; a=0 on channel
        # padding is harmless (those lanes are dropped).
        a = jnp.pad(a, ((0, 0), (0, t_pad), (0, d_pad)), constant_values=1.0)
        a = a.at[:, :, d:].set(0.0) if d_pad else a
        h0 = jnp.pad(h0, ((0, 0), (0, d_pad)))
    tp, dp = t + t_pad, d + d_pad

    y, h_t = pl.pallas_call(
        _kernel,
        grid=(b, dp // block_d, tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, block_d), lambda bi, di, ti: (bi, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ti: (bi, ti, di)),
            pl.BlockSpec((1, block_d), lambda bi, di, ti: (bi, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, tp, dp), x.dtype),
            jax.ShapeDtypeStruct((b, dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret,
    )(x, a, h0)
    return y[:, :t, :d], h_t[:, :d]
