"""Block-column-skipping delta matvec (the paper's sparse MxV, TPU-native).

EdgeDRNN skips single weight columns per zero delta element. On TPU the
memory system moves 128-lane-aligned tiles HBM->VMEM, so the faithful
adaptation skips *column blocks*: the contraction dim is tiled into
``block_k``-wide blocks; a block in which no delta element fired is never
fetched.

Mechanism: ``pltpu.PrefetchScalarGridSpec`` with two prefetched scalars —
``n_active`` and a compacted list ``active_ids`` of fired k-block indices.
The k grid axis walks ``0..num_k_blocks-1`` but the weight/delta BlockSpecs
index-map through ``active_ids``, so for grid steps ``i < n_active`` the DMA
engine fetches exactly the fired blocks and for ``i >= n_active`` the
(predicated-off) steps re-fetch block 0 and are skipped by ``pl.when`` —
i.e. the HBM traffic is ``(1 - Gamma_block) * bytes(W)``, the Eq. 8 law at
block granularity.

Weight layout: ``w: [O, I]`` (output-major), matching the paper's
concatenated-column DRAM arrangement (Fig. 6) transposed for row-major HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(n_active_ref, active_ids_ref, dx_ref, w_ref, acc_ref, out_ref):
    """One (o-block, k-step) cell: out[B, BO] += dx[B, BK] @ w[BO, BK].T."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    @pl.when(i < n_active_ref[0])
    def _accumulate():
        dx = dx_ref[...]
        w = w_ref[...]
        out_ref[...] += jax.lax.dot_general(
            dx, w, (((1,), (1,)), ((), ())),
            preferred_element_type=out_ref.dtype)


def pack_spmv_weights(w: Array, block_o: int = 128,
                      block_k: int = 128) -> Array:
    """Zero-pad ``w: [O, I]`` to block multiples once, at init time.

    :func:`delta_spmv` re-pads its weight operand on every invocation; on a
    hot path (one call per gate block per timestep) that pad lives inside
    the jitted graph and costs an HBM copy per step. Callers that own the
    weights (the DeltaGRU backends, the streaming engine) pack once and
    pass ``packed=True`` with the true ``out_dim``.
    """
    o_dim, i_dim = w.shape
    return jnp.pad(w, ((0, (-o_dim) % block_o), (0, (-i_dim) % block_k)))


@functools.partial(jax.jit, static_argnames=("block_o", "block_k",
                                             "interpret", "packed", "out_dim"))
def delta_spmv(w: Array, dx: Array, acc: Array | None = None, *,
               block_o: int = 128, block_k: int = 128,
               interpret: bool | None = None, packed: bool = False,
               out_dim: int | None = None) -> Array:
    """``acc + dx @ w.T`` with fired-block-only weight fetch.

    Args:
      w: ``[O, I]`` weights, or the :func:`pack_spmv_weights` layout when
        ``packed=True``.
      dx: ``[B, I]`` delta vectors (zeros = not fired).
      acc: ``[B, O]`` accumulator (delta memory M); zeros if None.
      block_o/block_k: VMEM tile sizes (128-aligned for MXU).
      interpret: run the Pallas body in Python; ``None`` chooses by
        platform (:func:`repro.kernels.ops._interpret_default`).
      packed: weights are already block-padded (skips the per-call pad).
      out_dim: true output dim O when ``packed`` (defaults to ``w.shape[0]``).

    Returns ``[B, O]``.
    """
    from repro.kernels.ops import _interpret_default  # ops imports us
    interpret = _interpret_default(interpret)
    b, i_dim = dx.shape
    o_dim = out_dim if (packed and out_dim is not None) else w.shape[0]
    if acc is None:
        acc = jnp.zeros((b, o_dim), w.dtype)

    # Pad to block multiples (zero-padding is exact for matmul-accumulate).
    o_pad = (-o_dim) % block_o
    k_pad = (-i_dim) % block_k
    w_p = w if packed else jnp.pad(w, ((0, o_pad), (0, k_pad)))
    dx_p = jnp.pad(dx, ((0, 0), (0, k_pad)))
    acc_p = jnp.pad(acc, ((0, 0), (0, o_pad)))
    if packed and w_p.shape[1] != dx_p.shape[1]:
        raise ValueError(
            f"packed weights k-dim {w_p.shape[1]} != padded delta k-dim "
            f"{dx_p.shape[1]}; pack with the same block_k")
    nbo = w_p.shape[0] // block_o
    nbk = w_p.shape[1] // block_k

    # Accumulate across k-blocks in f32 regardless of input dtype (matches
    # the MXU's f32 accumulator and the oracle's single-rounding semantics).
    out_dtype = acc.dtype
    acc_p = acc_p.astype(jnp.float32)

    # Fired-block compaction (host/XLA side — the Delta Unit's job).
    fired = jnp.any(dx_p.reshape(b, nbk, block_k) != 0, axis=(0, 2))  # [nbk]
    n_active = jnp.sum(fired).astype(jnp.int32).reshape((1,))
    active_ids = jnp.nonzero(fired, size=nbk, fill_value=0)[0].astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nbo, nbk),
        in_specs=[
            pl.BlockSpec((b, block_k),
                         lambda o, i, n, ids: (0, ids[i])),       # dx
            pl.BlockSpec((block_o, block_k),
                         lambda o, i, n, ids: (o, ids[i])),       # w
            pl.BlockSpec((b, block_o),
                         lambda o, i, n, ids: (0, o)),            # acc
        ],
        out_specs=pl.BlockSpec((b, block_o),
                               lambda o, i, n, ids: (0, o)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w_p.shape[0]), jnp.float32),
        interpret=interpret,
    )(n_active, active_ids, dx_p, w_p, acc_p)
    return out[:, :o_dim].astype(out_dtype)


def delta_spmv_hbm_bytes(w_shape, dx: Array, block_k: int = 128,
                         weight_bytes: int = 2) -> Array:
    """Model of weight HBM traffic for one call (for the roofline/bench)."""
    o_dim, i_dim = w_shape
    b = dx.shape[0]
    k_pad = (-i_dim) % block_k
    dxp = jnp.pad(dx, ((0, 0), (0, k_pad)))
    nbk = dxp.shape[1] // block_k
    fired = jnp.any(dxp.reshape(b, nbk, block_k) != 0, axis=(0, 2))
    return jnp.sum(fired) * block_k * o_dim * weight_bytes
