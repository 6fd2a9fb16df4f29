"""Public jit'd wrappers for the Pallas kernels.

Each op auto-selects ``interpret`` mode: compiled on TPU, Python-interpreted
on CPU (this container), with a pure-jnp reference fallback available for
backends where even interpretation is unsupported. The `use_ref` escape
hatch also serves lowering paths (e.g. the 512-device dry-run) where we want
plain XLA HLO instead of kernel custom-calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.delta_spmv import delta_spmv as _delta_spmv_pallas
from repro.kernels.delta_spmv import delta_spmv_hbm_bytes  # re-export  # noqa: F401
from repro.kernels.deltagru_cell import deltagru_act as _deltagru_act_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_scan_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6_scan_pallas

Array = jax.Array


_FORCE_REF = False


def set_force_ref(value: bool):
    """Globally route all kernel ops to the jnp reference implementation.

    Used by the dry-run driver: Pallas interpret mode builds per-element
    HLO loops that are meaningless to SPMD-partition at 512 devices; the
    ref path produces the scan/einsum HLO a real TPU run's kernel would be
    measured against."""
    global _FORCE_REF
    _FORCE_REF = value


def _interpret_default(interpret: bool | None = None) -> bool:
    """The one place the Pallas mode is chosen: ``interpret`` as given, or
    for ``None`` compiled kernels on a TPU and the interpreter everywhere
    else. Every kernel entry point takes ``interpret=None`` and resolves
    it here."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def delta_spmv(w: Array, dx: Array, acc: Array | None = None, *,
               block_o: int = 128, block_k: int = 128,
               use_ref: bool = False, interpret: bool | None = None,
               packed: bool = False, out_dim: int | None = None) -> Array:
    """Block-column-skipping ``acc + dx @ w.T`` (paper's sparse MxV).

    ``packed=True`` means ``w`` is already the
    :func:`~repro.kernels.delta_spmv.pack_spmv_weights` block-padded layout
    (skips the per-call pad); ``out_dim`` is then the true output dim.
    """
    if use_ref or _FORCE_REF:
        if packed:
            w = w[:out_dim if out_dim is not None else w.shape[0],
                  :dx.shape[-1]]
        return _ref.delta_spmv_ref(w, dx, acc, block_k=block_k)
    return _delta_spmv_pallas(w, dx, acc, block_o=block_o, block_k=block_k,
                              interpret=interpret, packed=packed,
                              out_dim=out_dim)


def deltagru_act(m_prev: Array, zx: Array, zh: Array, h_prev: Array, *,
                 block_h: int = 128, use_ref: bool = False,
                 interpret: bool | None = None):
    """Fused DeltaGRU pointwise pipeline (paper Fig. 7)."""
    if use_ref or _FORCE_REF:
        return _ref.deltagru_act_ref(m_prev, zx, zh, h_prev)
    return _deltagru_act_pallas(m_prev, zx, zh, h_prev, block_h=block_h,
                                interpret=interpret)


def rwkv6_scan(r: Array, k: Array, v: Array, w: Array, u: Array,
               s0: Array | None = None, *, chunk: int = 64,
               use_ref: bool = False, interpret: bool | None = None):
    """WKV6 linear-attention recurrence over ``[B, H, T, D]``."""
    if use_ref or _FORCE_REF:
        return _ref.rwkv6_scan_batched_ref(r, k, v, w, u, s0)
    return _rwkv6_scan_pallas(r, k, v, w, u, s0, chunk=chunk,
                              interpret=interpret)


def rwkv6_chunked(r: Array, k: Array, v: Array, w: Array, u: Array,
                  s0: Array | None = None, *, chunk: int = 16):
    """Chunk-parallel WKV6 (matmul-form, differentiable, pure jnp).

    The §Perf hillclimb path for RWKV training/prefill: identical math to
    :func:`rwkv6_scan` with O(chunk) arithmetic intensity. Pads T to a
    chunk multiple internally (w=1 freezes decay on padding).
    """
    b, h, t, d = r.shape
    pad = (-t) % chunk
    if pad:
        pd = ((0, 0), (0, 0), (0, pad), (0, 0))
        r, k, v = jnp.pad(r, pd), jnp.pad(k, pd), jnp.pad(v, pd)
        w = jnp.pad(w, pd, constant_values=1.0)
    y, s_t = _ref.rwkv6_chunked_ref(r, k, v, w, u, s0, chunk=chunk)
    return y[:, :, :t], s_t


def rglru_scan(x: Array, a: Array, h0: Array | None = None, *,
               chunk: int = 128, block_d: int = 128, use_ref: bool = False,
               interpret: bool | None = None):
    """RG-LRU diagonal recurrence over ``[B, T, D]``."""
    if use_ref or _FORCE_REF:
        return _ref.rglru_scan_batched_ref(x, a, h0)
    return _rglru_scan_pallas(x, a, h0, chunk=chunk, block_d=block_d,
                              interpret=interpret)


def deltagru_cell_fused(w_x: Array, w_h: Array, m_prev: Array, h_prev: Array,
                        dx: Array, dh: Array, *, use_ref: bool = False,
                        interpret: bool | None = None):
    """Full fused DeltaGRU step: sparse MxV (MXU) + activation pipe (VPU).

    This is the composition the FPGA executes per timestep; on TPU the two
    kernels pipeline back-to-back with the M/h state resident on-chip.
    """
    zx = delta_spmv(w_x, dx, use_ref=use_ref, interpret=interpret)
    zh = delta_spmv(w_h, dh, use_ref=use_ref, interpret=interpret)
    return deltagru_act(m_prev, zx, zh, h_prev, use_ref=use_ref,
                        interpret=interpret)
