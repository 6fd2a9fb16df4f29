"""Fused sequence-level DeltaGRU layer kernel (paper Figs. 6 + 7, Eq. 3).

The seed executed one DeltaGRU timestep as *three* device ops — two
``delta_spmv`` calls (input and recurrent gate blocks, each with its own
padding + fired-block compaction) and one activation kernel — plus Python
dispatch per timestep. EdgeDRNN's pipeline does the whole step in one pass:
the Delta Unit encodes, the MxV streams the *concatenated* ``[3H, I+H]``
weight matrix (Fig. 6 column layout) skipping unfired columns, and the
activation stage (Fig. 7) consumes partial sums in place.

This module is the TPU-native analogue, one ``pallas_call`` per layer step:

* delta encode + dual thresholds happen in cheap fused XLA ops (the Delta
  Unit's job — elementwise, activation-sized, never weight-sized);
* input and hidden deltas are concatenated into ONE k-dimension so a single
  fired-block compaction drives a single block-sparse matvec over the
  packed ``[3, Hp, Ip+Hk]`` weight volume — halving the per-step grid
  setup/padding overhead of the two-call scheme;
* the candidate gate's k-blocks route to ``M_xc`` or ``M_hc`` by comparing
  the fired block id against the x/h seam (the seam is block-aligned by
  construction), preserving Eq. 3's split candidate memories;
* the Fig. 7 activation pipeline runs in the same kernel at the final
  k-step, so ``M`` and ``h`` never round-trip to HBM between MxV and
  activation.

The ``lax.scan`` sequence driver
(:func:`repro.core.deltagru.deltagru_sequence` with ``backend="fused"``)
runs whole ``[T, B, I]`` sequences on-device with zero per-step Python
dispatch, packing each layer's layout once outside the scan.

Quantized variant (``backend="fused_q8"``, paper Sec. IV-A + Fig. 6/7)
----------------------------------------------------------------------

The int8 pipeline — block geometry, quantizing packer, code-domain
integer-accumulator kernel, Q8.8/Q1.n LUT activation stage — lives in the
**cell-agnostic core** :mod:`repro.kernels.delta_q8` (it serves the LSTM
family too); this module re-exports the GRU-pinned spellings
(:class:`QuantGruLayout`, :func:`pack_spmv_weights_q8`,
:func:`deltagru_q8_step`, :func:`deltagru_q8_step_ref`) so every
historical import keeps working.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared cell-agnostic core: block geometry, concatenated-column pack,
# per-step Delta-Unit prologue, and the whole int8 pipeline. Names are
# re-exported here for compatibility — new code should import them from
# repro.kernels.delta_q8 directly.
from repro.kernels.delta_q8 import (  # noqa: F401  (re-exports)
    QuantDeltaLayout, _code_dot, _code_einsum, _grid_round,
    _GruBlockGeometry, _prep_step_operands,
    deltagru_q8_step, deltagru_q8_step_ref, pack_cat_volume,
    pack_delta_weights_q4, pack_delta_weights_q8, pack_nibbles,
    unpack_nibbles)

Array = jax.Array

# GRU-pinned alias: gates defaults to 3 on the shared layout, so the
# historical class name keeps meaning exactly what it always did.
QuantGruLayout = QuantDeltaLayout


@dataclass(frozen=True)
class FusedGruLayout(_GruBlockGeometry):
    """One DeltaGRU layer packed for the fused kernel (built once at init).

    ``w`` is ``[3, Hp, Ip + Hk]``: gate-major (r, u, c) rows, hidden dim
    padded to ``block_h``, and the concatenated k-dim = input columns padded
    to ``block_k`` followed by hidden columns padded to ``block_k`` — the
    Fig. 6 concatenated-column layout with a block-aligned x/h seam.

    Registered as a pytree (the weight volume is the only leaf; the
    geometry is static aux data), so layouts ride inside program objects
    across jit boundaries. Closing over one still works — it then rides as
    a jit constant.
    """

    w: Array
    input_size: int
    hidden_size: int
    block_h: int
    block_k: int


jax.tree_util.register_pytree_node(
    FusedGruLayout,
    lambda l: ((l.w,), (l.input_size, l.hidden_size, l.block_h, l.block_k)),
    lambda aux, ch: FusedGruLayout(w=ch[0], input_size=aux[0],
                                   hidden_size=aux[1], block_h=aux[2],
                                   block_k=aux[3]))


def pack_gru_layer(w_x: Array, w_h: Array, block_h: int = 128,
                   block_k: int = 128) -> FusedGruLayout:
    """Pack ``w_x: [3H, I]`` and ``w_h: [3H, H]`` into the fused layout."""
    i_dim, h_dim = w_x.shape[-1], w_h.shape[-1]
    assert w_x.shape[0] == 3 * h_dim and w_h.shape[0] == 3 * h_dim
    return FusedGruLayout(
        w=pack_cat_volume(w_x, w_h, gates=3, block_h=block_h,
                          block_k=block_k),
        input_size=i_dim, hidden_size=h_dim,
        block_h=block_h, block_k=block_k)


def _kernel(n_active_ref, active_ids_ref, d_ref, w_ref, m_ref, h_ref,
            m_out_ref, h_out_ref, acc_ref, *, nbk: int, nbk_x: int):
    """One (o-block, k-step) cell of the fused layer step.

    Accumulates ``d @ w.T`` partials into the four delta memories (the c
    gate splits on the x/h seam) and runs the Fig. 7 activation pipeline at
    the last k-step, all without leaving VMEM.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = m_ref[...].astype(jnp.float32)

    @pl.when(i < n_active_ref[0])
    def _accumulate():
        d = d_ref[...]                               # [B, BK]
        w = w_ref[...]                               # [3, BH, BK]
        p = _code_dot(d, w)
        is_x = active_ids_ref[i] < nbk_x             # block left of the seam?
        acc_ref[:, 0, :] += p[:, 0, :]               # M_r: both streams
        acc_ref[:, 1, :] += p[:, 1, :]               # M_u: both streams
        pc = p[:, 2, :]
        acc_ref[:, 2, :] += jnp.where(is_x, pc, 0.0)   # M_xc: x blocks only
        acc_ref[:, 3, :] += jnp.where(is_x, 0.0, pc)   # M_hc: h blocks only

    @pl.when(i == nbk - 1)
    def _activate():
        m = acc_ref[...]
        h_prev = h_ref[...].astype(jnp.float32)
        r = jax.nn.sigmoid(m[:, 0])
        u = jax.nn.sigmoid(m[:, 1])
        c = jnp.tanh(m[:, 2] + r * m[:, 3])
        h_new = (1.0 - u) * c + u * h_prev
        m_out_ref[...] = m.astype(m_out_ref.dtype)
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "interpret"))
def _fused_step(w: Array, m_prev: Array, h_prev: Array, dx: Array, dh: Array,
                *, input_size: int, hidden_size: int, block_h: int,
                block_k: int, interpret: bool):
    """One fused layer step on already-encoded deltas.

    ``m_prev: [B, 4H]``, ``h_prev: [B, H]``, ``dx: [B, I]``, ``dh: [B, H]``
    -> ``(m_new: [B, 4H], h_new: [B, H])``.
    """
    lay = FusedGruLayout(w, input_size, hidden_size, block_h, block_k)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    d_cat, m4, hprev, n_active, active_ids = _prep_step_operands(
        lay, m_prev, h_prev, dx, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lay.nbo, nbk),
        in_specs=[
            pl.BlockSpec((b, block_k),
                         lambda o, i, n, ids: (0, ids[i])),        # d_cat
            pl.BlockSpec((3, block_h, block_k),
                         lambda o, i, n, ids: (0, o, ids[i])),     # w
            pl.BlockSpec((b, 4, block_h),
                         lambda o, i, n, ids: (0, 0, o)),          # m_prev
            pl.BlockSpec((b, block_h),
                         lambda o, i, n, ids: (0, o)),             # h_prev
        ],
        out_specs=[
            pl.BlockSpec((b, 4, block_h), lambda o, i, n, ids: (0, 0, o)),
            pl.BlockSpec((b, block_h), lambda o, i, n, ids: (0, o)),
        ],
        scratch_shapes=[pltpu.VMEM((b, 4, block_h), jnp.float32)],
    )
    m_new, h_new = pl.pallas_call(
        functools.partial(_kernel, nbk=nbk, nbk_x=lay.nbk_x),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_cat, w, m4, hprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim])


def deltagru_seq_step(layout: FusedGruLayout, m_prev: Array, h_prev: Array,
                      dx: Array, dh: Array, *,
                      interpret: bool | None = None):
    """Public single-step entry on encoded deltas (see :func:`_fused_step`)."""
    from repro.kernels.ops import _interpret_default  # ops imports us
    return _fused_step(layout.w, m_prev, h_prev, dx, dh,
                       input_size=layout.input_size,
                       hidden_size=layout.hidden_size,
                       block_h=layout.block_h, block_k=layout.block_k,
                       interpret=_interpret_default(interpret))


def deltagru_seq_step_ref(layout: FusedGruLayout, m_prev: Array,
                          h_prev: Array, dx: Array, dh: Array):
    """Pure-jnp oracle of the fused step (also the no-Pallas fallback)."""
    b = dx.shape[0]
    h_dim = layout.hidden_size
    w = layout.w.astype(jnp.float32)
    wx = w[:, :h_dim, :layout.input_size]            # [3, H, I]
    wh = w[:, :h_dim, layout.ip:layout.ip + h_dim]   # [3, H, H]
    px = _code_einsum(dx.astype(jnp.float32), wx)
    ph = _code_einsum(dh.astype(jnp.float32), wh)
    m = m_prev.reshape(b, 4, h_dim).astype(jnp.float32)
    m_r = m[:, 0] + px[:, 0] + ph[:, 0]
    m_u = m[:, 1] + px[:, 1] + ph[:, 1]
    m_xc = m[:, 2] + px[:, 2]
    m_hc = m[:, 3] + ph[:, 2]
    r = jax.nn.sigmoid(m_r)
    u = jax.nn.sigmoid(m_u)
    c = jnp.tanh(m_xc + r * m_hc)
    h_new = (1.0 - u) * c + u * h_prev.astype(jnp.float32)
    m_new = jnp.stack([m_r, m_u, m_xc, m_hc], 1).reshape(b, 4 * h_dim)
    return m_new.astype(m_prev.dtype), h_new.astype(h_prev.dtype)


def pack_spmv_weights_q8(w_x: Array, w_h: Array, b: Array | None = None,
                         block_h: int = 128, block_k: int = 128,
                         act_frac_bits: int = 8, act_int_bits: int = 8,
                         lut_frac_bits: int = 4,
                         with_ref_codes: bool | None = None) -> QuantGruLayout:
    """GRU-pinned spelling of the cell-agnostic quantizing packer
    (:func:`repro.kernels.delta_q8.pack_delta_weights_q8` with
    ``gates=3``); kept so the historical GRU export path reads the same."""
    return pack_delta_weights_q8(
        w_x, w_h, b=b, gates=3, block_h=block_h, block_k=block_k,
        act_frac_bits=act_frac_bits, act_int_bits=act_int_bits,
        lut_frac_bits=lut_frac_bits, with_ref_codes=with_ref_codes)


# The lax.scan sequence/stack drivers over these kernels live in
# repro.core.deltagru.deltagru_sequence(backend="fused" | "fused_q8"):
# delta state and firing-stat semantics are shared with the other backends
# there, and the per-layer layouts are packed once outside the scan.
