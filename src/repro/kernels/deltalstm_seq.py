"""Fused sequence-level DeltaLSTM layer kernel (the Fig. 6/7 pipeline on
the LSTM cell family).

The delta-network algorithm originated on LSTM cells (Neil et al. 2017) and
the paper's edge-platform comparison benchmarks an LSTM workload (Table
VII); this kernel brings the LSTM family onto the same single-pass pipeline
as :mod:`repro.kernels.deltagru_seq`:

* one ``pallas_call`` per layer step over a concatenated ``[4H, I+H]``
  weight layout — gate-major (i, f, g, o) rows, input columns then hidden
  columns, each padded to the 128-lane block so the x/h seam is
  block-aligned (the same Fig. 6 concatenated-column DRAM picture, one more
  gate row);
* input and hidden deltas share ONE k-dimension, so a single fired-block
  compaction drives a single block-sparse matvec over the packed
  ``[4, Hp, Ip+Hk]`` volume (reused verbatim from the GRU kernel's
  prologue — the Delta Unit's job is cell-agnostic);
* unlike the GRU, the LSTM's four delta memories ``M_i, M_f, M_g, M_o``
  each accumulate BOTH streams (there is no ``r * M_hc`` split candidate),
  so no seam routing is needed — every fired block adds to all four rows;
* the activation stage (``i = sigma, f = sigma, g = tanh, o = sigma``,
  ``c = f * c_prev + i * g``, ``h = o * tanh(c)``) runs in the same kernel
  at the final k-step, with the cell state ``c`` resident in VMEM — ``M``,
  ``h`` and ``c`` never round-trip to HBM between MxV and activation.

The ``lax.scan`` sequence/stack drivers live in
:func:`repro.core.deltalstm.deltalstm_sequence` (``backend="fused"``),
packing each layer's layout once outside the scan, exactly like the GRU
drivers.

Quantized variant (``backend="fused_q8"``): the int8 4-gate pipeline —
``[4, Hp, Ip+Hk]`` int8 codes, code-domain integer accumulators, Q8.8/Q1.n
LUT activations, saturating Q8.8 cell state — lives in the cell-agnostic
core :mod:`repro.kernels.delta_q8`; this module re-exports the LSTM
spellings (:class:`QuantLstmLayout`, :func:`pack_lstm_weights_q8`,
:func:`deltalstm_q8_step`, :func:`deltalstm_q8_step_ref`).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.delta_q8 import (  # noqa: F401  (re-exports)
    QuantDeltaLayout, _code_dot, _code_einsum, _GruBlockGeometry,
    _prep_step_operands,
    deltalstm_q8_step, deltalstm_q8_step_ref, pack_cat_volume,
    pack_delta_weights_q4, pack_delta_weights_q8, pack_nibbles,
    unpack_nibbles)

Array = jax.Array

# LSTM-pinned alias of the shared quantized layout (``gates=4`` instances;
# see :mod:`repro.kernels.delta_q8` for the int8 pipeline itself).
QuantLstmLayout = QuantDeltaLayout


def pack_lstm_weights_q8(w_x: Array, w_h: Array, b: Array | None = None,
                         block_h: int = 128, block_k: int = 128,
                         act_frac_bits: int = 8, act_int_bits: int = 8,
                         lut_frac_bits: int = 4,
                         with_ref_codes: bool | None = None
                         ) -> QuantDeltaLayout:
    """LSTM spelling of the cell-agnostic quantizing packer
    (:func:`repro.kernels.delta_q8.pack_delta_weights_q8`, ``gates=4``)."""
    return pack_delta_weights_q8(
        w_x, w_h, b=b, gates=4, block_h=block_h, block_k=block_k,
        act_frac_bits=act_frac_bits, act_int_bits=act_int_bits,
        lut_frac_bits=lut_frac_bits, with_ref_codes=with_ref_codes)


@dataclass(frozen=True)
class FusedLstmLayout(_GruBlockGeometry):
    """One DeltaLSTM layer packed for the fused kernel (built once at init).

    ``w`` is ``[4, Hp, Ip + Hk]``: gate-major (i, f, g, o) rows, hidden dim
    padded to ``block_h``, and the concatenated k-dim = input columns padded
    to ``block_k`` followed by hidden columns padded to ``block_k``. Shares
    the block-geometry mixin with :class:`~repro.kernels.deltagru_seq.\
FusedGruLayout`, so the two cells' kernels agree on every seam/pad
    computation by construction.

    Registered as a pytree (the weight volume is the only leaf), so layouts
    ride inside compiled programs across jit boundaries.
    """

    w: Array
    input_size: int
    hidden_size: int
    block_h: int
    block_k: int


jax.tree_util.register_pytree_node(
    FusedLstmLayout,
    lambda l: ((l.w,), (l.input_size, l.hidden_size, l.block_h, l.block_k)),
    lambda aux, ch: FusedLstmLayout(w=ch[0], input_size=aux[0],
                                    hidden_size=aux[1], block_h=aux[2],
                                    block_k=aux[3]))


def pack_lstm_layer(w_x: Array, w_h: Array, block_h: int = 128,
                    block_k: int = 128) -> FusedLstmLayout:
    """Pack ``w_x: [4H, I]`` and ``w_h: [4H, H]`` into the fused layout
    (the same seam/pad arithmetic as the GRU packer, shared via
    :func:`~repro.kernels.delta_q8.pack_cat_volume`)."""
    i_dim, h_dim = w_x.shape[-1], w_h.shape[-1]
    assert w_x.shape[0] == 4 * h_dim and w_h.shape[0] == 4 * h_dim
    return FusedLstmLayout(
        w=pack_cat_volume(w_x, w_h, gates=4, block_h=block_h,
                          block_k=block_k),
        input_size=i_dim, hidden_size=h_dim,
        block_h=block_h, block_k=block_k)


def _lstm_kernel(n_active_ref, active_ids_ref, d_ref, w_ref, m_ref, c_ref,
                 m_out_ref, h_out_ref, c_out_ref, acc_ref, *, nbk: int):
    """One (o-block, k-step) cell of the fused LSTM layer step.

    Accumulates ``d @ w.T`` partials into the four delta memories (every
    fired block feeds all four gates — no candidate split) and runs the
    i/f/g/o + cell-state pipeline at the last k-step, all without leaving
    VMEM. Unlike the GRU kernel there is no ``h_prev`` operand: the LSTM
    update ``h = o * tanh(c)`` reads only the cell state.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = m_ref[...].astype(jnp.float32)

    @pl.when(i < n_active_ref[0])
    def _accumulate():
        d = d_ref[...]                               # [B, BK]
        w = w_ref[...]                               # [4, BH, BK]
        p = _code_dot(d, w)
        acc_ref[...] += p                            # M_i, M_f, M_g, M_o

    @pl.when(i == nbk - 1)
    def _activate():
        m = acc_ref[...]
        c_prev = c_ref[...].astype(jnp.float32)
        gi = jax.nn.sigmoid(m[:, 0])
        gf = jax.nn.sigmoid(m[:, 1])
        gg = jnp.tanh(m[:, 2])
        go = jax.nn.sigmoid(m[:, 3])
        c_new = gf * c_prev + gi * gg
        h_new = go * jnp.tanh(c_new)
        m_out_ref[...] = m.astype(m_out_ref.dtype)
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)
        c_out_ref[...] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "interpret"))
def _fused_lstm_step(w: Array, m_prev: Array, h_prev: Array, c_prev: Array,
                     dx: Array, dh: Array, *, input_size: int,
                     hidden_size: int, block_h: int, block_k: int,
                     interpret: bool):
    """One fused layer step on already-encoded deltas.

    ``m_prev: [B, 4H]``, ``h_prev: [B, H]``, ``c_prev: [B, H]``,
    ``dx: [B, I]``, ``dh: [B, H]``
    -> ``(m_new: [B, 4H], h_new: [B, H], c_new: [B, H])``.
    """
    lay = FusedLstmLayout(w, input_size, hidden_size, block_h, block_k)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    # the shared prologue also pads h_prev; the LSTM activation never
    # reads it (h = o * tanh(c)), so it is simply not handed to the kernel
    d_cat, m4, _, n_active, active_ids = _prep_step_operands(
        lay, m_prev, h_prev, dx, dh)
    cprev = jnp.pad(c_prev, ((0, 0), (0, hp - h_dim)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lay.nbo, nbk),
        in_specs=[
            pl.BlockSpec((b, block_k),
                         lambda o, i, n, ids: (0, ids[i])),        # d_cat
            pl.BlockSpec((4, block_h, block_k),
                         lambda o, i, n, ids: (0, o, ids[i])),     # w
            pl.BlockSpec((b, 4, block_h),
                         lambda o, i, n, ids: (0, 0, o)),          # m_prev
            pl.BlockSpec((b, block_h),
                         lambda o, i, n, ids: (0, o)),             # c_prev
        ],
        out_specs=[
            pl.BlockSpec((b, 4, block_h), lambda o, i, n, ids: (0, 0, o)),
            pl.BlockSpec((b, block_h), lambda o, i, n, ids: (0, o)),
            pl.BlockSpec((b, block_h), lambda o, i, n, ids: (0, o)),
        ],
        scratch_shapes=[pltpu.VMEM((b, 4, block_h), jnp.float32)],
    )
    m_new, h_new, c_new = pl.pallas_call(
        functools.partial(_lstm_kernel, nbk=nbk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), c_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_cat, w, m4, cprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim],
            c_new[:, :h_dim])


def deltalstm_seq_step(layout: FusedLstmLayout, m_prev: Array, h_prev: Array,
                       c_prev: Array, dx: Array, dh: Array, *,
                       interpret: bool | None = None):
    """Public single-step entry on encoded deltas (see
    :func:`_fused_lstm_step`)."""
    from repro.kernels.ops import _interpret_default  # ops imports us
    return _fused_lstm_step(layout.w, m_prev, h_prev, c_prev, dx, dh,
                            input_size=layout.input_size,
                            hidden_size=layout.hidden_size,
                            block_h=layout.block_h, block_k=layout.block_k,
                            interpret=_interpret_default(interpret))


def deltalstm_seq_step_ref(layout: FusedLstmLayout, m_prev: Array,
                           h_prev: Array, c_prev: Array, dx: Array,
                           dh: Array):
    """Pure-jnp oracle of the fused step (also the no-Pallas fallback)."""
    b = dx.shape[0]
    h_dim = layout.hidden_size
    w = layout.w.astype(jnp.float32)
    wx = w[:, :h_dim, :layout.input_size]            # [4, H, I]
    wh = w[:, :h_dim, layout.ip:layout.ip + h_dim]   # [4, H, H]
    px = _code_einsum(dx.astype(jnp.float32), wx)
    ph = _code_einsum(dh.astype(jnp.float32), wh)
    m = m_prev.reshape(b, 4, h_dim).astype(jnp.float32) + px + ph
    gi = jax.nn.sigmoid(m[:, 0])
    gf = jax.nn.sigmoid(m[:, 1])
    gg = jnp.tanh(m[:, 2])
    go = jax.nn.sigmoid(m[:, 3])
    c_new = gf * c_prev.astype(jnp.float32) + gi * gg
    h_new = go * jnp.tanh(c_new)
    return (m.reshape(b, 4 * h_dim).astype(m_prev.dtype),
            h_new.astype(h_prev.dtype), c_new.astype(c_prev.dtype))
