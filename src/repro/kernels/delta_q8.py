"""Cell-agnostic quantized delta-kernel core (paper Sec. IV-A, Figs. 6/7).

EdgeDRNN serves every cell family from ONE fixed-point pipeline: the Delta
Unit encodes on the Q8.8 activation grid, the MxV streams an int8 weight
volume from DRAM (skipping unfired columns), the PEs accumulate integer
partial sums, and the activation stage dequantizes + walks the Q8.8-input /
Q1.n-output LUT nonlinearities. Nothing in that pipeline is specific to the
3-gate GRU — only the *routing* of partial sums into delta memories and the
final gate equations differ per cell. This module is that shared core:

* :class:`_GruBlockGeometry` — the Fig. 6 block/pad/seam arithmetic every
  packed layout (fp32 and int8, GRU and LSTM) must agree on;
* :func:`pack_cat_volume` — the concatenated-column ``[G, Hp, Ip+Hk]``
  pack, gate-count-parameterized;
* :func:`_prep_step_operands` — the per-step Delta-Unit prologue (pad,
  x/h concat, single fired-block compaction) shared by every fused kernel;
* :class:`QuantDeltaLayout` — ONE quantized layout for any gate count:
  int8 codes ``[G, Hp, Ip+Hk]``, per-gate-row scales ``[G, Hp]``, the
  activation-grid bias expanded to the four delta memories, and the
  Q8.8/LUT grid constants baked at pack time;
* :func:`pack_delta_weights_q8` — the gate-count-parametric quantizing
  packer (``gates=3`` reproduces the historical GRU pack bit for bit;
  ``gates=4`` is the LSTM volume) — and its int4 sibling
  :func:`pack_delta_weights_q4`, which nibble-packs two codes per byte
  along the row axis (:func:`pack_nibbles`) so the streamed volume is
  half the q8 bytes;
* the int8/int4 Pallas kernels + bit-identical jnp oracles for both
  builtin cells: :func:`deltagru_q8_step` / :func:`deltagru_q8_step_ref`
  (G=3, seam-routed split-candidate memories, Fig. 7 GRU activation) and
  :func:`deltalstm_q8_step` / :func:`deltalstm_q8_step_ref` (G=4, all
  four memories take both streams, i/f/g/o + saturating Q8.8 cell state).
  Both steps dispatch on ``layout.weight_bits`` (8 = int8 codes streamed
  1 byte/element, 4 = nibble-packed codes streamed 0.5 byte/element with
  in-register unpack) and both accept ``buffered=True`` to run the
  double-buffered weight-streaming variant: the weight volume stays in
  HBM (``memory_space=ANY``) and the kernel overlaps the DMA for fired
  block ``k+1`` with the accumulation of block ``k`` through a two-slot
  VMEM scratch + DMA-semaphore pair, bit-identical to the unbuffered
  walk (code-domain sums are exact, and the block order is the same).

Fixed-point semantics (identical for both cells, matching the hardware):

* deltas arrive on the Q8.8 activation grid, so every ``delta x code``
  product is an exact dyadic rational in fp32;
* the delta memories ``M`` carry **unscaled code-domain partial sums**
  (the PE's integer accumulator): all cross-step and cross-block
  additions are exact, which makes the Pallas kernel, the jnp reference
  and any other summation order *bit-identical*;
* the activation stage dequantizes in-register (``b + scale * M``) and
  pushes through the Q8.8-input / Q1.n-output LUT grid of
  :mod:`repro.quant.lut`, rounding new states back onto Q8.8. The LSTM
  cell state ``c`` lives on the (wide) Q8.8 accumulator grid: the
  recurrence ``c = f * c_prev + i * g`` re-rounds onto the grid each
  step and **saturates** at the rails (clip, never wrap) — the int16
  accumulator behaviour of the hardware.

GRU-pinned spellings (``QuantGruLayout``, ``pack_spmv_weights_q8``) are
re-exported from :mod:`repro.kernels.deltagru_seq`, LSTM spellings from
:mod:`repro.kernels.deltalstm_seq`; both are thin aliases of this module.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Delta memories per layer. Both builtin cells carry four: the GRU splits
# its candidate gate across the x/h seam (M_r, M_u, M_xc, M_hc — 3 gate
# rows, 4 memories), the LSTM has one per gate (M_i, M_f, M_g, M_o). The
# shared prologue and the [B, 4H] state convention lean on this.
N_MEM = 4


class _GruBlockGeometry:
    """Shared block geometry of the Fig. 6 concatenated layout.

    Mixin over any layout dataclass carrying ``input_size``,
    ``hidden_size``, ``block_h``, ``block_k`` — the fp32 and int8 packs
    (of every cell family) must agree on this arithmetic or their
    kernels' seams diverge. (The name predates the LSTM family; the
    geometry was always cell-agnostic.)
    """

    @property
    def ip(self) -> int:          # padded input k-extent
        return self.input_size + (-self.input_size) % self.block_k

    @property
    def hk(self) -> int:          # padded hidden k-extent
        return self.hidden_size + (-self.hidden_size) % self.block_k

    @property
    def hp(self) -> int:          # padded hidden (output) extent
        return self.hidden_size + (-self.hidden_size) % self.block_h

    @property
    def nbk_x(self) -> int:
        return self.ip // self.block_k

    @property
    def nbk(self) -> int:
        return (self.ip + self.hk) // self.block_k

    @property
    def nbo(self) -> int:
        return self.hp // self.block_h


def pack_cat_volume(w_x: Array, w_h: Array, gates: int, block_h: int,
                    block_k: int) -> Array:
    """The Fig. 6 concatenated-column pack, gate-count-parameterized.

    ``w_x: [gH, I]``, ``w_h: [gH, H]`` -> ``[g, Hp, Ip + Hk]``: gate-major
    rows, hidden dim padded to ``block_h``, input columns then hidden
    columns each padded to ``block_k`` (block-aligned x/h seam). This is
    the ONE copy of the seam/pad arithmetic every cell's packer must agree
    on — the GRU (g=3) and LSTM (g=4) layouts, fp32 and int8, all call it.
    """
    i_dim, h_dim = w_x.shape[-1], w_h.shape[-1]
    hp = h_dim + (-h_dim) % block_h
    ip = i_dim + (-i_dim) % block_k
    hk = h_dim + (-h_dim) % block_k
    wxg = jnp.pad(w_x.reshape(gates, h_dim, i_dim),
                  ((0, 0), (0, hp - h_dim), (0, ip - i_dim)))
    whg = jnp.pad(w_h.reshape(gates, h_dim, h_dim),
                  ((0, 0), (0, hp - h_dim), (0, hk - h_dim)))
    return jnp.concatenate([wxg, whg], axis=2)


def _prep_step_operands(lay: _GruBlockGeometry, m_prev: Array, h_prev: Array,
                        dx: Array, dh: Array):
    """Shared per-step prologue of every fused kernel: pad the operands to
    the block grid, concatenate the deltas across the x/h seam, and run the
    single fired-block compaction (the Delta Unit's job — elementwise,
    activation-sized, never weight-sized)."""
    b = dx.shape[0]
    h_dim, hp = lay.hidden_size, lay.hp
    d_cat = jnp.concatenate([
        jnp.pad(dx, ((0, 0), (0, lay.ip - lay.input_size))),
        jnp.pad(dh, ((0, 0), (0, lay.hk - h_dim)))], axis=1)
    m4 = jnp.pad(m_prev.reshape(b, N_MEM, h_dim),
                 ((0, 0), (0, 0), (0, hp - h_dim)))
    hprev = jnp.pad(h_prev, ((0, 0), (0, hp - h_dim)))
    fired = jnp.any(d_cat.reshape(b, lay.nbk, lay.block_k) != 0, axis=(0, 2))
    n_active = jnp.sum(fired).astype(jnp.int32).reshape((1,))
    active_ids = jnp.nonzero(fired, size=lay.nbk,
                             fill_value=0)[0].astype(jnp.int32)
    return d_cat, m4, hprev, n_active, active_ids


def pack_nibbles(codes: Array, block_h: int) -> Array:
    """Pack int4 codes (two per byte) along the row (second-to-last) axis.

    The packing is *per row block*: within each ``block_h``-row block,
    packed row ``j`` holds row ``j`` in its low nibble and row
    ``j + block_h//2`` in its high nibble, column by column. A kernel
    weight block of the packed volume is then ``[G, block_h//2, block_k]``
    bytes: the 128-wide k-block, and so the firing granularity, is the
    q8 one, and the lane (last) dimension keeps the TPU's 128 tiling. The
    in-register unpack is a mask/shift plus ONE concatenation along the
    rows. ``codes`` must be int8 values in ``[-8, 7]`` whose row extent is
    a multiple of ``block_h``; returns int8 with half the rows.
    """
    *lead, rows, k = codes.shape
    if rows % block_h or block_h % 2:
        raise ValueError(f"pack_nibbles: row dim {rows} not a multiple of "
                         f"an even block_h={block_h}")
    half = block_h // 2
    c = codes.reshape(*lead, rows // block_h, 2, half, k)
    lo = c[..., 0, :, :].astype(jnp.int32) & 15
    hi = c[..., 1, :, :].astype(jnp.int32) & 15
    return (lo | (hi << 4)).astype(jnp.int8).reshape(*lead, rows // 2, k)


def unpack_nibbles(packed: Array, block_h: int) -> Array:
    """Inverse of :func:`pack_nibbles` (sign-extended via the xor-sub
    trick: ``((n & 15) ^ 8) - 8`` maps the 4-bit two's-complement pattern
    back to ``[-8, 7]``)."""
    *lead, rh, k = packed.shape
    half = block_h // 2
    p = packed.reshape(*lead, rh // half, half, k).astype(jnp.int32)
    lo = ((p & 15) ^ 8) - 8
    hi = (((p >> 4) & 15) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=-2).reshape(
        *lead, 2 * rh, k).astype(jnp.int8)


def _kernel_unpack_nibbles(w):
    """In-register unpack of ONE packed weight block inside a kernel body:
    ``[G, block_h//2, block_k]`` int8 bytes -> ``[G, block_h, block_k]``
    fp32 codes. Valid because every kernel block of the packed volume is
    exactly one row block (see :func:`pack_nibbles`): low nibbles are the
    block's first half-rows, high nibbles the second, so the unpack is one
    concat along the rows."""
    p = w.astype(jnp.int32)
    lo = ((p & 15) ^ 8) - 8
    hi = (((p >> 4) & 15) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=-2).astype(jnp.float32)


def _code_dot(d, w):
    """``[B, BK] x [G, BH, BK] -> [B, G, BH]`` at full fp32 precision.

    Every fused kernel's block product. On a TPU the default f32 matmul
    rounds its operands to bf16, which would round the Q8.8 deltas (up to
    16 significant bits) and break the exact code-domain accumulation of
    the q8/q4 kernels, and move the fp32 kernels off ``dense``;
    ``HIGHEST`` keeps them whole. Off the TPU it changes nothing."""
    return jax.lax.dot_general(d, w, (((1,), (2,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _code_einsum(d, w):
    """The jnp oracles' ``[B, K] x [G, H, K] -> [B, G, H]`` product, at the
    same full precision as :func:`_code_dot`."""
    return jnp.einsum("bi,ghi->bgh", d, w,
                      precision=jax.lax.Precision.HIGHEST)


def _grid_round(v, scale: float, vmin: float, vmax: float):
    """Round onto a Qm.n grid, then clip — the exact op sequence of
    :func:`repro.quant.fake_quant.quantize`, shared by the Pallas kernel
    bodies and the jnp references so all of them round identically.
    The clip is what makes the fixed-point accumulators *saturate* at the
    rails instead of wrapping."""
    q = jnp.round(v * scale) / scale
    return jnp.clip(q, vmin, vmax)


# ---------------------------------------------------------------------------
# The quantized layout (any gate count)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuantDeltaLayout(_GruBlockGeometry):
    """One delta-RNN layer packed for an int8 fused kernel, any gate count.

    ``w_q`` is the Fig. 6 ``[gates, Hp, Ip + Hk]`` volume as **int8 codes**
    (the kernel's HBM operand — 1 byte/element); ``scales: [gates, Hp]``
    holds the per-gate-row symmetric dequant scales; ``b4: [4, Hp]`` is the
    bias quantized onto the activation grid and expanded to the four delta
    memories (GRU: ``b_r, b_u, b_c, 0``; LSTM: ``b_i, b_f, b_g, b_o``) —
    consumed at the activation stage, never accumulated (the M state for
    the q8 backends is the PE's unscaled integer accumulator).
    ``w_codes_f32`` is an optional pre-converted fp32 copy of the codes for
    the off-TPU jnp emulation path, built at pack time so the per-step scan
    body does no int8->f32 conversion.

    ``gates`` is static pytree metadata (3 = GRU, 4 = LSTM): one class
    serves every cell family, so the exporter, the program compiler and the
    serving engine never branch on layout *types*. The activation/LUT grid
    constants (``act_*``, ``lut_*``) are plain Python floats fixed at pack
    time: the jitted steps close over them, adding zero per-timestep host
    work.

    ``weight_bits`` (static, 8 or 4) declares the streamed code width:
    at 8, ``w_q`` is ``[gates, Hp, Ip+Hk]`` int8 codes in ``[-127, 127]``;
    at 4 it is the nibble-packed ``[gates, Hp//2, Ip+Hk]`` volume
    (two codes in ``[-7, 7]`` per byte, rows paired within each
    ``block_h`` block, :func:`pack_nibbles`) — the only
    weight-sized HBM operand then streams half the q8 bytes per fired
    column, and the kernels unpack in-register.
    """

    w_q: Array                  # int8 [gates, Hp, Ip+Hk] (q4: Hp//2 rows)
    scales: Array               # f32  [gates, Hp]
    b4: Array                   # f32  [4, Hp] (activation-grid bias)
    input_size: int
    hidden_size: int
    block_h: int
    block_k: int
    act_scale: float            # Q8.8 grid: 256.0
    act_min: float
    act_max: float
    lut_scale: float            # Q1.n LUT output grid: 2**n
    lut_min: float
    lut_max: float
    w_codes_f32: Array | None = None
    gates: int = 3
    weight_bits: int = 8

    def quantize_act(self, x: Array) -> Array:
        """Round onto the activation (Q8.8) grid — the Delta Unit's input."""
        return _grid_round(x, self.act_scale, self.act_min, self.act_max)

    def dequantized(self):
        """The matching fp32 fused layout carrying the same quantized
        values (:class:`~repro.kernels.deltagru_seq.FusedGruLayout` for
        ``gates=3``, :class:`~repro.kernels.deltalstm_seq.FusedLstmLayout`
        for ``gates=4``)."""
        if self.gates == 3:
            from repro.kernels.deltagru_seq import FusedGruLayout as Lay
        elif self.gates == 4:
            from repro.kernels.deltalstm_seq import FusedLstmLayout as Lay
        else:
            raise ValueError(f"no fused fp32 layout registered for "
                             f"gates={self.gates}")
        w = _layout_codes_f32(self) * self.scales[:, :, None]
        return Lay(w=w, input_size=self.input_size,
                   hidden_size=self.hidden_size,
                   block_h=self.block_h, block_k=self.block_k)


jax.tree_util.register_pytree_node(
    QuantDeltaLayout,
    lambda l: ((l.w_q, l.scales, l.b4, l.w_codes_f32),
               (l.input_size, l.hidden_size, l.block_h, l.block_k,
                l.act_scale, l.act_min, l.act_max,
                l.lut_scale, l.lut_min, l.lut_max, l.gates, l.weight_bits)),
    lambda aux, ch: QuantDeltaLayout(
        w_q=ch[0], scales=ch[1], b4=ch[2], w_codes_f32=ch[3],
        input_size=aux[0], hidden_size=aux[1], block_h=aux[2],
        block_k=aux[3], act_scale=aux[4], act_min=aux[5], act_max=aux[6],
        lut_scale=aux[7], lut_min=aux[8], lut_max=aux[9], gates=aux[10],
        weight_bits=aux[11]))


def pack_delta_weights_q8(w_x: Array, w_h: Array, b: Array | None = None,
                          *, gates: int = 3,
                          block_h: int = 128, block_k: int = 128,
                          act_frac_bits: int = 8, act_int_bits: int = 8,
                          lut_frac_bits: int = 4,
                          with_ref_codes: bool | None = None,
                          weight_bits: int = 8) -> QuantDeltaLayout:
    """Quantize + pack one layer into the int8/int4 Fig. 6 runtime layout.

    Gate-count-parametric: ``w_x: [gH, I]``, ``w_h: [gH, H]`` with
    ``g = gates``. Per-gate-row symmetric quantization:
    ``scale[g, o] = absmax(w[g, o, :]) / qmax`` over the concatenated
    (x then h) row, codes clipped to ``[-qmax, qmax]`` so the grid is
    symmetric (``qmax = 127`` at 8 bits, ``7`` at 4 bits — the int4 grid
    drops the ``-8`` pattern to stay symmetric, exactly like int8 drops
    ``-128``). Rows that are entirely zero (including Hp padding rows)
    get scale ``1/qmax`` and all-zero codes. At ``weight_bits=4`` the
    stored ``w_q`` is the nibble-packed half-height volume
    (:func:`pack_nibbles`).

    The bias rows are quantized onto the activation grid and expanded to
    the four delta memories: gate rows first, zero rows after — for the
    GRU (g=3) this is exactly the ``(b_r, b_u, b_c, 0)`` split-candidate
    convention; for the LSTM (g=4) it is one bias row per gate.

    ``with_ref_codes=None`` auto-builds the fp32 code copy off-TPU only
    (the jnp emulation path needs it hoisted out of the scan; a TPU run
    streams the packed volume directly and never materializes it).
    """
    if weight_bits not in (4, 8):
        raise ValueError(
            f"weight_bits must be 4 or 8, got {weight_bits!r} — the packed "
            f"delta pipeline defines only the int8 and nibble-packed int4 "
            f"code grids")
    gh, i_dim = w_x.shape
    h_dim = w_h.shape[-1]
    if gh != gates * h_dim or w_h.shape[0] != gates * h_dim:
        raise ValueError(
            f"pack_delta_weights_q8(gates={gates}) expects w_x [{gates}H, I]"
            f" / w_h [{gates}H, H]; got w_x {tuple(w_x.shape)}, w_h "
            f"{tuple(w_h.shape)} (hidden={h_dim}) — wrong cell family?")
    hp = h_dim + (-h_dim) % block_h
    w3 = pack_cat_volume(w_x.astype(jnp.float32), w_h.astype(jnp.float32),
                         gates, block_h, block_k)      # [g, Hp, Ip+Hk]
    qmax = 127.0 if weight_bits == 8 else 7.0
    absmax = jnp.max(jnp.abs(w3), axis=2)              # [g, Hp]
    scales = jnp.where(absmax > 0, absmax, 1.0) / qmax
    codes = jnp.clip(jnp.round(w3 / scales[:, :, None]), -qmax, qmax)
    if weight_bits == 8:
        w_q = codes.astype(jnp.int8)
    else:
        w_q = pack_nibbles(codes.astype(jnp.int8), block_h)

    act_scale = float(2 ** act_frac_bits)
    act_min = -float(2 ** act_int_bits)
    act_max = float(2 ** act_int_bits) - 1.0 / act_scale
    lut_scale = float(2 ** lut_frac_bits)
    lut_min, lut_max = -2.0, 2.0 - 1.0 / lut_scale     # Q1.n output grid

    if b is None:
        b4 = jnp.zeros((N_MEM, hp), jnp.float32)
    else:
        bg = b.astype(jnp.float32).reshape(gates, h_dim)
        bg = jnp.clip(jnp.round(bg * act_scale) / act_scale, act_min, act_max)
        b4 = jnp.pad(bg, ((0, N_MEM - gates), (0, hp - h_dim)))
    if with_ref_codes is None:
        from repro.kernels.ops import _interpret_default  # ops imports us
        with_ref_codes = _interpret_default()
    return QuantDeltaLayout(
        w_q=w_q, scales=scales, b4=b4, input_size=i_dim, hidden_size=h_dim,
        block_h=block_h, block_k=block_k,
        act_scale=act_scale, act_min=act_min, act_max=act_max,
        lut_scale=lut_scale, lut_min=lut_min, lut_max=lut_max,
        w_codes_f32=codes if with_ref_codes else None, gates=gates,
        weight_bits=weight_bits)


def pack_delta_weights_q4(w_x: Array, w_h: Array, b: Array | None = None,
                          **kw) -> QuantDeltaLayout:
    """The int4 spelling of :func:`pack_delta_weights_q8`: codes in
    ``[-7, 7]``, scale ``absmax/7``, nibble-packed ``w_q`` streaming half
    the q8 bytes per fired column."""
    return pack_delta_weights_q8(w_x, w_h, b, weight_bits=4, **kw)


def _layout_codes_f32(layout: QuantDeltaLayout) -> Array:
    """The full (unpacked) fp32 code volume of a layout, any width."""
    if layout.w_codes_f32 is not None:
        return layout.w_codes_f32
    if layout.weight_bits == 4:
        return unpack_nibbles(layout.w_q, layout.block_h).astype(jnp.float32)
    return layout.w_q.astype(jnp.float32)


def _ref_code_slices(layout: QuantDeltaLayout):
    """fp32 code views of the x / h column ranges for the jnp oracles."""
    h_dim = layout.hidden_size
    codes = _layout_codes_f32(layout)
    cx = codes[:, :h_dim, :layout.input_size]             # [g, H, I]
    ch = codes[:, :h_dim, layout.ip:layout.ip + h_dim]    # [g, H, H]
    return cx, ch


# ---------------------------------------------------------------------------
# GRU instantiation (gates=3, seam-routed split-candidate memories)
# ---------------------------------------------------------------------------

def _q8_gru_kernel(n_active_ref, active_ids_ref, d_ref, w_ref, s_ref, b_ref,
                   m_ref, h_ref, m_out_ref, h_out_ref, acc_ref, *, nbk: int,
                   nbk_x: int, weight_bits: int, act_scale: float,
                   act_min: float, act_max: float, lut_scale: float,
                   lut_min: float, lut_max: float):
    """One (o-block, k-step) cell of the int8/int4 fused GRU layer step.

    ``w_ref`` holds packed codes (the only weight-sized HBM operand); they
    are widened to fp32 in-register and the raw ``delta x code`` products
    accumulate *unscaled* (the PE's integer accumulator — every addition
    is exact for on-grid deltas). At ``weight_bits=4`` each weight block
    is one nibble-packed row block (``block_h//2`` rows of bytes)
    unpacked in-register before the dot. The candidate gate's partials route to
    ``M_xc`` / ``M_hc`` on the x/h seam. The final k-step dequantizes
    (``b + scale * acc``) and runs the Fig. 7 pipeline on the Q8.8-input /
    Q1.n-output LUT grids, rounding the new ``h`` back onto Q8.8.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_active_ref[0])
    def _accumulate():
        d = d_ref[...]                               # [B, BK] on the Q8.8 grid
        if weight_bits == 4:
            w = _kernel_unpack_nibbles(w_ref[...])   # nibbles -> f32 codes
        else:
            w = w_ref[...].astype(jnp.float32)       # int8 codes -> f32
        p = _code_dot(d, w)
        is_x = active_ids_ref[i] < nbk_x
        acc_ref[:, 0, :] += p[:, 0, :]               # M_r codes
        acc_ref[:, 1, :] += p[:, 1, :]               # M_u codes
        pc = p[:, 2, :]
        acc_ref[:, 2, :] += jnp.where(is_x, pc, 0.0)   # M_xc codes
        acc_ref[:, 3, :] += jnp.where(is_x, 0.0, pc)   # M_hc codes

    @pl.when(i == nbk - 1)
    def _activate():
        def q88(v):
            return _grid_round(v, act_scale, act_min, act_max)

        def lut(v):
            return _grid_round(v, lut_scale, lut_min, lut_max)

        m_new = m_ref[...].astype(jnp.float32) + acc_ref[...]  # code domain
        s = s_ref[...].astype(jnp.float32)                     # [3, BH]
        s4 = jnp.concatenate([s, s[2:3]], axis=0)              # c scale x2
        msc = b_ref[...][None] + m_new * s4[None]              # dequantized
        h_prev = h_ref[...].astype(jnp.float32)
        r = lut(jax.nn.sigmoid(q88(msc[:, 0])))
        u = lut(jax.nn.sigmoid(q88(msc[:, 1])))
        c = lut(jnp.tanh(q88(msc[:, 2] + r * msc[:, 3])))
        h_new = q88((1.0 - u) * c + u * h_prev)
        m_out_ref[...] = m_new.astype(m_out_ref.dtype)
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "act_scale",
    "act_min", "act_max", "lut_scale", "lut_min", "lut_max", "weight_bits",
    "interpret"))
def _fused_q8_step(w_q: Array, scales: Array, b4: Array, m_prev: Array,
                   h_prev: Array, dx: Array, dh: Array, *, input_size: int,
                   hidden_size: int, block_h: int, block_k: int,
                   act_scale: float, act_min: float, act_max: float,
                   lut_scale: float, lut_min: float, lut_max: float,
                   weight_bits: int, interpret: bool):
    """One int8/int4 fused GRU layer step on already-encoded deltas.

    ``m_prev: [B, 4H]`` (code-domain accumulator), ``h_prev: [B, H]``,
    ``dx: [B, I]``, ``dh: [B, H]`` -> ``(m_new: [B, 4H], h_new: [B, H])``.
    """
    lay = QuantDeltaLayout(w_q, scales, b4, input_size, hidden_size, block_h,
                           block_k, act_scale, act_min, act_max, lut_scale,
                           lut_min, lut_max, gates=3)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    # a packed q4 row block is half as tall in bytes; the block index map
    # is identical (BlockSpec indices count blocks, not elements)
    wbh = block_h // 2 if weight_bits == 4 else block_h
    d_cat, m4, hprev, n_active, active_ids = _prep_step_operands(
        lay, m_prev, h_prev, dx, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lay.nbo, nbk),
        in_specs=[
            pl.BlockSpec((b, block_k),
                         lambda o, i, n, ids: (0, ids[i])),        # d_cat
            pl.BlockSpec((3, wbh, block_k),
                         lambda o, i, n, ids: (0, o, ids[i])),     # w_q packed
            pl.BlockSpec((3, block_h),
                         lambda o, i, n, ids: (0, o)),             # scales
            pl.BlockSpec((4, block_h),
                         lambda o, i, n, ids: (0, o)),             # b4
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
        functools.partial(_q8_gru_kernel, nbk=nbk, nbk_x=lay.nbk_x,
                          weight_bits=weight_bits,
                          act_scale=act_scale, act_min=act_min,
                          act_max=act_max, lut_scale=lut_scale,
                          lut_min=lut_min, lut_max=lut_max),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_cat, w_q, scales, b4, m4, hprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim])


def deltagru_q8_step(layout: QuantDeltaLayout, m_prev: Array, h_prev: Array,
                     dx: Array, dh: Array, *, interpret: bool | None = None,
                     buffered: bool = False):
    """Public int8/int4 GRU single-step entry on encoded deltas (see
    :func:`_fused_q8_step`; ``buffered=True`` runs the double-buffered
    weight-streaming variant :func:`_fused_q8_step_dbuf` — bit-identical
    output, weights DMA'd from HBM with a two-slot overlap)."""
    step = _fused_q8_step_dbuf if buffered else _fused_q8_step
    from repro.kernels.ops import _interpret_default  # ops imports us
    return step(layout.w_q, layout.scales, layout.b4, m_prev,
                h_prev, dx, dh, input_size=layout.input_size,
                hidden_size=layout.hidden_size,
                block_h=layout.block_h, block_k=layout.block_k,
                act_scale=layout.act_scale, act_min=layout.act_min,
                act_max=layout.act_max, lut_scale=layout.lut_scale,
                lut_min=layout.lut_min, lut_max=layout.lut_max,
                weight_bits=layout.weight_bits,
                interpret=_interpret_default(interpret))


def deltagru_q8_step_ref(layout: QuantDeltaLayout, m_prev: Array,
                         h_prev: Array, dx: Array, dh: Array):
    """Pure-jnp oracle of the int8 GRU step (also the no-Pallas fallback).

    Bit-identical to the kernel: the code-domain accumulation is exact in
    fp32 for on-grid deltas and realistic magnitudes (products and partial
    sums are dyadic rationals well inside the 24-bit mantissa), so the
    summation order cannot matter; the dequant/LUT stage then performs the
    same pointwise op sequence as the kernel.
    """
    b = dx.shape[0]
    h_dim = layout.hidden_size
    cx, ch = _ref_code_slices(layout)
    px = _code_einsum(dx.astype(jnp.float32), cx)
    ph = _code_einsum(dh.astype(jnp.float32), ch)
    m = m_prev.reshape(b, 4, h_dim).astype(jnp.float32)
    m_r = m[:, 0] + (px[:, 0] + ph[:, 0])
    m_u = m[:, 1] + (px[:, 1] + ph[:, 1])
    m_xc = m[:, 2] + px[:, 2]
    m_hc = m[:, 3] + ph[:, 2]

    def q88(v):
        return _grid_round(v, layout.act_scale, layout.act_min,
                           layout.act_max)

    def lut(v):
        return _grid_round(v, layout.lut_scale, layout.lut_min,
                           layout.lut_max)

    s = layout.scales[:, :h_dim]
    b4 = layout.b4[:, :h_dim]
    sc_r = b4[0] + m_r * s[0]
    sc_u = b4[1] + m_u * s[1]
    sc_xc = b4[2] + m_xc * s[2]
    sc_hc = b4[3] + m_hc * s[2]
    r = lut(jax.nn.sigmoid(q88(sc_r)))
    u = lut(jax.nn.sigmoid(q88(sc_u)))
    c = lut(jnp.tanh(q88(sc_xc + r * sc_hc)))
    h_new = q88((1.0 - u) * c + u * h_prev.astype(jnp.float32))
    m_new = jnp.stack([m_r, m_u, m_xc, m_hc], 1).reshape(b, 4 * h_dim)
    return m_new.astype(m_prev.dtype), h_new.astype(h_prev.dtype)


# ---------------------------------------------------------------------------
# Double-buffered weight streaming (GRU)
# ---------------------------------------------------------------------------

def _q8_gru_kernel_dbuf(n_active_ref, active_ids_ref, d_ref, w_hbm, s_ref,
                        b_ref, m_ref, h_ref, m_out_ref, h_out_ref, wbuf,
                        acc_ref, sem, *, nbk_x: int, weight_bits: int,
                        act_scale: float, act_min: float, act_max: float,
                        lut_scale: float, lut_min: float, lut_max: float):
    """One o-block of the double-buffered int8/int4 fused GRU layer step.

    The weight volume stays in HBM (``memory_space=ANY``, pre-tiled to
    ``[nbo, nbk, 3, wbh, block_k]`` so one fired block is one leading
    index); the kernel overlaps the DMA for fired block ``j+1`` with the
    accumulation of block ``j`` through the two-slot VMEM scratch
    ``wbuf`` and the DMA-semaphore pair ``sem`` — the EdgeDRNN fetch
    pipeline, where the MxV never waits on DRAM except for the first
    block. The accumulation order is identical to the unbuffered kernel's
    k-walk and code-domain sums are exact, so the outputs are
    *bit-identical* to :func:`_q8_gru_kernel`.
    """
    o = pl.program_id(0)
    n = n_active_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def dma(slot, j):
        return pltpu.make_async_copy(
            w_hbm.at[o, active_ids_ref[j]], wbuf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _stream():
        dma(0, 0).start()

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n)
            def _prefetch():
                dma(1 - slot, j + 1).start()

            dma(slot, j).wait()
            if weight_bits == 4:
                w = _kernel_unpack_nibbles(wbuf[slot])
            else:
                w = wbuf[slot].astype(jnp.float32)
            d = d_ref[j]                             # fired delta block j
            p = _code_dot(d, w)
            is_x = active_ids_ref[j] < nbk_x
            acc_ref[:, 0, :] += p[:, 0, :]
            acc_ref[:, 1, :] += p[:, 1, :]
            pc = p[:, 2, :]
            acc_ref[:, 2, :] += jnp.where(is_x, pc, 0.0)
            acc_ref[:, 3, :] += jnp.where(is_x, 0.0, pc)
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def q88(v):
        return _grid_round(v, act_scale, act_min, act_max)

    def lut(v):
        return _grid_round(v, lut_scale, lut_min, lut_max)

    m_new = m_ref[...].astype(jnp.float32) + acc_ref[...]      # code domain
    s = s_ref[...].astype(jnp.float32)                         # [3, BH]
    s4 = jnp.concatenate([s, s[2:3]], axis=0)                  # c scale x2
    msc = b_ref[...][None] + m_new * s4[None]                  # dequantized
    h_prev = h_ref[...].astype(jnp.float32)
    r = lut(jax.nn.sigmoid(q88(msc[:, 0])))
    u = lut(jax.nn.sigmoid(q88(msc[:, 1])))
    c = lut(jnp.tanh(q88(msc[:, 2] + r * msc[:, 3])))
    h_new = q88((1.0 - u) * c + u * h_prev)
    m_out_ref[...] = m_new.astype(m_out_ref.dtype)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "act_scale",
    "act_min", "act_max", "lut_scale", "lut_min", "lut_max", "weight_bits",
    "interpret"))
def _fused_q8_step_dbuf(w_q: Array, scales: Array, b4: Array, m_prev: Array,
                        h_prev: Array, dx: Array, dh: Array, *,
                        input_size: int, hidden_size: int, block_h: int,
                        block_k: int, act_scale: float, act_min: float,
                        act_max: float, lut_scale: float, lut_min: float,
                        lut_max: float, weight_bits: int, interpret: bool):
    """Double-buffered variant of :func:`_fused_q8_step` (bit-identical).

    Grid is ``(nbo,)`` only: the k-walk moves into an in-kernel
    ``fori_loop`` over fired blocks so the weight DMA for block ``j+1``
    can be issued while block ``j`` accumulates. The fired delta blocks
    are pre-gathered (activation-sized, the Delta Unit's job) with the
    block index leading, so the loop indexes VMEM on the leading dim
    only; the weight volume is re-tiled to ``[nbo, nbk, 3, wbh,
    block_k]`` so one fired block is one leading DMA index (loop-invariant —
    XLA hoists it out of `lax.scan` sequence bodies).
    """
    lay = QuantDeltaLayout(w_q, scales, b4, input_size, hidden_size, block_h,
                           block_k, act_scale, act_min, act_max, lut_scale,
                           lut_min, lut_max, gates=3)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    wbh = block_h // 2 if weight_bits == 4 else block_h
    d_cat, m4, hprev, n_active, active_ids = _prep_step_operands(
        lay, m_prev, h_prev, dx, dh)
    d_act = jnp.take(d_cat.reshape(b, nbk, block_k), active_ids,
                     axis=1).transpose(1, 0, 2)                # [nbk, B, BK]
    w_stream = w_q.reshape(3, lay.nbo, wbh, nbk,
                           block_k).transpose(1, 3, 0, 2, 4)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lay.nbo,),
        in_specs=[
            pl.BlockSpec((nbk, b, block_k),
                         lambda o, n, ids: (0, 0, 0)),         # d_act
            pl.BlockSpec(memory_space=pl.ANY),                # w_stream HBM
            pl.BlockSpec((3, block_h), lambda o, n, ids: (0, o)),   # scales
            pl.BlockSpec((4, block_h), lambda o, n, ids: (0, o)),   # b4
            pl.BlockSpec((b, 4, block_h),
                         lambda o, n, ids: (0, 0, o)),         # m_prev
            pl.BlockSpec((b, block_h), lambda o, n, ids: (0, o)),   # h_prev
        ],
        out_specs=[
            pl.BlockSpec((b, 4, block_h), lambda o, n, ids: (0, 0, o)),
            pl.BlockSpec((b, block_h), lambda o, n, ids: (0, o)),
        ],
        scratch_shapes=[pltpu.VMEM((2, 3, wbh, block_k), jnp.int8),
                        pltpu.VMEM((b, 4, block_h), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    m_new, h_new = pl.pallas_call(
        functools.partial(_q8_gru_kernel_dbuf, nbk_x=lay.nbk_x,
                          weight_bits=weight_bits,
                          act_scale=act_scale, act_min=act_min,
                          act_max=act_max, lut_scale=lut_scale,
                          lut_min=lut_min, lut_max=lut_max),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_act, w_stream, scales, b4, m4, hprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim])


# ---------------------------------------------------------------------------
# LSTM instantiation (gates=4, no seam routing, saturating Q8.8 cell state)
# ---------------------------------------------------------------------------

def _q8_lstm_kernel(n_active_ref, active_ids_ref, d_ref, w_ref, s_ref, b_ref,
                    m_ref, c_ref, m_out_ref, h_out_ref, c_out_ref, acc_ref,
                    *, nbk: int, weight_bits: int, act_scale: float,
                    act_min: float, act_max: float, lut_scale: float,
                    lut_min: float, lut_max: float):
    """One (o-block, k-step) cell of the int8 fused LSTM layer step.

    Same integer-accumulator semantics as the GRU kernel, but every fired
    block feeds all four delta memories (no candidate split, so no seam
    routing) and the activation stage is the i/f/g/o + cell-state
    pipeline: gates on the Q1.n LUT grid, cell state ``c`` re-rounded onto
    the Q8.8 accumulator grid every step with **saturation** at the rails
    (the clip in :func:`_grid_round` — an int16 accumulator clips, it does
    not wrap). Like the fp32 LSTM kernel there is no ``h_prev`` operand:
    ``h = o * tanh(c)`` reads only the cell state.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_active_ref[0])
    def _accumulate():
        d = d_ref[...]                               # [B, BK] on the Q8.8 grid
        if weight_bits == 4:
            w = _kernel_unpack_nibbles(w_ref[...])   # nibbles -> f32 codes
        else:
            w = w_ref[...].astype(jnp.float32)       # int8 codes -> f32
        acc_ref[...] += _code_dot(d, w)

    @pl.when(i == nbk - 1)
    def _activate():
        def q88(v):
            return _grid_round(v, act_scale, act_min, act_max)

        def lut(v):
            return _grid_round(v, lut_scale, lut_min, lut_max)

        m_new = m_ref[...].astype(jnp.float32) + acc_ref[...]  # code domain
        s = s_ref[...].astype(jnp.float32)                     # [4, BH]
        msc = b_ref[...][None] + m_new * s[None]               # dequantized
        c_prev = c_ref[...].astype(jnp.float32)
        gi = lut(jax.nn.sigmoid(q88(msc[:, 0])))
        gf = lut(jax.nn.sigmoid(q88(msc[:, 1])))
        gg = lut(jnp.tanh(q88(msc[:, 2])))
        go = lut(jax.nn.sigmoid(q88(msc[:, 3])))
        c_new = q88(gf * c_prev + gi * gg)        # saturating Q8.8 accumulator
        h_new = q88(go * lut(jnp.tanh(c_new)))
        m_out_ref[...] = m_new.astype(m_out_ref.dtype)
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)
        c_out_ref[...] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "act_scale",
    "act_min", "act_max", "lut_scale", "lut_min", "lut_max", "weight_bits",
    "interpret"))
def _fused_q8_lstm_step(w_q: Array, scales: Array, b4: Array, m_prev: Array,
                        h_prev: Array, c_prev: Array, dx: Array, dh: Array,
                        *, input_size: int, hidden_size: int, block_h: int,
                        block_k: int, act_scale: float, act_min: float,
                        act_max: float, lut_scale: float, lut_min: float,
                        lut_max: float, weight_bits: int, interpret: bool):
    """One int8/int4 fused LSTM layer step on already-encoded deltas.

    ``m_prev: [B, 4H]`` (code-domain accumulator), ``c_prev: [B, H]`` (on
    the Q8.8 grid), ``dx: [B, I]``, ``dh: [B, H]`` ->
    ``(m_new: [B, 4H], h_new: [B, H], c_new: [B, H])``.
    """
    lay = QuantDeltaLayout(w_q, scales, b4, input_size, hidden_size, block_h,
                           block_k, act_scale, act_min, act_max, lut_scale,
                           lut_min, lut_max, gates=4)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    wbh = block_h // 2 if weight_bits == 4 else block_h
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
            pl.BlockSpec((4, wbh, block_k),
                         lambda o, i, n, ids: (0, o, ids[i])),     # w_q packed
            pl.BlockSpec((4, block_h),
                         lambda o, i, n, ids: (0, o)),             # scales
            pl.BlockSpec((4, block_h),
                         lambda o, i, n, ids: (0, o)),             # b4
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
        functools.partial(_q8_lstm_kernel, nbk=nbk, weight_bits=weight_bits,
                          act_scale=act_scale, act_min=act_min,
                          act_max=act_max, lut_scale=lut_scale,
                          lut_min=lut_min, lut_max=lut_max),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), c_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_cat, w_q, scales, b4, m4, cprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim],
            c_new[:, :h_dim])


def _q8_lstm_kernel_dbuf(n_active_ref, active_ids_ref, d_ref, w_hbm, s_ref,
                         b_ref, m_ref, c_ref, m_out_ref, h_out_ref,
                         c_out_ref, wbuf, acc_ref, sem, *, weight_bits: int,
                         act_scale: float, act_min: float, act_max: float,
                         lut_scale: float, lut_min: float, lut_max: float):
    """One o-block of the double-buffered int8/int4 fused LSTM layer step
    (the LSTM twin of :func:`_q8_gru_kernel_dbuf`: no seam routing, all
    four delta memories take both streams, saturating Q8.8 cell state —
    bit-identical to :func:`_q8_lstm_kernel`)."""
    o = pl.program_id(0)
    n = n_active_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def dma(slot, j):
        return pltpu.make_async_copy(
            w_hbm.at[o, active_ids_ref[j]], wbuf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _stream():
        dma(0, 0).start()

        def body(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n)
            def _prefetch():
                dma(1 - slot, j + 1).start()

            dma(slot, j).wait()
            if weight_bits == 4:
                w = _kernel_unpack_nibbles(wbuf[slot])
            else:
                w = wbuf[slot].astype(jnp.float32)
            d = d_ref[j]                             # fired delta block j
            acc_ref[...] += _code_dot(d, w)
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def q88(v):
        return _grid_round(v, act_scale, act_min, act_max)

    def lut(v):
        return _grid_round(v, lut_scale, lut_min, lut_max)

    m_new = m_ref[...].astype(jnp.float32) + acc_ref[...]      # code domain
    s = s_ref[...].astype(jnp.float32)                         # [4, BH]
    msc = b_ref[...][None] + m_new * s[None]                   # dequantized
    c_prev = c_ref[...].astype(jnp.float32)
    gi = lut(jax.nn.sigmoid(q88(msc[:, 0])))
    gf = lut(jax.nn.sigmoid(q88(msc[:, 1])))
    gg = lut(jnp.tanh(q88(msc[:, 2])))
    go = lut(jax.nn.sigmoid(q88(msc[:, 3])))
    c_new = q88(gf * c_prev + gi * gg)            # saturating Q8.8 accumulator
    h_new = q88(go * lut(jnp.tanh(c_new)))
    m_out_ref[...] = m_new.astype(m_out_ref.dtype)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "input_size", "hidden_size", "block_h", "block_k", "act_scale",
    "act_min", "act_max", "lut_scale", "lut_min", "lut_max", "weight_bits",
    "interpret"))
def _fused_q8_lstm_step_dbuf(w_q: Array, scales: Array, b4: Array,
                             m_prev: Array, h_prev: Array, c_prev: Array,
                             dx: Array, dh: Array, *, input_size: int,
                             hidden_size: int, block_h: int, block_k: int,
                             act_scale: float, act_min: float,
                             act_max: float, lut_scale: float,
                             lut_min: float, lut_max: float,
                             weight_bits: int, interpret: bool):
    """Double-buffered variant of :func:`_fused_q8_lstm_step`
    (bit-identical; see :func:`_fused_q8_step_dbuf` for the scheme)."""
    lay = QuantDeltaLayout(w_q, scales, b4, input_size, hidden_size, block_h,
                           block_k, act_scale, act_min, act_max, lut_scale,
                           lut_min, lut_max, gates=4)
    b = dx.shape[0]
    h_dim, hp = hidden_size, lay.hp
    nbk = lay.nbk
    wbh = block_h // 2 if weight_bits == 4 else block_h
    d_cat, m4, _, n_active, active_ids = _prep_step_operands(
        lay, m_prev, h_prev, dx, dh)
    cprev = jnp.pad(c_prev, ((0, 0), (0, hp - h_dim)))
    d_act = jnp.take(d_cat.reshape(b, nbk, block_k), active_ids,
                     axis=1).transpose(1, 0, 2)                # [nbk, B, BK]
    w_stream = w_q.reshape(4, lay.nbo, wbh, nbk,
                           block_k).transpose(1, 3, 0, 2, 4)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lay.nbo,),
        in_specs=[
            pl.BlockSpec((nbk, b, block_k),
                         lambda o, n, ids: (0, 0, 0)),         # d_act
            pl.BlockSpec(memory_space=pl.ANY),                # w_stream HBM
            pl.BlockSpec((4, block_h), lambda o, n, ids: (0, o)),   # scales
            pl.BlockSpec((4, block_h), lambda o, n, ids: (0, o)),   # b4
            pl.BlockSpec((b, 4, block_h),
                         lambda o, n, ids: (0, 0, o)),         # m_prev
            pl.BlockSpec((b, block_h), lambda o, n, ids: (0, o)),   # c_prev
        ],
        out_specs=[
            pl.BlockSpec((b, 4, block_h), lambda o, n, ids: (0, 0, o)),
            pl.BlockSpec((b, block_h), lambda o, n, ids: (0, o)),
            pl.BlockSpec((b, block_h), lambda o, n, ids: (0, o)),
        ],
        scratch_shapes=[pltpu.VMEM((2, 4, wbh, block_k), jnp.int8),
                        pltpu.VMEM((b, 4, block_h), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    m_new, h_new, c_new = pl.pallas_call(
        functools.partial(_q8_lstm_kernel_dbuf, weight_bits=weight_bits,
                          act_scale=act_scale, act_min=act_min,
                          act_max=act_max, lut_scale=lut_scale,
                          lut_min=lut_min, lut_max=lut_max),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4, hp), m_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), h_prev.dtype),
            jax.ShapeDtypeStruct((b, hp), c_prev.dtype),
        ],
        interpret=interpret,
    )(n_active, active_ids, d_act, w_stream, scales, b4, m4, cprev)
    return (m_new[:, :, :h_dim].reshape(b, 4 * h_dim), h_new[:, :h_dim],
            c_new[:, :h_dim])


def deltalstm_q8_step(layout: QuantDeltaLayout, m_prev: Array, h_prev: Array,
                      c_prev: Array, dx: Array, dh: Array, *,
                      interpret: bool | None = None, buffered: bool = False):
    """Public int8/int4 LSTM single-step entry on encoded deltas (see
    :func:`_fused_q8_lstm_step`; ``buffered=True`` runs the
    double-buffered weight-streaming variant — bit-identical output)."""
    step = _fused_q8_lstm_step_dbuf if buffered else _fused_q8_lstm_step
    from repro.kernels.ops import _interpret_default  # ops imports us
    return step(
        layout.w_q, layout.scales, layout.b4, m_prev, h_prev, c_prev, dx, dh,
        input_size=layout.input_size, hidden_size=layout.hidden_size,
        block_h=layout.block_h, block_k=layout.block_k,
        act_scale=layout.act_scale, act_min=layout.act_min,
        act_max=layout.act_max, lut_scale=layout.lut_scale,
        lut_min=layout.lut_min, lut_max=layout.lut_max,
        weight_bits=layout.weight_bits,
        interpret=_interpret_default(interpret))


def deltalstm_q8_step_ref(layout: QuantDeltaLayout, m_prev: Array,
                          h_prev: Array, c_prev: Array, dx: Array,
                          dh: Array):
    """Pure-jnp oracle of the int8 LSTM step (also the no-Pallas fallback).

    Bit-identical to the kernel for the same reason as the GRU oracle: the
    code-domain accumulation is exact in fp32 for on-grid deltas, and the
    dequant / LUT / cell-state stage is the same pointwise op sequence.
    """
    b = dx.shape[0]
    h_dim = layout.hidden_size
    cx, ch = _ref_code_slices(layout)
    px = _code_einsum(dx.astype(jnp.float32), cx)
    ph = _code_einsum(dh.astype(jnp.float32), ch)
    m = m_prev.reshape(b, 4, h_dim).astype(jnp.float32) + (px + ph)

    def q88(v):
        return _grid_round(v, layout.act_scale, layout.act_min,
                           layout.act_max)

    def lut(v):
        return _grid_round(v, layout.lut_scale, layout.lut_min,
                           layout.lut_max)

    s = layout.scales[:, :h_dim]
    b4 = layout.b4[:, :h_dim]
    gi = lut(jax.nn.sigmoid(q88(b4[0] + m[:, 0] * s[0])))
    gf = lut(jax.nn.sigmoid(q88(b4[1] + m[:, 1] * s[1])))
    gg = lut(jnp.tanh(q88(b4[2] + m[:, 2] * s[2])))
    go = lut(jax.nn.sigmoid(q88(b4[3] + m[:, 3] * s[3])))
    c_new = q88(gf * c_prev.astype(jnp.float32) + gi * gg)
    h_new = q88(go * lut(jnp.tanh(c_new)))
    m_new = m.reshape(b, 4 * h_dim)
    return (m_new.astype(m_prev.dtype), h_new.astype(h_prev.dtype),
            c_new.astype(c_prev.dtype))
