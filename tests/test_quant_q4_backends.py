"""int4 nibble-packed backend (``fused_q4``) equivalence + pricing.

The ``fused_q4`` path must *bit-match* an independently written fake-quant
fixed-point reference built from the :mod:`repro.quant` primitives and a
TEST-LOCAL numpy nibble decoder (same Qm.n grids, same documented packing
convention, none of the runtime's unpack code): int4 per-gate-row weight
codes in [-7, 7], two codes per streamed byte over the
``[gates, Hp//2, Ip+Hk]`` volume, Q8.8 activation grid, unscaled
code-domain delta memories, bias + dequant at the activation stage,
Q8.8 -> Q1.4 LUT nonlinearities. Because the code-domain accumulation is
exact in fp32 for on-grid deltas, every summation order gives the same
bits — the Pallas kernel (with its in-register unpack), its jnp oracle and
the reference below must agree exactly, not approximately.

Also pinned here: the nibble pack/unpack round trip (incl. odd raw
``I + H`` extents through block padding), exporter idempotency at
``bits=4``, the ``bits`` validation errors, the QAT W4 policy, the
double-buffered weight-streaming parity (buffered == unbuffered, bitwise,
both cells and both widths), the exact Eq. 7 pricing ladder
(q4 = 0.5x q8 = 0.125x fp32) including the registry's bytes-per-weight
map (the ``bits // 8`` truncation regression), and
batcher session parity on quantized-int4 programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import list_backends
from repro.core.deltagru import deltagru_sequence, init_gru_stack
from repro.core.deltalstm import deltalstm_sequence, init_lstm_stack
from repro.core.perf_model import (backend_weight_bits,
                                   dram_traffic_bytes_per_timestep)
from repro.core.program import compile_delta_program
from repro.core.sparsity import GruDims, lstm_dims
from repro.kernels.delta_q8 import (deltagru_q8_step, deltalstm_q8_step,
                                    pack_delta_weights_q4,
                                    pack_delta_weights_q8, pack_nibbles,
                                    unpack_nibbles)
from repro.models.gru_rnn import GruTaskConfig, init_lstm_model
from repro.quant.export import (quantize_delta_model, quantize_delta_stack,
                                quantize_stack)
from repro.quant.fake_quant import (ACT_Q88, WGT_Q13, WGT_Q17, QFormat,
                                    quantize, weight_format_for_bits)
from repro.quant.qat import EDGEDRNN_QAT_W4, QatPolicy
from repro.serve.engine import DeltaStreamEngine
from repro.serve.scheduler import GruStreamBatcher

LUT_Q14 = QFormat(1, 4)


def _unpack_nibbles_np(packed, block_h):
    """TEST-LOCAL numpy nibble decoder, written from the documented
    convention (not the runtime code): the codes are paired along the
    rows (second-to-last axis). Within each row block of ``block_h // 2``
    packed rows, packed row ``j`` carries row ``j`` of the block in its
    low nibble and row ``j + block_h // 2`` in its high nibble, column by
    column, each a 4-bit two's-complement code."""
    p = np.asarray(packed).astype(np.int32)
    half = block_h // 2
    *lead, rp, k = p.shape
    out = np.empty((*lead, 2 * rp, k), np.int32)
    for blk in range(rp // half):
        rows = p[..., blk * half:(blk + 1) * half, :]
        lo = ((rows & 15) ^ 8) - 8
        hi = (((rows >> 4) & 15) ^ 8) - 8
        out[..., 2 * blk * half:(2 * blk + 1) * half, :] = lo
        out[..., (2 * blk + 1) * half:(2 * blk + 2) * half, :] = hi
    return out


def _codes_f32(lay):
    """fp32 code volume of a layout via the independent numpy decoder."""
    if lay.weight_bits == 4:
        return jnp.asarray(
            _unpack_nibbles_np(lay.w_q, lay.block_h).astype(np.float32))
    return lay.w_q.astype(jnp.float32)


def _gru_stack_and_xs(key, i, h, layers, t, b, scale=0.5):
    params = init_gru_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * scale
    return params, xs


def _lstm_stack_and_xs(key, i, h, layers, t, b, scale=0.5):
    params = init_lstm_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * scale
    return params, xs


def _fake_quant_gru_q4_reference(layouts, xs, theta_x, theta_h):
    """Independent fixed-point DeltaGRU oracle on int4 codes (python loop,
    quant/ grids, test-local nibble decoder)."""
    t_len, b, _ = xs.shape
    hs, xhats, hhats, ms = [], [], [], []
    for lay in layouts:
        hs.append(jnp.zeros((b, lay.hidden_size)))
        xhats.append(jnp.zeros((b, lay.input_size)))
        hhats.append(jnp.zeros((b, lay.hidden_size)))
        ms.append(jnp.zeros((b, 4 * lay.hidden_size)))
    ys = []
    for t in range(t_len):
        inp = quantize(xs[t], ACT_Q88)
        for li, lay in enumerate(layouts):
            h_dim, i_dim = lay.hidden_size, lay.input_size
            raw_x = inp - xhats[li]
            fired_x = jnp.abs(raw_x) >= theta_x
            dx = jnp.where(fired_x, raw_x, 0.0)
            xhats[li] = jnp.where(fired_x, inp, xhats[li])
            raw_h = hs[li] - hhats[li]
            fired_h = jnp.abs(raw_h) >= theta_h
            dh = jnp.where(fired_h, raw_h, 0.0)
            hhats[li] = jnp.where(fired_h, hs[li], hhats[li])
            codes = _codes_f32(lay)
            cx = codes[:, :h_dim, :i_dim]
            ch = codes[:, :h_dim, lay.ip:lay.ip + h_dim]
            m = ms[li].reshape(b, 4, h_dim)
            m_r = m[:, 0] + (dx @ cx[0].T + dh @ ch[0].T)
            m_u = m[:, 1] + (dx @ cx[1].T + dh @ ch[1].T)
            m_xc = m[:, 2] + dx @ cx[2].T
            m_hc = m[:, 3] + dh @ ch[2].T
            ms[li] = jnp.stack([m_r, m_u, m_xc, m_hc], 1).reshape(b, -1)
            s = lay.scales[:, :h_dim]
            b4 = lay.b4[:, :h_dim]
            r = quantize(jax.nn.sigmoid(
                quantize(b4[0] + m_r * s[0], ACT_Q88)), LUT_Q14)
            u = quantize(jax.nn.sigmoid(
                quantize(b4[1] + m_u * s[1], ACT_Q88)), LUT_Q14)
            c = quantize(jnp.tanh(quantize(
                (b4[2] + m_xc * s[2]) + r * (b4[3] + m_hc * s[2]),
                ACT_Q88)), LUT_Q14)
            hs[li] = quantize((1.0 - u) * c + u * hs[li], ACT_Q88)
            inp = hs[li]
        ys.append(inp)
    return jnp.stack(ys)


def _fake_quant_lstm_q4_reference(layouts, xs, theta_x, theta_h):
    """Independent fixed-point DeltaLSTM oracle on int4 codes."""
    t_len, b, _ = xs.shape
    hs, cs, xhats, hhats, ms = [], [], [], [], []
    for lay in layouts:
        hs.append(jnp.zeros((b, lay.hidden_size)))
        cs.append(jnp.zeros((b, lay.hidden_size)))
        xhats.append(jnp.zeros((b, lay.input_size)))
        hhats.append(jnp.zeros((b, lay.hidden_size)))
        ms.append(jnp.zeros((b, 4 * lay.hidden_size)))
    ys = []
    for t in range(t_len):
        inp = quantize(xs[t], ACT_Q88)
        for li, lay in enumerate(layouts):
            h_dim, i_dim = lay.hidden_size, lay.input_size
            raw_x = inp - xhats[li]
            fired_x = jnp.abs(raw_x) >= theta_x
            dx = jnp.where(fired_x, raw_x, 0.0)
            xhats[li] = jnp.where(fired_x, inp, xhats[li])
            raw_h = hs[li] - hhats[li]
            fired_h = jnp.abs(raw_h) >= theta_h
            dh = jnp.where(fired_h, raw_h, 0.0)
            hhats[li] = jnp.where(fired_h, hs[li], hhats[li])
            codes = _codes_f32(lay)
            cx = codes[:, :h_dim, :i_dim]
            ch = codes[:, :h_dim, lay.ip:lay.ip + h_dim]
            m = ms[li].reshape(b, 4, h_dim)
            mg = [m[:, g] + (dx @ cx[g].T + dh @ ch[g].T) for g in range(4)]
            ms[li] = jnp.stack(mg, 1).reshape(b, -1)
            s = lay.scales[:, :h_dim]
            b4 = lay.b4[:, :h_dim]
            gi = quantize(jax.nn.sigmoid(
                quantize(b4[0] + mg[0] * s[0], ACT_Q88)), LUT_Q14)
            gf = quantize(jax.nn.sigmoid(
                quantize(b4[1] + mg[1] * s[1], ACT_Q88)), LUT_Q14)
            gg = quantize(jnp.tanh(
                quantize(b4[2] + mg[2] * s[2], ACT_Q88)), LUT_Q14)
            go = quantize(jax.nn.sigmoid(
                quantize(b4[3] + mg[3] * s[3], ACT_Q88)), LUT_Q14)
            cs[li] = quantize(gf * cs[li] + gi * gg, ACT_Q88)
            hs[li] = quantize(
                go * quantize(jnp.tanh(cs[li]), LUT_Q14), ACT_Q88)
            inp = hs[li]
        ys.append(inp)
    return jnp.stack(ys)


class TestNibblePacking:
    @pytest.mark.parametrize("shape,block_h",
                             [((3, 16, 8), 8), ((4, 24, 5), 4),
                              ((32, 1), 32)])
    def test_round_trip(self, shape, block_h):
        rng = np.random.default_rng(sum(shape) + block_h)
        codes = rng.integers(-7, 8, size=shape).astype(np.int8)
        packed = pack_nibbles(jnp.asarray(codes), block_h)
        assert packed.dtype == jnp.int8
        assert packed.shape == shape[:-2] + (shape[-2] // 2, shape[-1])
        np.testing.assert_array_equal(
            np.asarray(unpack_nibbles(packed, block_h)), codes)
        # and the independent numpy decoder agrees — this pins the
        # low/high nibble-to-row convention, not just invertibility
        np.testing.assert_array_equal(
            _unpack_nibbles_np(packed, block_h), codes)

    def test_rejects_non_block_multiple(self):
        with pytest.raises(ValueError, match="block"):
            pack_nibbles(jnp.zeros((3, 10), jnp.int8), 4)

    def test_odd_raw_extent_pads_through(self):
        """An odd raw H (the paired row extent) still packs: the rows are
        padded to block_h multiples first, so the nibble pairing never
        straddles row blocks."""
        p = init_gru_stack(jax.random.PRNGKey(0), 13, 17, 1)[0]
        lay = pack_delta_weights_q4(p.w_x, p.w_h, b=p.b, block_h=8,
                                    block_k=8)
        assert 17 % 2 == 1 and lay.hp % lay.block_h == 0
        assert lay.weight_bits == 4
        assert lay.w_q.shape == (3, lay.hp // 2, lay.ip + lay.hk)
        codes = _unpack_nibbles_np(lay.w_q, lay.block_h)
        assert codes.min() >= -7 and codes.max() <= 7
        # dequantized codes reproduce the int4 fake-quant view of w_x
        w = codes[:, :17, :13] * np.asarray(lay.scales)[:, :17, None]
        np.testing.assert_allclose(w.reshape(3 * 17, 13),
                                   np.asarray(_q4_view(p.w_x, lay)),
                                   atol=1e-6)


def _q4_view(w_x, lay):
    """Per-gate-row symmetric int4 requant of raw weights (independent of
    the packer's internals)."""
    g, h = 3, lay.hidden_size
    w = np.asarray(w_x).reshape(g, h, -1)
    s = np.asarray(lay.scales)[:, :h]
    codes = np.clip(np.round(w / s[:, :, None]), -7, 7)
    return (codes * s[:, :, None]).reshape(g * h, -1)


class TestFusedQ4BitMatchGru:
    # interpret=True exercises the actual Pallas kernel incl. the
    # in-register nibble unpack (the default route off-TPU is the
    # bit-identical jnp oracle).
    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    @pytest.mark.parametrize("i,h,layers,b",
                             [(10, 24, 2, 2), (14, 32, 1, 1)])
    def test_bitmatches_fake_quant_reference(self, kw, i, h, layers, b):
        """Acceptance bar: fused_q4 == the int4 fake-quant fixed-point
        oracle, bit for bit, at nonzero dual thresholds."""
        params, xs = _gru_stack_and_xs(i + h, i, h, layers, 12, b)
        qparams, layouts = quantize_stack(params, bits=4)
        want = _fake_quant_gru_q4_reference(layouts, xs, 6 / 256, 12 / 256)
        got, _, _ = deltagru_sequence(qparams, xs, 6 / 256, 12 / 256,
                                      backend="fused_q4", layouts=layouts,
                                      **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    def test_theta_zero_is_quantized_plain_gru(self, kw):
        """At theta=0 the code-domain delta memories telescope exactly, so
        fused_q4 IS the int4-quantized plain GRU (bit-identical)."""
        params, xs = _gru_stack_and_xs(3, 12, 16, 2, 10, 2)
        qparams, layouts = quantize_stack(params, bits=4)
        want = _fake_quant_gru_q4_reference(layouts, xs, 0.0, 0.0)
        got, _, _ = deltagru_sequence(qparams, xs, 0.0, 0.0,
                                      backend="fused_q4", layouts=layouts,
                                      **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_tracks_fp32_dense_within_2x_q8_budget(self):
        """The int4 grid is coarser than int8, but the drift rail is 2x
        the q8 budget (0.5), not unbounded."""
        params, xs = _gru_stack_and_xs(7, 12, 24, 2, 16, 2)
        qparams, layouts = quantize_stack(params, bits=4)
        want, _, _ = deltagru_sequence(params, xs, 0.02, 0.02)
        got, _, _ = deltagru_sequence(qparams, xs, 0.02, 0.02,
                                      backend="fused_q4", layouts=layouts)
        assert float(jnp.max(jnp.abs(got - want))) < 0.5

    def test_packed_weights_are_nibble_volume(self):
        params, _ = _gru_stack_and_xs(0, 8, 16, 1, 4, 1)
        _, layouts = quantize_stack(params, bits=4)
        for lay in layouts:
            assert lay.weight_bits == 4
            assert lay.w_q.dtype == jnp.int8          # the HBM operand
            assert lay.w_q.shape == (3, lay.hp // 2, lay.ip + lay.hk)
            codes = _unpack_nibbles_np(lay.w_q, lay.block_h)
            assert codes.min() >= -7 and codes.max() <= 7


class TestFusedQ4BitMatchLstm:
    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    @pytest.mark.parametrize("i,h,layers,b",
                             [(10, 24, 2, 2), (14, 32, 1, 1)])
    def test_bitmatches_fake_quant_reference(self, kw, i, h, layers, b):
        params, xs = _lstm_stack_and_xs(i + h, i, h, layers, 12, b)
        qparams, layouts = quantize_delta_stack(params, cell="lstm",
                                                bits=4)
        want = _fake_quant_lstm_q4_reference(layouts, xs, 6 / 256, 12 / 256)
        got, _, _ = deltalstm_sequence(qparams, xs, 6 / 256, 12 / 256,
                                       backend="fused_q4", layouts=layouts,
                                       **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    def test_theta_zero_is_quantized_plain_lstm(self, kw):
        params, xs = _lstm_stack_and_xs(3, 12, 16, 2, 10, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm",
                                                bits=4)
        want = _fake_quant_lstm_q4_reference(layouts, xs, 0.0, 0.0)
        got, _, _ = deltalstm_sequence(qparams, xs, 0.0, 0.0,
                                       backend="fused_q4", layouts=layouts,
                                       **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_tracks_fp32_dense_within_2x_q8_budget(self):
        params, xs = _lstm_stack_and_xs(7, 12, 24, 2, 16, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm",
                                                bits=4)
        want, _, _ = deltalstm_sequence(params, xs, 0.02, 0.02)
        got, _, _ = deltalstm_sequence(qparams, xs, 0.02, 0.02,
                                       backend="fused_q4", layouts=layouts)
        assert float(jnp.max(jnp.abs(got - want))) < 0.5


class TestDoubleBufferedStreaming:
    """The two-slot DMA weight-streaming variant must be BITWISE identical
    to the unbuffered kernel — same accumulation order, same exact sums —
    for both cells at both streamed widths, including the zero-delta
    (nothing fired) step."""

    def _gru_operands(self, bits, key=0, i=12, h=24, b=2):
        p = init_gru_stack(jax.random.PRNGKey(key), i, h, 1)[0]
        pack = (pack_delta_weights_q4 if bits == 4
                else pack_delta_weights_q8)
        lay = pack(p.w_x, p.w_h, b=p.b)
        k = jax.random.fold_in(jax.random.PRNGKey(key), 9)
        dx = lay.quantize_act(jax.random.normal(k, (b, i)) * 0.3)
        dh = lay.quantize_act(
            jax.random.normal(jax.random.fold_in(k, 1), (b, h)) * 0.3)
        m = jax.random.normal(jax.random.fold_in(k, 2), (b, 4 * h))
        h0 = lay.quantize_act(
            jax.random.normal(jax.random.fold_in(k, 3), (b, h)) * 0.5)
        return lay, m, h0, dx, dh

    @pytest.mark.parametrize("bits", [8, 4])
    def test_gru_buffered_matches_unbuffered(self, bits):
        lay, m, h0, dx, dh = self._gru_operands(bits)
        m1, h1 = deltagru_q8_step(lay, m, h0, dx, dh, interpret=True)
        m2, h2 = deltagru_q8_step(lay, m, h0, dx, dh, interpret=True,
                                  buffered=True)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_gru_buffered_zero_delta(self, bits):
        """n_active == 0: the DMA loop must not issue and the activation
        stage still runs on the carried memories."""
        lay, m, h0, dx, dh = self._gru_operands(bits)
        zx, zh = jnp.zeros_like(dx), jnp.zeros_like(dh)
        m1, h1 = deltagru_q8_step(lay, m, h0, zx, zh, interpret=True)
        m2, h2 = deltagru_q8_step(lay, m, h0, zx, zh, interpret=True,
                                  buffered=True)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_lstm_buffered_matches_unbuffered(self, bits):
        p = init_lstm_stack(jax.random.PRNGKey(1), 12, 24, 1)[0]
        lay = quantize_delta_stack([p], cell="lstm", bits=bits)[1][0]
        k = jax.random.PRNGKey(11)
        dx = lay.quantize_act(jax.random.normal(k, (2, 12)) * 0.3)
        dh = lay.quantize_act(
            jax.random.normal(jax.random.fold_in(k, 1), (2, 24)) * 0.3)
        m = jax.random.normal(jax.random.fold_in(k, 2), (2, 96))
        h0 = lay.quantize_act(
            jax.random.normal(jax.random.fold_in(k, 3), (2, 24)) * 0.5)
        c0 = lay.quantize_act(
            jax.random.normal(jax.random.fold_in(k, 4), (2, 24)) * 0.5)
        out1 = deltalstm_q8_step(lay, m, h0, c0, dx, dh, interpret=True)
        out2 = deltalstm_q8_step(lay, m, h0, c0, dx, dh, interpret=True,
                                 buffered=True)
        for a, b in zip(out1, out2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestQ4Exporter:
    def test_bits_validated(self):
        params, _ = _gru_stack_and_xs(0, 8, 16, 1, 4, 1)
        for bad in (2, 3, 16, 0):
            with pytest.raises(ValueError, match="packed runtime width"):
                quantize_stack(params, bits=bad)
        with pytest.raises(ValueError, match="weight_bits"):
            pack_delta_weights_q8(params[0].w_x, params[0].w_h,
                                  weight_bits=5)

    def test_weight_format_for_bits(self):
        assert weight_format_for_bits(8) is WGT_Q17
        assert weight_format_for_bits(4) is WGT_Q13
        assert WGT_Q13.bits == 4
        with pytest.raises(ValueError, match="no weight grid"):
            weight_format_for_bits(6)

    def test_qat_w4_policy(self):
        assert EDGEDRNN_QAT_W4.weight_bits == 4
        assert EDGEDRNN_QAT_W4.weight_fmt is WGT_Q13
        assert QatPolicy.for_weight_bits(8).weight_fmt is WGT_Q17
        with pytest.raises(ValueError, match="no weight grid"):
            QatPolicy.for_weight_bits(5)
        # W4 fake-quant lands every weight on the Q0.3 grid
        w = EDGEDRNN_QAT_W4.quantize_params(
            {"w": jnp.linspace(-0.9, 0.9, 13)})["w"]
        np.testing.assert_allclose(np.asarray(w) * 8.0,
                                   np.round(np.asarray(w) * 8.0),
                                   atol=1e-6)

    def test_quantization_idempotent(self):
        """Re-exporting the int4 fake-quant view reproduces the same
        packed bytes."""
        params, _ = _gru_stack_and_xs(1, 8, 16, 2, 4, 1)
        qparams, layouts = quantize_stack(params, bits=4)
        _, layouts2 = quantize_stack(qparams, bits=4)
        for a, b in zip(layouts, layouts2):
            np.testing.assert_array_equal(np.asarray(a.w_q),
                                          np.asarray(b.w_q))
            np.testing.assert_array_equal(np.asarray(a.b4),
                                          np.asarray(b.b4))

    def test_quantize_delta_model_bits4(self):
        task = GruTaskConfig(8, 16, 2, 3, task="regression")
        model = init_lstm_model(jax.random.PRNGKey(1), task)
        prog = quantize_delta_model(model, bits=4)
        assert prog.cell == "lstm" and prog.backend == "fused_q4"
        assert all(lay.weight_bits == 4 for lay in prog.layouts)
        # identical to the compile_delta_program spelling, bit for bit
        prog2 = compile_delta_program(model, cell="lstm",
                                      backend="fused_q4")
        xs = jnp.zeros((4, 1, 8))
        np.testing.assert_array_equal(np.asarray(prog.sequence(xs)[0]),
                                      np.asarray(prog2.sequence(xs)[0]))

    def test_fused_q4_in_registry_lists(self):
        for cell in ("gru", "lstm"):
            assert list_backends(cell)[-1] == "fused_q4"
        assert backend_weight_bits("gru")["fused_q4"] == 4
        assert backend_weight_bits("lstm")["fused_q4"] == 4


class TestQ4Pricing:
    def _task_and_progs(self, key=0):
        task = GruTaskConfig(10, 16, 2, 2, task="regression",
                             theta_x=4 / 256, theta_h=8 / 256)
        model = init_lstm_model(jax.random.PRNGKey(key), task)
        return (task, model, quantize_delta_model(model),
                quantize_delta_model(model, bits=4))

    def test_eq7_pricing_ladder_exact(self):
        """Eq. 6/7 at matched gammas: int4 on the 64-bit bus packs K=16
        PEs and streams EXACTLY 0.5x the int8 bytes and 0.125x fp32."""
        dims = lstm_dims(10, 16, 2)
        b_q4 = dram_traffic_bytes_per_timestep(dims, 0.9, 0.8,
                                               w_weight_bits=4)
        b_q8 = dram_traffic_bytes_per_timestep(dims, 0.9, 0.8,
                                               w_weight_bits=8)
        b_fp = dram_traffic_bytes_per_timestep(dims, 0.9, 0.8,
                                               w_weight_bits=32)
        assert b_q4 == 0.5 * b_q8 == 0.125 * b_fp

    def test_engine_prices_int4_width(self):
        task, _, qprog8, qprog4 = self._task_and_progs()
        e_q4 = DeltaStreamEngine(qprog4, task)
        e_q8 = DeltaStreamEngine(qprog8, task)
        assert e_q4.accel.w_weight_bits == 4 and e_q4.accel.k_pes == 16
        assert e_q8.accel.w_weight_bits == 8 and e_q8.accel.k_pes == 8
        rng = np.random.default_rng(1)
        xs = np.cumsum(rng.normal(size=(16, 10)) * 0.1, axis=0).astype(
            np.float32)
        e_q4.step_many(xs)
        e_q8.step_many(xs)
        r_q4, r_q8 = e_q4.report(), e_q8.report()
        assert r_q4["weight_bits"] == 4 and r_q8["weight_bits"] == 8
        assert r_q4["mean_weight_bytes_per_step"] > 0
        # same-gamma comparison would be exactly 2x; firing differs only
        # by the int4-vs-int8 weight grids, so the ratio stays close to 2
        ratio = (r_q8["mean_weight_bytes_per_step"]
                 / r_q4["mean_weight_bytes_per_step"])
        assert 1.5 < ratio < 3.0

    def test_bench_bytes_map_not_truncated(self):
        """Regression for a ``bits // 8`` truncation: priced from the
        registry's widths, a 4-bit weight is 0.5 bytes, not 0, and the
        modeled bytes come out at exactly half of q8 at the same
        (matched) firing, for both cells."""
        for cell, dims in (("gru", GruDims(16, 32, 2)),
                           ("lstm", lstm_dims(16, 32, 2))):
            bits = backend_weight_bits(cell)
            assert {b: bits[b] / 8 for b in ("fused", "fused_q8",
                                             "fused_q4")} == {
                "fused": 4.0, "fused_q8": 1.0, "fused_q4": 0.5}
            b_q4, b_q8 = (dram_traffic_bytes_per_timestep(
                dims, 0.5, 0.25, w_weight_bits=bits[b])
                for b in ("fused_q4", "fused_q8"))
            assert b_q4 > 0 and b_q4 == 0.5 * b_q8

    def test_batcher_sessions_on_q4_lstm(self):
        """int4 LSTM streams recycle through batcher sessions (one
        fused_q4 tile per tick) with per-stream accounting identical to
        dedicated engines."""
        task, _, _, qprog4 = self._task_and_progs(key=2)
        eng = DeltaStreamEngine(qprog4, task, n_streams=2)
        assert eng.program is qprog4
        rep = eng.report()
        assert rep["backend"] == "fused_q4" and rep["weight_fetch"] == "tile"
        cb = GruStreamBatcher(eng)
        rng = np.random.default_rng(0)
        seqs = [rng.normal(size=(t, 10)).astype(np.float32)
                for t in (5, 9, 4, 7)]
        uids = [cb.submit(s) for s in seqs]
        done = cb.run_until_drained()
        assert sorted(r.uid for r in done) == sorted(uids)
        by_uid = {r.uid: r for r in done}
        for uid, s in zip(uids, seqs):
            solo = DeltaStreamEngine(qprog4, task)
            want = np.asarray(solo.step_many(s))
            np.testing.assert_allclose(np.stack(by_uid[uid].outputs), want,
                                       atol=1e-5)
            st = by_uid[uid].stats
            assert st["steps"] == len(s)
            assert st["gamma_dh"] == pytest.approx(
                solo.report()["gamma_dh"], abs=1e-5)
