"""Quantized DeltaLSTM backend (``fused_q8``, cell="lstm") equivalence.

The LSTM instantiation of the cell-agnostic q8 core
(:mod:`repro.kernels.delta_q8`) must *bit-match* an independently written
fake-quant fixed-point reference built from the :mod:`repro.quant`
primitives (same Qm.n grids): int8 per-gate-row weight codes over the
``[4, Hp, Ip+Hk]`` volume, Q8.8 activation grid, unscaled code-domain
delta memories, bias + dequant at the activation stage, Q8.8 -> Q1.4 LUT
i/f/g/o gates, and the cell state ``c`` on the *saturating* Q8.8
accumulator grid. Because the code-domain accumulation is exact in fp32
for on-grid deltas, every summation order gives the same bits — the
Pallas kernel, its jnp oracle and the reference below must agree exactly,
not approximately.

Also covers the fixed-point LSTM edge cases the issue calls out: Q8.8
saturation of ``c`` under long sequences (clip, never wrap), exporter
idempotency, the GRU-spelling rejection of LSTM model dicts, and
engine/batcher session parity on quantized LSTM programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import list_backends
from repro.core.deltalstm import (LstmLayerParams, deltalstm_sequence,
                                  deltalstm_step, init_deltalstm_state,
                                  init_lstm_stack, lstm_stack_m_init)
from repro.core.program import compile_delta_program
from repro.models.gru_rnn import (GruTaskConfig, init_gru_model,
                                  init_lstm_model)
from repro.quant.export import (quantize_delta_model, quantize_delta_stack,
                                quantize_gru_model)
from repro.quant.fake_quant import ACT_Q88, QFormat, quantize
from repro.serve.engine import DeltaStreamEngine
from repro.serve.scheduler import GruStreamBatcher

LUT_Q14 = QFormat(1, 4)


def _stack_and_xs(key, i, h, layers, t, b, scale=0.5):
    params = init_lstm_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * scale
    return params, xs


def _fake_quant_lstm_reference(layouts, xs, theta_x, theta_h):
    """Independent fixed-point DeltaLSTM oracle (python loop, quant/ grids).

    Works directly on the exporter's int8 codes + scales; mirrors the
    declared semantics, not the kernel's code, so it catches packing and
    kernel bugs alike. Per-gate matmuls are a *different* summation order
    than the kernel's block walk — intentionally: the code-domain
    accumulator makes every order bit-identical.
    """
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
            # Eq. 2 dual-threshold delta encoding on the Q8.8 grid
            raw_x = inp - xhats[li]
            fired_x = jnp.abs(raw_x) >= theta_x
            dx = jnp.where(fired_x, raw_x, 0.0)
            xhats[li] = jnp.where(fired_x, inp, xhats[li])
            raw_h = hs[li] - hhats[li]
            fired_h = jnp.abs(raw_h) >= theta_h
            dh = jnp.where(fired_h, raw_h, 0.0)
            hhats[li] = jnp.where(fired_h, hs[li], hhats[li])
            # code-domain MxV accumulate, one matmul per gate
            codes = lay.w_q.astype(jnp.float32)
            cx = codes[:, :h_dim, :i_dim]
            ch = codes[:, :h_dim, lay.ip:lay.ip + h_dim]
            m = ms[li].reshape(b, 4, h_dim)
            mg = [m[:, g] + (dx @ cx[g].T + dh @ ch[g].T) for g in range(4)]
            ms[li] = jnp.stack(mg, 1).reshape(b, -1)
            # activation stage: bias + dequant, Q8.8-in / Q1.4-out LUTs
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
            # saturating Q8.8 cell-state accumulator
            cs[li] = quantize(gf * cs[li] + gi * gg, ACT_Q88)
            hs[li] = quantize(
                go * quantize(jnp.tanh(cs[li]), LUT_Q14), ACT_Q88)
            inp = hs[li]
        ys.append(inp)
    return jnp.stack(ys)


def _plain_quant_lstm_reference(layouts, xs):
    """Quantized *plain* LSTM on the same grids (no deltas, no memories)."""
    t_len, b, _ = xs.shape
    hs = [jnp.zeros((b, lay.hidden_size)) for lay in layouts]
    cs = [jnp.zeros((b, lay.hidden_size)) for lay in layouts]
    ys = []
    for t in range(t_len):
        inp = quantize(xs[t], ACT_Q88)
        for li, lay in enumerate(layouts):
            h_dim, i_dim = lay.hidden_size, lay.input_size
            codes = lay.w_q.astype(jnp.float32)
            cx = codes[:, :h_dim, :i_dim]
            ch = codes[:, :h_dim, lay.ip:lay.ip + h_dim]
            s = lay.scales[:, :h_dim]
            b4 = lay.b4[:, :h_dim]
            acc = [inp @ cx[g].T + hs[li] @ ch[g].T for g in range(4)]
            gi = quantize(jax.nn.sigmoid(
                quantize(b4[0] + acc[0] * s[0], ACT_Q88)), LUT_Q14)
            gf = quantize(jax.nn.sigmoid(
                quantize(b4[1] + acc[1] * s[1], ACT_Q88)), LUT_Q14)
            gg = quantize(jnp.tanh(
                quantize(b4[2] + acc[2] * s[2], ACT_Q88)), LUT_Q14)
            go = quantize(jax.nn.sigmoid(
                quantize(b4[3] + acc[3] * s[3], ACT_Q88)), LUT_Q14)
            cs[li] = quantize(gf * cs[li] + gi * gg, ACT_Q88)
            hs[li] = quantize(
                go * quantize(jnp.tanh(cs[li]), LUT_Q14), ACT_Q88)
            inp = hs[li]
        ys.append(inp)
    return jnp.stack(ys)


class TestLstmFusedQ8BitMatch:
    # interpret=True exercises the actual Pallas kernel (the default route
    # off-TPU is the bit-identical jnp oracle).
    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    @pytest.mark.parametrize("i,h,layers,b",
                             [(10, 24, 2, 2), (14, 32, 1, 1)])
    def test_bitmatches_fake_quant_reference(self, kw, i, h, layers, b):
        """Acceptance bar: LSTM fused_q8 == the fake-quant fixed-point
        oracle, bit for bit, at nonzero dual thresholds."""
        params, xs = _stack_and_xs(i + h, i, h, layers, 12, b)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        want = _fake_quant_lstm_reference(layouts, xs, 6 / 256, 12 / 256)
        got, _, _ = deltalstm_sequence(qparams, xs, 6 / 256, 12 / 256,
                                       backend="fused_q8", layouts=layouts,
                                       **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    def test_theta_zero_is_quantized_plain_lstm(self, kw):
        """At theta=0 the code-domain delta memories telescope exactly, so
        fused_q8 IS the quantized plain LSTM (bit-identical)."""
        params, xs = _stack_and_xs(3, 12, 16, 2, 10, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        want = _plain_quant_lstm_reference(layouts, xs)
        got, _, _ = deltalstm_sequence(qparams, xs, 0.0, 0.0,
                                       backend="fused_q8", layouts=layouts,
                                       **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_outputs_and_cell_state_on_q88_grid(self):
        params, xs = _stack_and_xs(5, 8, 16, 1, 8, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        ys, final, _ = deltalstm_sequence(qparams, xs, 0.02, 0.02,
                                          backend="fused_q8",
                                          layouts=layouts)
        for arr in (np.asarray(ys), np.asarray(final.layers[0].c)):
            scaled = arr * 256.0
            np.testing.assert_allclose(scaled, np.round(scaled), atol=1e-4)

    def test_packed_weights_are_int8_four_gates(self):
        params, _ = _stack_and_xs(0, 8, 16, 1, 4, 1)
        _, layouts = quantize_delta_stack(params, cell="lstm")
        for lay in layouts:
            assert lay.gates == 4
            assert lay.w_q.dtype == jnp.int8          # the HBM operand
            assert lay.w_q.shape[0] == 4
            assert lay.scales.shape == (4, lay.hp)
            assert lay.b4.shape == (4, lay.hp)
            assert int(jnp.max(jnp.abs(lay.w_q.astype(jnp.int32)))) <= 127

    def test_tracks_fp32_dense_within_quant_budget(self):
        params, xs = _stack_and_xs(7, 12, 24, 2, 16, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        want, _, _ = deltalstm_sequence(params, xs, 0.02, 0.02)
        got, _, _ = deltalstm_sequence(qparams, xs, 0.02, 0.02,
                                       backend="fused_q8", layouts=layouts)
        assert float(jnp.max(jnp.abs(got - want))) < 0.25

    def test_rejects_custom_activations_and_matvec(self):
        p = init_lstm_stack(jax.random.PRNGKey(0), 8, 16, 1)[0]
        st = init_deltalstm_state(p, (1,), m_init="zero")
        x = jnp.ones((1, 8))
        with pytest.raises(ValueError, match="fused_q8"):
            deltalstm_step(p, st, x, 0.0, 0.0, backend="fused_q8",
                           sigmoid=lambda z: z)
        with pytest.raises(ValueError, match="matvec"):
            deltalstm_step(p, st, x, 0.0, 0.0, backend="fused_q8",
                           matvec=lambda w, v: v @ w.T)


class TestCellStateSaturation:
    """The issue's long-sequence edge case: a cell state driven past the
    Q8.8 rail must CLIP there (the int16 accumulator saturates), never
    wrap to the negative rail."""

    def _runaway_params(self, h=8, i=4):
        """Zero weights, biases engineered so every step adds +1 to c:
        i = f = g = 1 (saturated gates), o = 0.5."""
        b = jnp.concatenate([
            8.0 * jnp.ones((h,)),    # b_i: sigmoid->1.0 on the Q1.4 LUT
            8.0 * jnp.ones((h,)),    # b_f: 1.0 -> c never decays
            8.0 * jnp.ones((h,)),    # b_g: tanh->1.0
            jnp.zeros((h,)),         # b_o: 0.5
        ])
        return LstmLayerParams(w_x=jnp.zeros((4 * h, i)),
                               w_h=jnp.zeros((4 * h, h)), b=b)

    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    def test_cell_state_clips_at_act_max(self, kw):
        params = [self._runaway_params()]
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        act_max = layouts[0].act_max
        t = 300                      # c grows ~ +1/step; rail is ~256
        xs = jnp.zeros((t, 1, 4))
        _, final, _ = deltalstm_sequence(qparams, xs, 0.0, 0.0,
                                         backend="fused_q8",
                                         layouts=layouts, **kw)
        c = np.asarray(final.layers[0].c)
        # saturated exactly at the rail — a wrapping accumulator would
        # have swung to the negative rail instead
        np.testing.assert_array_equal(c, np.full_like(c, act_max))

    def test_prefix_monotone_then_flat(self):
        """c rises monotonically to the rail and stays; h stays finite and
        on-grid the whole way."""
        params = [self._runaway_params()]
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        act_max = layouts[0].act_max
        xs = jnp.zeros((300, 1, 4))
        prog = compile_delta_program(qparams, cell="lstm",
                                     backend="fused_q8",
                                     layouts=tuple(layouts))
        state = prog.init_state((1,))
        prev_c = 0.0
        for ti in range(300):
            y, state, _ = prog.step(state, xs[ti])
            c = float(state.stack.layers[0].c[0, 0])
            assert c >= prev_c                       # clip, not wrap
            assert np.isfinite(np.asarray(y)).all()
            prev_c = c
        assert prev_c == act_max


class TestLstmExporter:
    def test_quantization_idempotent(self):
        """Re-exporting the fake-quant view reproduces the same codes."""
        params, _ = _stack_and_xs(1, 8, 16, 2, 4, 1)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        _, layouts2 = quantize_delta_stack(qparams, cell="lstm")
        for a, b in zip(layouts, layouts2):
            np.testing.assert_array_equal(np.asarray(a.w_q),
                                          np.asarray(b.w_q))
            np.testing.assert_array_equal(np.asarray(a.b4),
                                          np.asarray(b.b4))

    def test_gru_spelling_rejects_lstm_dict(self):
        """The historical GRU exporter must refuse a 4-gate model dict
        instead of mis-packing 3-of-4 gate rows."""
        task = GruTaskConfig(8, 16, 1, 3)
        model = init_lstm_model(jax.random.PRNGKey(0), task)
        with pytest.raises(ValueError, match="quantize_delta_model"):
            quantize_gru_model(model)

    def test_wrong_cell_stack_rejected(self):
        """A 4-gate stack quantized as cell='gru' (and vice versa) is a
        loud shape error, not a silent mis-pack."""
        lstm_stack = init_lstm_stack(jax.random.PRNGKey(0), 8, 16, 1)
        with pytest.raises(ValueError, match="wrong cell family"):
            quantize_delta_stack(lstm_stack, cell="gru")
        gru_model = init_gru_model(jax.random.PRNGKey(0),
                                   GruTaskConfig(8, 16, 1, 3))
        with pytest.raises(ValueError, match="wrong cell family"):
            quantize_delta_stack(gru_model["gru"], cell="lstm")

    def test_quantize_delta_model_infers_cell(self):
        task = GruTaskConfig(8, 16, 2, 3, task="regression")
        model = init_lstm_model(jax.random.PRNGKey(1), task)
        prog = quantize_delta_model(model)
        assert prog.cell == "lstm" and prog.backend == "fused_q8"
        assert prog.head is not None
        assert all(lay.gates == 4 for lay in prog.layouts)
        # identical to the compile_delta_program spelling, bit for bit
        prog2 = compile_delta_program(model, cell="lstm",
                                      backend="fused_q8")
        xs = jnp.zeros((4, 1, 8))
        ys1, _, _ = prog.sequence(xs)
        ys2, _, _ = prog2.sequence(xs)
        np.testing.assert_array_equal(np.asarray(ys1), np.asarray(ys2))

    def test_fused_q8_in_registry_lists(self):
        assert "fused_q8" in list_backends("lstm")
        assert lstm_stack_m_init("fused_q8") == "zero"
        # both cells register the same kernels, once each
        assert list_backends("lstm") == list_backends("gru")


class TestLstmQ8Programs:
    def test_sequence_matches_legacy_kwargs(self):
        params, xs = _stack_and_xs(2, 10, 24, 2, 14, 2)
        qparams, layouts = quantize_delta_stack(params, cell="lstm")
        prog = compile_delta_program(params, cell="lstm",
                                     backend="fused_q8")
        got, _, st_p = prog.sequence(xs, 0.02, 0.05)
        want, _, st_l = deltalstm_sequence(qparams, xs, 0.02, 0.05,
                                           backend="fused_q8",
                                           layouts=layouts)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(st_p["gamma_dh"]) == pytest.approx(
            float(st_l["gamma_dh"]), abs=1e-6)

    def test_state_convention_enforced(self):
        """A bias-convention (fused) state cannot run through a fused_q8
        program — the m_init mismatch would silently double-count the
        bias through the dequant scale."""
        params, xs = _stack_and_xs(4, 8, 16, 1, 4, 1)
        qprog = compile_delta_program(params, cell="lstm",
                                      backend="fused_q8")
        fprog = compile_delta_program(params, cell="lstm", backend="fused")
        with pytest.raises(ValueError, match="m_init"):
            qprog.step(fprog.init_state((1,)), xs[0])


class TestLstmQ8Engine:
    def _task_and_prog(self, key=0):
        task = GruTaskConfig(10, 16, 2, 2, task="regression",
                             theta_x=4 / 256, theta_h=8 / 256)
        model = init_lstm_model(jax.random.PRNGKey(key), task)
        return task, model, quantize_delta_model(model)

    def test_engine_stats_parity_on_quantized_lstm(self):
        """step loop == step_many on a quantized LSTM program, and the
        engine's gammas match the sequence entry point's."""
        task, _, qprog = self._task_and_prog()
        rng = np.random.default_rng(0)
        xs = np.cumsum(rng.normal(size=(24, 10)) * 0.1, axis=0).astype(
            np.float32)
        e1 = DeltaStreamEngine(qprog, task)
        outs1 = np.stack([np.asarray(e1.step(x)) for x in xs])
        e2 = DeltaStreamEngine(qprog, task)
        outs2 = np.asarray(e2.step_many(xs))
        np.testing.assert_array_equal(outs1, outs2)
        r1, r2 = e1.report(), e2.report()
        for k in ("steps", "gamma_dx", "gamma_dh", "mean_est_latency_us",
                  "mean_weight_bytes_per_step"):
            assert r1[k] == pytest.approx(r2[k], rel=1e-6)
        _, _, st = qprog.sequence(jnp.asarray(xs)[:, None, :], task.theta_x,
                                  task.theta_h)
        assert r1["gamma_dx"] == pytest.approx(float(st["gamma_dx"]),
                                               abs=1e-5)
        assert r1["gamma_dh"] == pytest.approx(float(st["gamma_dh"]),
                                               abs=1e-5)

    def test_int8_weight_pricing_on_four_gates(self):
        """Eq. 6/7 bytes-per-op term for the quantized LSTM: int8 on the
        64-bit bus keeps K=8 PEs (the paper's operating point) while the
        fp32 fused path drops to K=2 — exactly 0.25x the bytes at matched
        firing fractions, on the 4-gate volume."""
        from repro.core.perf_model import dram_traffic_bytes_per_timestep
        from repro.core.sparsity import lstm_dims
        task, model, qprog = self._task_and_prog()
        e_q8 = DeltaStreamEngine(qprog, task)
        e_fp = DeltaStreamEngine(
            compile_delta_program(model, cell="lstm", backend="fused"),
            task)
        assert e_q8.accel.w_weight_bits == 8 and e_q8.accel.k_pes == 8
        assert e_fp.accel.w_weight_bits == 32 and e_fp.accel.k_pes == 2
        assert e_q8.dims.gates == 4
        # the model itself: exactly 0.25x at matched gammas
        dims = lstm_dims(task.input_size, task.hidden_size,
                         task.num_layers)
        b_q8 = dram_traffic_bytes_per_timestep(dims, 0.9, 0.8,
                                               w_weight_bits=8)
        b_fp = dram_traffic_bytes_per_timestep(dims, 0.9, 0.8,
                                               w_weight_bits=32)
        assert b_q8 == 0.25 * b_fp
        # end-to-end: firing differs only by the Q8.8 input rounding, so
        # the measured ratio stays close to 4
        rng = np.random.default_rng(1)
        xs = np.cumsum(rng.normal(size=(16, 10)) * 0.1, axis=0).astype(
            np.float32)
        e_q8.step_many(xs)
        e_fp.step_many(xs)
        r_q8, r_fp = e_q8.report(), e_fp.report()
        assert r_q8["weight_bits"] == 8 and r_fp["weight_bits"] == 32
        assert r_q8["mean_weight_bytes_per_step"] > 0
        ratio = (r_fp["mean_weight_bytes_per_step"]
                 / r_q8["mean_weight_bytes_per_step"])
        assert 2.0 < ratio < 8.0

    def test_batcher_sessions_on_quantized_lstm(self):
        """Quantized LSTM streams recycle through batcher sessions with
        per-stream accounting identical to dedicated engines."""
        task, _, qprog = self._task_and_prog(key=2)
        eng = DeltaStreamEngine(qprog, task, n_streams=2)
        assert eng.program is qprog
        assert eng.report()["backend"] == "fused_q8"
        cb = GruStreamBatcher(eng)
        rng = np.random.default_rng(0)
        seqs = [rng.normal(size=(t, 10)).astype(np.float32)
                for t in (5, 9, 4, 7)]
        uids = [cb.submit(s) for s in seqs]
        done = cb.run_until_drained()
        assert sorted(r.uid for r in done) == sorted(uids)
        by_uid = {r.uid: r for r in done}
        for uid, s in zip(uids, seqs):
            solo = DeltaStreamEngine(qprog, task)
            want = np.asarray(solo.step_many(s))
            # the delta-RNN states are on-grid (bit-exact across batch
            # shapes); the fp32 head matmul may differ in the last ulp
            # between the batched and solo engines
            np.testing.assert_allclose(np.stack(by_uid[uid].outputs), want,
                                       atol=1e-5)
            st = by_uid[uid].stats
            assert st["steps"] == len(s)
            assert st["gamma_dh"] == pytest.approx(
                solo.report()["gamma_dh"], abs=1e-5)
