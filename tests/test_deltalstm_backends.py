"""DeltaLSTM backend parity: fused kernel, compiled programs, serving.

The LSTM family must carry the same guarantees the GRU family earned PR by
PR: the fused single-kernel path tracks the dense reference (and, at
theta=0, the plain-LSTM oracle) in both the auto-routed jnp-ref mode and
Pallas interpret mode; ``cell="lstm"`` programs are bit-equivalent
re-spellings of the legacy kwargs with the state convention enforced; and
LSTM programs stream through ``DeltaStreamEngine`` / ``GruStreamBatcher``
sessions with correct per-stream accounting priced on the 4-gate weight
volume.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import get_backend, list_backends
from repro.core.deltalstm import (deltalstm_sequence, deltalstm_stack_step,
                                  deltalstm_step, init_deltalstm_stack_state,
                                  init_deltalstm_state, init_lstm_layer,
                                  init_lstm_stack, lstm_sequence,
                                  lstm_stack_m_init, pack_lstm_stack)
from repro.core.perf_model import estimate_stack
from repro.core.program import compile_delta_program, compile_deltagru
from repro.core.sparsity import lstm_dims
from repro.models.gru_rnn import (GruTaskConfig, init_gru_model,
                                  init_lstm_model)
from repro.serve.engine import DeltaStreamEngine, GruStreamEngine
from repro.serve.scheduler import GruStreamBatcher

# "fused" auto-routes to the jnp ref off-TPU, so the interpret=True rows
# are what actually exercise the Pallas kernel here (same convention as
# the GRU suite in test_backends.py).
KERNEL_PATHS = [("fused", {}), ("fused", {"interpret": True})]


def _stack_and_xs(key=0, i=10, h=24, layers=2, t=14, b=2, scale=0.5):
    params = init_lstm_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * scale
    return params, xs


class TestLstmRegistry:
    def test_fused_registered(self):
        assert set(("dense", "fused", "fused_q8")) <= set(
            list_backends("lstm"))

    def test_spec_fields(self):
        spec = get_backend("fused", cell="lstm")
        assert spec.m_init == "bias"
        assert spec.weight_bits == 32
        assert not spec.supports_custom_acts
        assert get_backend("dense", cell="lstm").supports_custom_acts
        q8 = get_backend("fused_q8", cell="lstm")
        assert q8.m_init == "zero" and q8.weight_bits == 8
        assert not q8.supports_custom_acts

    def test_stack_m_init_reads_registry(self):
        assert lstm_stack_m_init("fused") == "bias"
        assert lstm_stack_m_init("fused_q8") == "zero"
        with pytest.raises(ValueError, match="unknown lstm backend"):
            lstm_stack_m_init("blocksparse")


class TestLstmCrossBackendEquivalence:
    @pytest.mark.parametrize("backend,kw", KERNEL_PATHS)
    @pytest.mark.parametrize("b", [1, 4])
    def test_theta_zero_matches_lstm_oracle(self, backend, kw, b):
        """Acceptance bar: fused == plain-LSTM oracle at theta=0."""
        params, xs = _stack_and_xs(0, 14, 32, 2, 20, b)
        want = lstm_sequence(params, xs)
        got, _, _ = deltalstm_sequence(params, xs, 0.0, 0.0,
                                       backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)

    @pytest.mark.parametrize("backend,kw", KERNEL_PATHS)
    @pytest.mark.parametrize("i,h,layers,b",
                             [(14, 32, 1, 1), (40, 200, 2, 3),
                              (130, 128, 2, 2)])
    def test_dual_thresholds_match_dense(self, backend, kw, i, h, layers, b):
        """At nonzero (Θ_x, Θ_h) the fused path tracks the dense delta
        path: same deltas, same gammas, same outputs — including shapes
        that exercise multi-block grids and the x/h seam."""
        params, xs = _stack_and_xs(i + h, i, h, layers, 16, b)
        want, _, st_d = deltalstm_sequence(params, xs, 0.05, 0.1,
                                           backend="dense")
        got, _, st_k = deltalstm_sequence(params, xs, 0.05, 0.1,
                                          backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        assert float(st_k["gamma_dx"]) == pytest.approx(
            float(st_d["gamma_dx"]), abs=1e-6)
        assert float(st_k["gamma_dh"]) == pytest.approx(
            float(st_d["gamma_dh"]), abs=1e-6)

    @pytest.mark.parametrize("kw", [{}, {"interpret": True}])
    def test_single_step_matches_dense(self, kw):
        """Step-level parity incl. the cell state c (the LSTM-only state
        the GRU kernel had no analogue for)."""
        p = init_lstm_layer(jax.random.PRNGKey(3), 24, 48)
        st = init_deltalstm_state(p, (2,))
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24))
        want = deltalstm_step(p, st, x, 0.02, 0.02)
        got = deltalstm_step(p, st, x, 0.02, 0.02, backend="fused", **kw)
        np.testing.assert_allclose(np.asarray(got.h), np.asarray(want.h),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.state.c),
                                   np.asarray(want.state.c), atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.state.m),
                                   np.asarray(want.state.m), atol=1e-5)

    def test_fused_rejects_custom_activations(self):
        p = init_lstm_layer(jax.random.PRNGKey(0), 8, 16)
        st = init_deltalstm_state(p, (1,))
        with pytest.raises(ValueError, match="fused backend"):
            deltalstm_step(p, st, jnp.ones((1, 8)), 0.0, 0.0,
                           backend="fused", sigmoid=lambda z: z)

    def test_unknown_backend_rejected(self):
        p = init_lstm_layer(jax.random.PRNGKey(0), 8, 16)
        st = init_deltalstm_state(p, (1,))
        with pytest.raises(ValueError, match="unknown lstm backend"):
            deltalstm_step(p, st, jnp.ones((1, 8)), 0.0, 0.0,
                           backend="blocksparse")


class TestLstmPrograms:
    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_sequence_matches_legacy_kwargs(self, backend):
        params, xs = _stack_and_xs()
        prog = compile_delta_program(params, cell="lstm", backend=backend)
        got, _, st_p = prog.sequence(xs, 0.05, 0.1)
        want, _, st_l = deltalstm_sequence(params, xs, 0.05, 0.1,
                                           backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(st_p["gamma_dh"]) == pytest.approx(
            float(st_l["gamma_dh"]), abs=1e-6)

    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_step_matches_legacy_stack_step(self, backend):
        params, xs = _stack_and_xs(key=3)
        prog = compile_delta_program(params, cell="lstm", backend=backend)
        st_p = prog.init_state((2,))
        st_l = init_deltalstm_stack_state(params, (2,),
                                          m_init=lstm_stack_m_init(backend))
        layouts, packs = pack_lstm_stack(params, backend)
        for x in xs[:4]:
            y_p, st_p, _ = prog.step(st_p, x, 0.05, 0.1)
            y_l, st_l, _ = deltalstm_stack_step(params, st_l, x, 0.05, 0.1,
                                                backend=backend,
                                                layouts=layouts, packs=packs)
            np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_l))

    def test_program_is_a_pytree(self):
        params, xs = _stack_and_xs()
        prog = compile_delta_program(params, cell="lstm", backend="fused")
        fn = jax.jit(lambda p, xs: p.sequence(
            xs, 0.05, 0.1, collect_sparsity=False)[0])
        got = fn(prog, xs)
        want, _, _ = prog.sequence(xs, 0.05, 0.1, collect_sparsity=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)

    def test_cross_cell_state_rejected(self):
        """A GRU program's state cannot run through an LSTM program (and
        vice versa) — the cell tag is checked before the backend tag."""
        lstm_params, xs = _stack_and_xs()
        gru_prog = compile_deltagru(
            init_gru_model(jax.random.PRNGKey(0),
                           GruTaskConfig(10, 24, 2, 3)), backend="fused")
        lstm_prog = compile_delta_program(lstm_params, cell="lstm",
                                          backend="fused")
        with pytest.raises(ValueError, match="cell"):
            lstm_prog.step(gru_prog.init_state((2,)), xs[0])
        with pytest.raises(ValueError, match="cell"):
            gru_prog.step(lstm_prog.init_state((2,)), xs[0])

    def test_model_dict_compile_carries_head(self):
        task = GruTaskConfig(8, 16, 2, 3, task="regression")
        model = init_lstm_model(jax.random.PRNGKey(0), task)
        prog = compile_delta_program(model, cell="lstm", backend="fused")
        assert prog.head is not None and prog.cell == "lstm"
        ys, _, _ = prog.sequence(jnp.zeros((4, 1, 8)))
        assert prog.apply_head(ys).shape == (4, 1, 3)

    def test_wrong_cell_for_dict_rejected(self):
        task = GruTaskConfig(8, 16, 1, 3)
        model = init_lstm_model(jax.random.PRNGKey(0), task)
        with pytest.raises(ValueError, match="lstm"):
            compile_delta_program(model, cell="gru", backend="fused")


class TestLstmStreaming:
    def _task_model(self, n_layers=2, key=0):
        task = GruTaskConfig(8, 16, n_layers, 3, task="regression",
                             theta_x=0.05, theta_h=0.05)
        return task, init_lstm_model(jax.random.PRNGKey(key), task)

    def test_engine_runs_lstm_program(self):
        task, model = self._task_model()
        prog = compile_delta_program(model, cell="lstm", backend="fused")
        eng = DeltaStreamEngine(prog, task)
        assert eng.cell == "lstm" and eng.dims.gates == 4
        xs = np.cumsum(np.random.default_rng(0).normal(size=(12, 8)) * 0.2,
                       axis=0).astype(np.float32)
        outs = np.asarray(eng.step_many(xs))
        assert outs.shape == (12, 3)
        # outputs == program.sequence + head, exactly
        ys, _, _ = prog.sequence(jnp.asarray(xs)[:, None, :], 0.05, 0.05)
        want = np.asarray(prog.apply_head(ys))[:, 0]
        np.testing.assert_allclose(outs, want, atol=1e-6)

    def test_legacy_dict_shim_infers_lstm(self):
        task, model = self._task_model()
        eng = GruStreamEngine(model, task)        # alias + dict shim
        assert eng.cell == "lstm" and eng.backend == "fused"
        eng.step(np.zeros(8, np.float32))
        assert eng.report()["cell"] == "lstm"

    def test_accounting_prices_four_gate_volume(self):
        """The Eq. 7 terms must price the LSTM's 4-gate weight volume: the
        engine's latency/byte figures reproduce estimate_stack on
        lstm_dims (4/3x the GRU figures at identical firing)."""
        task, model = self._task_model()
        eng = DeltaStreamEngine(
            compile_delta_program(model, cell="lstm", backend="dense"), task)
        xs = np.cumsum(np.random.default_rng(1).normal(size=(20, 8)) * 0.2,
                       axis=0).astype(np.float32)
        eng.step_many(xs)
        rep = eng.report()
        dims = lstm_dims(task.input_size, task.hidden_size, task.num_layers)
        est = estimate_stack(dims, rep["gamma_dx"], rep["gamma_dh"],
                             eng.accel)
        assert rep["mean_est_latency_us"] == pytest.approx(
            est.latency_s * 1e6, rel=1e-4)
        from repro.core.sparsity import GruDims
        est3 = estimate_stack(
            GruDims(task.input_size, task.hidden_size, task.num_layers),
            rep["gamma_dx"], rep["gamma_dh"], eng.accel)
        assert est.latency_s == pytest.approx(est3.latency_s * 4 / 3,
                                              rel=1e-6)

    def test_stream_sessions_and_batcher_parity(self):
        """LSTM streams recycle through batcher sessions with per-stream
        accounting identical to dedicated single-stream engines."""
        task, model = self._task_model(key=2)
        prog = compile_delta_program(model, cell="lstm", backend="fused")
        eng = DeltaStreamEngine(prog, task, n_streams=2)
        assert eng.program is prog and eng.report()["backend"] == "fused"
        cb = GruStreamBatcher(eng)
        rng = np.random.default_rng(0)
        seqs = [rng.normal(size=(t, 8)).astype(np.float32)
                for t in (5, 9, 4, 7)]
        uids = [cb.submit(s) for s in seqs]
        done = cb.run_until_drained()
        assert sorted(r.uid for r in done) == sorted(uids)
        by_uid = {r.uid: r for r in done}
        for uid, s in zip(uids, seqs):
            solo = DeltaStreamEngine(prog, task)
            want = np.asarray(solo.step_many(s))
            np.testing.assert_allclose(np.stack(by_uid[uid].outputs), want,
                                       atol=1e-5)
            st = by_uid[uid].stats
            assert st["steps"] == len(s)
            assert st["gamma_dh"] == pytest.approx(
                solo.report()["gamma_dh"], abs=1e-5)
