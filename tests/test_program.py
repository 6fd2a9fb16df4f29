"""Compiled-program API: registry, compile_deltagru, sessions, batcher.

The compile-then-stream split must be a pure re-spelling of the legacy
``backend=`` / ``layouts=`` / ``packs=`` knobs — bit-identical outputs per
backend — while making the historical silent-corruption trap (an
``m_init``-mismatched state) unrepresentable, and the per-stream session
API must recycle slots without perturbing concurrent streams.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import (BackendSpec, get_backend, list_backends,
                                 register_backend, unregister_backend)
from repro.core.deltagru import (deltagru_sequence, deltagru_stack_step,
                                 init_deltagru_stack_state, init_gru_stack,
                                 stack_m_init)
from repro.core.program import (DeltaGruProgram, DeltaGruProgramState,
                                compile_deltagru)
from repro.models.gru_rnn import (GruTaskConfig, gru_model_forward,
                                  init_gru_model)
from repro.quant.export import quantize_gru_model
from repro.serve.engine import GruStreamEngine
from repro.serve.scheduler import GruStreamBatcher

ALL_BACKENDS = ("dense", "fused", "fused_q8", "fused_q4")


def _stack_and_xs(key=0, i=10, h=24, layers=2, t=14, b=2):
    params = init_gru_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * 0.5
    return params, xs


class TestRegistry:
    def test_builtin_backends_registered(self):
        # one registration per kernel: a tile of streams is the call's
        # shape, not a backend name
        assert list_backends("gru") == ALL_BACKENDS
        assert list_backends("lstm") == ALL_BACKENDS

    def test_spec_fields(self):
        assert get_backend("fused_q8").m_init == "zero"
        assert get_backend("fused_q8").weight_bits == 8
        for be in ("dense", "fused"):
            assert get_backend(be).m_init == "bias"
            assert get_backend(be).weight_bits == 32
        assert not get_backend("fused").supports_custom_acts
        assert get_backend("dense").supports_custom_acts

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown gru backend"):
            get_backend("spmd")
        with pytest.raises(ValueError, match="unknown lstm backend"):
            get_backend("blocksparse", cell="lstm")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_backend(BackendSpec(
                name="dense", cell="gru", pack=lambda p, b: (p, None, None),
                step=lambda *a, **k: None))

    def test_new_cell_scoped_registration(self):
        """Same name, different cell: no collision (registry is keyed on
        (cell, name)); cleanup restores the registry."""
        spec = BackendSpec(name="dense", cell="testcell",
                           pack=lambda p, b: (p, None, None),
                           step=lambda *a, **k: None)
        register_backend(spec)
        try:
            assert get_backend("dense", cell="testcell") is spec
        finally:
            unregister_backend("dense", cell="testcell")

    def test_stack_m_init_reads_registry(self):
        assert stack_m_init("fused_q8") == "zero"
        assert stack_m_init("fused") == "bias"
        with pytest.raises(ValueError, match="backend"):
            stack_m_init("nope")


class TestCompileEquivalence:
    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    def test_sequence_matches_legacy_kwargs(self, backend):
        """program.sequence == deltagru_sequence(backend=...) bit-for-bit."""
        params, xs = _stack_and_xs()
        prog = compile_deltagru(params, backend=backend)
        got, _, st_p = prog.sequence(xs, 0.05, 0.1)
        want, _, st_l = deltagru_sequence(params, xs, 0.05, 0.1,
                                          backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(st_p["gamma_dh"]) == pytest.approx(
            float(st_l["gamma_dh"]), abs=1e-6)

    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    def test_step_matches_legacy_stack_step(self, backend):
        """program.step == deltagru_stack_step under the legacy knobs."""
        params, xs = _stack_and_xs(key=3)
        prog = compile_deltagru(params, backend=backend)
        st_p = prog.init_state((2,))
        st_l = init_deltagru_stack_state(params, (2,),
                                         m_init=stack_m_init(backend))
        from repro.core.deltagru import pack_stack
        layouts, packs = pack_stack(params, backend)
        for x in xs[:4]:
            y_p, st_p, _ = prog.step(st_p, x, 0.05, 0.1)
            y_l, st_l, _ = deltagru_stack_step(params, st_l, x, 0.05, 0.1,
                                               backend=backend,
                                               layouts=layouts, packs=packs)
            np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_l))

    def test_program_is_a_pytree(self):
        """Programs pass through jit as arguments (layers/layouts are
        leaves, backend is static)."""
        params, xs = _stack_and_xs()
        for backend in ("fused", "fused_q8", "fused_q4"):
            prog = compile_deltagru(params, backend=backend)
            fn = jax.jit(lambda p, xs: p.sequence(
                xs, 0.05, 0.1, collect_sparsity=False)[0])
            got = fn(prog, xs)
            want, _, _ = prog.sequence(xs, 0.05, 0.1, collect_sparsity=False)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)

    def test_model_forward_program_kwarg(self):
        """gru_model_forward(program=) == the legacy backend= path."""
        task = GruTaskConfig(8, 16, 2, 3, theta_x=0.05, theta_h=0.05)
        model = init_gru_model(jax.random.PRNGKey(0), task)
        xs = jax.random.normal(jax.random.PRNGKey(1), (10, 2, 8)) * 0.5
        want, _ = gru_model_forward(model, task, xs, backend="fused")
        got, _ = gru_model_forward(model, task, xs,
                                   program=compile_deltagru(model,
                                                            backend="fused"))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_model_forward_rejects_conflicting_kwargs(self):
        """Legacy knobs alongside program= raise (no silent knob drift)."""
        task = GruTaskConfig(8, 16, 1, 3, theta_x=0.05, theta_h=0.05)
        model = init_gru_model(jax.random.PRNGKey(0), task)
        xs = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8)) * 0.5
        prog = compile_deltagru(model, backend="fused_q8")
        with pytest.raises(ValueError, match="conflict"):
            gru_model_forward(model, task, xs, backend="dense", program=prog)


class TestStateConvention:
    def test_init_state_conventions(self):
        """fp32 programs fold biases into M; fused_q8 starts at zero."""
        params, _ = _stack_and_xs()
        params = [p._replace(b=p.b + 0.25) for p in params]  # nonzero bias
        m_fused = compile_deltagru(params, "fused").init_state((1,))
        m_q8 = compile_deltagru(params, "fused_q8").init_state((1,))
        h = params[0].hidden_size
        want_m0 = np.concatenate([np.full(3 * h, 0.25), np.zeros(h)])
        np.testing.assert_allclose(
            np.asarray(m_fused.stack.layers[0].m[0]), want_m0, atol=1e-6)
        assert not np.any(np.asarray(m_q8.stack.layers[0].m))

    def test_mismatched_state_raises(self):
        params, xs = _stack_and_xs()
        p_fused = compile_deltagru(params, "fused")
        p_q8 = compile_deltagru(params, "fused_q8")
        state = p_fused.init_state((2,))
        with pytest.raises(ValueError, match="m_init"):
            p_q8.step(state, xs[0])
        with pytest.raises(ValueError, match="m_init"):
            p_q8.sequence(xs, init_state=state)

    def test_foreign_state_raises(self):
        """A raw stack state (no convention tag) is rejected outright."""
        params, xs = _stack_and_xs()
        prog = compile_deltagru(params, "fused")
        raw = init_deltagru_stack_state(params, (2,))
        with pytest.raises(TypeError, match="init_state"):
            prog.step(raw, xs[0])

    def test_same_backend_states_interchange(self):
        """States from two same-backend programs interchange (the tag is
        the convention, not the identity)."""
        params, xs = _stack_and_xs()
        p1 = compile_deltagru(params, "fused")
        p2 = compile_deltagru(params, "fused")
        y, _, _ = p2.step(p1.init_state((2,)), xs[0])
        assert np.all(np.isfinite(np.asarray(y)))


class TestEngineShim:
    def _task_model(self):
        task = GruTaskConfig(8, 16, 2, 2, task="regression",
                             theta_x=0.05, theta_h=0.05)
        return task, init_gru_model(jax.random.PRNGKey(0), task)

    def test_program_and_legacy_kwargs_build_same_engine(self):
        task, model = self._task_model()
        xs = np.cumsum(np.random.default_rng(0).normal(size=(12, 8)) * 0.2,
                       axis=0).astype(np.float32)
        e_legacy = GruStreamEngine(model, task, backend="fused")
        e_prog = GruStreamEngine(compile_deltagru(model, "fused"), task)
        np.testing.assert_array_equal(np.asarray(e_legacy.step_many(xs)),
                                      np.asarray(e_prog.step_many(xs)))
        r1, r2 = e_legacy.report(), e_prog.report()
        assert r1 == r2

    def test_conflicting_kwargs_rejected(self):
        task, model = self._task_model()
        prog = compile_deltagru(model, "fused")
        with pytest.raises(ValueError, match="conflicts"):
            GruStreamEngine(prog, task, backend="fused_q8")
        with pytest.raises(ValueError, match="layouts"):
            GruStreamEngine(prog, task, layouts=prog.layouts)

    def test_headless_program_rejected(self):
        task, model = self._task_model()
        bare = compile_deltagru(model["gru"], "fused")   # no head
        with pytest.raises(ValueError, match="head"):
            GruStreamEngine(bare, task)

    def test_quantized_program_end_to_end(self):
        task, model = self._task_model()
        qprog = quantize_gru_model(model)
        eng = GruStreamEngine(qprog, task)
        assert eng.backend == "fused_q8"
        assert eng.accel.w_weight_bits == 8
        eng.step(np.zeros(8, np.float32))
        assert eng.report()["steps"] == 1

    def test_stats_carry_w_bytes_single_sync(self):
        """StreamStats materializes w_bytes with the rest of the carry:
        report()'s bytes figure comes from stats, not a second device
        read."""
        task, model = self._task_model()
        eng = GruStreamEngine(model, task)
        xs = np.cumsum(np.random.default_rng(1).normal(size=(9, 8)) * 0.2,
                       axis=0).astype(np.float32)
        eng.step_many(xs)
        s = eng.stats
        assert s.w_bytes > 0
        assert eng.report()["mean_weight_bytes_per_step"] == pytest.approx(
            s.w_bytes / s.steps)


class TestStreamSessions:
    def _engine(self, n=3, key=2):
        task = GruTaskConfig(8, 16, 2, 3, task="regression",
                             theta_x=0.05, theta_h=0.05)
        params = init_gru_model(jax.random.PRNGKey(key), task)
        return GruStreamEngine(params, task, n_streams=n), task, params

    def test_open_close_recycles_slots(self):
        eng, _, _ = self._engine(n=2)
        a, b = eng.open_stream(), eng.open_stream()
        assert (a, b) == (0, 1) and eng.free_streams == []
        with pytest.raises(RuntimeError, match="busy"):
            eng.open_stream()
        eng.step(np.zeros((2, 8), np.float32))
        rep = eng.close_stream(b)
        assert rep["steps"] == 1 and eng.free_streams == [b]
        assert eng.open_stream() == b
        with pytest.raises(ValueError, match="not open"):
            eng.close_stream(5)

    def test_masked_reset_isolates_streams(self):
        """Opening/closing slot B mid-flight must not perturb slot A."""
        eng, task, params = self._engine(n=2)
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(12, 2, 8)).astype(np.float32)
        a = eng.open_stream()
        eng.step_many(xs[:4])
        b = eng.open_stream()            # masked reset of slot 1 only
        eng.step_many(xs[4:8])
        eng.close_stream(b)
        b2 = eng.open_stream()           # recycle it again
        eng.step_many(xs[8:])
        got_a = eng.close_stream(a)
        # dedicated engine fed the same slot-A frames
        solo = GruStreamEngine(params, task)
        solo.step_many(xs[:, a])
        want = solo.report()
        assert got_a["steps"] == 12
        assert got_a["gamma_dh"] == pytest.approx(want["gamma_dh"], abs=1e-5)
        assert got_a["mean_est_latency_us"] == pytest.approx(
            want["mean_est_latency_us"], rel=1e-4)

    def test_engine_stats_survive_session_churn(self):
        """Engine-lifetime stats/report() stay exact however many sessions
        open/close: the masked per-slot reset zeroes only the per-stream
        accumulators, never the lifetime aggregates."""
        eng, task, params = self._engine(n=2)
        rng = np.random.default_rng(5)
        xs = rng.normal(size=(8, 2, 8)).astype(np.float32)
        eng.step_many(xs)
        before = eng.stats
        sid = eng.open_stream()          # zeroes slot accumulators only
        after = eng.stats
        assert after.fired_h == pytest.approx(before.fired_h, abs=1e-7)
        assert after.est_latency_s == pytest.approx(before.est_latency_s)
        assert after.w_bytes == pytest.approx(before.w_bytes)
        eng.close_stream(sid)
        # and the aggregates still match a churn-free engine's accounting
        solo = GruStreamEngine(params, task, n_streams=2)
        solo.step_many(xs)
        assert eng.report()["gamma_dh"] == pytest.approx(
            solo.report()["gamma_dh"], abs=1e-6)

    def test_per_stream_accounting_since_open(self):
        """close_stream reports only what flowed since open_stream."""
        eng, _, _ = self._engine(n=2)
        rng = np.random.default_rng(3)
        eng.step_many(rng.normal(size=(6, 2, 8)).astype(np.float32))
        sid = eng.open_stream()          # zeroes slot accumulators
        eng.step_many(rng.normal(size=(4, 2, 8)).astype(np.float32))
        rep = eng.close_stream(sid)
        assert rep["steps"] == 4
        assert 0.0 <= rep["gamma_dh"] <= 1.0
        assert rep["w_bytes"] < eng.stats.w_bytes * eng.n_streams + 1e-6


class TestGruStreamBatcher:
    def test_slot_recycling_parity_with_dedicated_engine(self):
        """Streams admitted/harvested through the batcher produce exactly
        what a dedicated single-stream engine produces, and every request
        carries its own accounting."""
        task = GruTaskConfig(8, 16, 2, 3, task="regression",
                             theta_x=0.05, theta_h=0.05)
        params = init_gru_model(jax.random.PRNGKey(2), task)
        eng = GruStreamEngine(params, task, n_streams=2)
        cb = GruStreamBatcher(eng)
        rng = np.random.default_rng(0)
        seqs = [rng.normal(size=(t, 8)).astype(np.float32)
                for t in (5, 9, 4, 7, 6)]
        uids = [cb.submit(s) for s in seqs]
        done = cb.run_until_drained()
        assert sorted(r.uid for r in done) == sorted(uids)
        by_uid = {r.uid: r for r in done}
        for uid, s in zip(uids, seqs):
            solo = GruStreamEngine(params, task)
            want = np.asarray(solo.step_many(s))
            np.testing.assert_allclose(np.stack(by_uid[uid].outputs), want,
                                       atol=1e-5)
            st = by_uid[uid].stats
            assert st["steps"] == len(s)
            assert st["mean_est_latency_us"] > 0

    def test_submit_validates_frame_shape(self):
        task = GruTaskConfig(8, 16, 1, 1, task="regression")
        params = init_gru_model(jax.random.PRNGKey(0), task)
        cb = GruStreamBatcher(GruStreamEngine(params, task, n_streams=2))
        with pytest.raises(ValueError, match="frames"):
            cb.submit(np.zeros((4, 5), np.float32))
        # zero-length streams would wedge their slot forever (the admit
        # opens it, the first tick IndexErrors before it can close)
        with pytest.raises(ValueError, match="frames"):
            cb.submit(np.zeros((0, 8), np.float32))
