"""Cross-backend DeltaGRU equivalence + zero-sync engine regression.

The execution paths (dense XLA and the fused single-kernel sequence path,
which takes one stream or a ``[B, ...]`` tile alike) must agree with each
other and — at ``theta == 0`` — with the plain-GRU Eq. 1 oracle. The
streaming engine's on-device gamma/latency accounting must reproduce the
seed's host-side accounting exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deltagru import (deltagru_sequence, deltagru_step,
                                 deltagru_stack_step, gru_sequence,
                                 init_deltagru_stack_state, init_deltagru_state,
                                 init_gru_layer, init_gru_stack)
from repro.core.perf_model import estimate_stack
from repro.core.sparsity import GruDims
from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.serve.engine import GruStreamEngine

# (backend, extra kwargs): "fused" auto-routes to the jnp ref off-TPU, so
# the interpret=True rows are what actually exercise the Pallas kernel here.
KERNEL_PATHS = [("fused", {}), ("fused", {"interpret": True})]
KERNEL_BACKENDS = ("fused",)


def _stack_and_xs(key, i, h, layers, t, b, dtype=jnp.float32, scale=0.5):
    params = init_gru_stack(key, i, h, layers, dtype)
    xs = (jax.random.normal(jax.random.fold_in(key, 1), (t, b, i)) *
          scale).astype(dtype)
    return params, xs


class TestCrossBackendEquivalence:
    @pytest.mark.parametrize("backend,kw", KERNEL_PATHS)
    @pytest.mark.parametrize("b", [1, 4])
    def test_theta_zero_matches_gru_oracle(self, backend, kw, b):
        """Acceptance bar: every backend == Eq. 1 oracle to <= 1e-4."""
        params, xs = _stack_and_xs(jax.random.PRNGKey(0), 14, 32, 2, 20, b)
        want = gru_sequence(params, xs)
        got, _, _ = deltagru_sequence(params, xs, 0.0, 0.0, backend=backend,
                                      **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)

    @pytest.mark.parametrize("backend,kw", KERNEL_PATHS)
    @pytest.mark.parametrize("i,h,layers,b",
                             [(14, 32, 1, 1), (40, 200, 2, 3), (130, 128, 2, 2)])
    def test_dual_thresholds_match_dense(self, backend, kw, i, h, layers, b):
        """At nonzero (Θ_x, Θ_h) the kernel paths track the dense delta
        path bit-for-block: same deltas, same gammas, same outputs."""
        params, xs = _stack_and_xs(jax.random.PRNGKey(i + h), i, h, layers,
                                   16, b)
        want, _, st_d = deltagru_sequence(params, xs, 0.05, 0.1,
                                          backend="dense")
        got, _, st_k = deltagru_sequence(params, xs, 0.05, 0.1,
                                         backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
        assert float(st_k["gamma_dx"]) == pytest.approx(
            float(st_d["gamma_dx"]), abs=1e-6)
        assert float(st_k["gamma_dh"]) == pytest.approx(
            float(st_d["gamma_dh"]), abs=1e-6)

    @pytest.mark.parametrize("backend", KERNEL_BACKENDS)
    def test_bfloat16(self, backend):
        params, xs = _stack_and_xs(jax.random.PRNGKey(7), 16, 64, 1, 12, 2,
                                   dtype=jnp.bfloat16)
        want, _, _ = deltagru_sequence(params, xs, 0.05, 0.05,
                                       backend="dense")
        got, _, _ = deltagru_sequence(params, xs, 0.05, 0.05,
                                      backend=backend)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=5e-2, rtol=5e-2)

    @pytest.mark.parametrize("backend", KERNEL_BACKENDS)
    def test_single_step_matches_dense(self, backend):
        p = init_gru_layer(jax.random.PRNGKey(3), 24, 48)
        st = init_deltagru_state(p, (2,))
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24))
        want = deltagru_step(p, st, x, 0.02, 0.02)
        got = deltagru_step(p, st, x, 0.02, 0.02, backend=backend)
        np.testing.assert_allclose(np.asarray(got.h), np.asarray(want.h),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.state.m),
                                   np.asarray(want.state.m), atol=1e-5)

    def test_fused_rejects_custom_activations(self):
        p = init_gru_layer(jax.random.PRNGKey(0), 8, 16)
        st = init_deltagru_state(p, (1,))
        x = jnp.ones((1, 8))
        with pytest.raises(ValueError, match="fused backend"):
            deltagru_step(p, st, x, 0.0, 0.0, backend="fused",
                          sigmoid=lambda z: z)

    def test_unknown_backend_rejected(self):
        p = init_gru_layer(jax.random.PRNGKey(0), 8, 16)
        st = init_deltagru_state(p, (1,))
        with pytest.raises(ValueError, match="backend"):
            deltagru_step(p, st, jnp.ones((1, 8)), 0.0, 0.0, backend="spmd")


class TestStreamEngineZeroSync:
    """The de-synced engine must keep the seed's accounting semantics."""

    def _inputs(self, t=40, i=14):
        return np.stack([np.sin(np.arange(i) * 0.3 + s * 0.05) for s in
                         range(t)]).astype(np.float32)

    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_stats_match_host_side_accounting(self, backend):
        """Gamma/latency accounting unchanged after moving on-device: replay
        the seed's per-step host loop (float(fx)/float(fh) + host
        estimate_stack) and compare against the device carry. The replay
        uses ``eng.accel`` — the Eq. 7 model now prices the backend's
        streamed weight width (fp32 here), see spec_for_backend."""
        task = GruTaskConfig(14, 32, 2, 1, task="regression",
                             theta_x=0.1, theta_h=0.1)
        params = init_gru_model(jax.random.PRNGKey(0), task)
        xs = self._inputs()
        eng = GruStreamEngine(params, task, backend=backend)
        for x in xs:
            eng.step(x)
        rep = eng.report()

        # seed-style host accounting
        dims = GruDims(14, 32, 2)
        state = init_deltagru_stack_state(params["gru"], batch_shape=(1,))
        fired_x = fired_h = lat = 0.0
        for x in xs:
            _, state, deltas = deltagru_stack_step(
                params["gru"], state, jnp.asarray(x)[None], 0.1, 0.1)
            fx = float(np.mean([np.mean(np.asarray(dx) != 0)
                                for dx, _ in deltas]))
            fh = float(np.mean([np.mean(np.asarray(dh) != 0)
                                for _, dh in deltas]))
            fired_x += fx
            fired_h += fh
            lat += estimate_stack(dims, 1 - fx, 1 - fh, eng.accel).latency_s
        t = len(xs)
        assert rep["steps"] == t
        assert rep["gamma_dx"] == pytest.approx(1 - fired_x / t, abs=1e-5)
        assert rep["gamma_dh"] == pytest.approx(1 - fired_h / t, abs=1e-5)
        assert rep["mean_est_latency_us"] == pytest.approx(
            1e6 * lat / t, rel=1e-4)

    def test_step_many_equals_step_loop(self):
        task = GruTaskConfig(14, 24, 2, 3, task="regression",
                             theta_x=0.05, theta_h=0.05)
        params = init_gru_model(jax.random.PRNGKey(1), task)
        xs = self._inputs(t=32)
        e1 = GruStreamEngine(params, task)
        outs1 = np.stack([np.asarray(e1.step(x)) for x in xs])
        e2 = GruStreamEngine(params, task)
        outs2 = np.asarray(e2.step_many(xs))
        np.testing.assert_allclose(outs1, outs2, atol=1e-6)
        r1, r2 = e1.report(), e2.report()
        for key in ("steps", "gamma_dx", "gamma_dh", "mean_est_latency_us"):
            assert r1[key] == pytest.approx(r2[key], rel=1e-6)

    def test_multi_stream_matches_independent_streams(self):
        """N vmapped streams through one kernel == N separate engines."""
        task = GruTaskConfig(8, 16, 1, 2, task="regression",
                             theta_x=0.05, theta_h=0.05)
        params = init_gru_model(jax.random.PRNGKey(2), task)
        t, n = 16, 3
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(t, n, 8)).astype(np.float32)
        eng = GruStreamEngine(params, task, n_streams=n)
        outs = np.asarray(eng.step_many(xs))
        for s in range(n):
            single = GruStreamEngine(params, task)
            want = np.asarray(single.step_many(xs[:, s]))
            np.testing.assert_allclose(outs[:, s], want, atol=1e-5)

    def test_dynamic_controller_runs_on_device(self):
        task = GruTaskConfig(14, 32, 1, 1, task="regression",
                             theta_x=0.02, theta_h=0.02)
        params = init_gru_model(jax.random.PRNGKey(0), task)
        eng = GruStreamEngine(params, task, dynamic_target_fired=0.2)
        eng.step_many(np.stack(
            [np.sin(np.arange(14) * 0.5 + s * 0.3) * 2.0 for s in range(60)]))
        assert eng.theta_h != pytest.approx(0.02)
