"""Delta-RWKV6: theta=0 bitwise decode parity, backends, programs, serving.

The cell-family contract every delta cell carries (GRU, LSTM, and now the
LM cells): at theta=0 the delta step IS the exact dense decode —
bit-for-bit, in both the jnp-ref mode and Pallas interpret mode — because
the Eq. 2 memory update degenerates to the raw stream and the projections
share one set of canonical expressions (``repro.core.deltarwkv`` owns
``mix_streams`` / ``group_norm_heads``; ``models/rwkv.py`` imports them).
Above theta=0 the fused fired-block path tracks the dense reconstruction
reference, ``cell="rwkv6"`` programs enforce the state convention, and
programs stream through ``DeltaStreamEngine`` with Eq. 7 accounting priced
on the generalized projection volumes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import get_backend, list_backends
from repro.core.deltarwkv import (deltarwkv_sequence, deltarwkv_stack_step,
                                  deltarwkv_step, init_deltarwkv_model,
                                  init_deltarwkv_stack,
                                  init_deltarwkv_stack_state,
                                  init_deltarwkv_state, rwkv_layer_dict)
from repro.core.perf_model import dram_traffic_bytes_per_timestep
from repro.core.program import compile_delta_program
from repro.core.sparsity import cell_dims
from repro.core.thresholds import ThresholdPolicy
from repro.models import rwkv as mrwkv
from repro.models.gru_rnn import GruTaskConfig
from repro.serve.engine import DeltaStreamEngine

D, B, T = 64, 2, 6


def _layer_and_xs(key=0, t=T, b=B, scale=1.0):
    lay = init_deltarwkv_stack(jax.random.PRNGKey(key), D, 1)[0]
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, D)) * scale
    return lay, rwkv_layer_dict(lay), xs


def _decode_chain(pd, xs, use_kernel=False, interpret=None):
    """The exact dense decode: per-step ``rwkv_time_mix`` with carried
    state (the bitwise reference)."""
    st = mrwkv.init_rwkv_state(xs.shape[1], D)
    ys = []
    for t in range(xs.shape[0]):
        y, new_last, wkv = mrwkv.rwkv_time_mix(pd, xs[t][:, None], st,
                                               use_kernel=use_kernel,
                                               interpret=interpret)
        st = mrwkv.RwkvState(tm_shift=new_last, cm_shift=st.cm_shift,
                             wkv=wkv)
        ys.append(y[:, 0])
    return jnp.stack(ys)


def _delta_chain(pd, xs, theta=0.0, backend="dense", interpret=None):
    st = mrwkv.init_rwkv_delta_state(pd, (xs.shape[1],))
    ys, deltas = [], []
    for t in range(xs.shape[0]):
        out = mrwkv.rwkv_time_mix_delta(pd, xs[t], st, theta, theta,
                                        backend=backend,
                                        interpret=interpret)
        st = out.state
        ys.append(out.h)
        deltas.append((out.delta_x, out.delta_h))
    return jnp.stack(ys), deltas


class TestRegistry:
    def test_backends_registered(self):
        assert set(("dense", "fused")) <= set(list_backends("rwkv6"))

    def test_spec_fields(self):
        for name in ("dense", "fused"):
            spec = get_backend(name, cell="rwkv6")
            assert spec.m_init == "zero"
            assert spec.weight_bits == 32
            assert not spec.supports_custom_acts
            assert not spec.union_compaction


class TestTheta0Bitwise:
    def test_dense_bitwise_jnp_ref(self):
        _, pd, xs = _layer_and_xs()
        ref = _decode_chain(pd, xs)
        got, _ = _delta_chain(pd, xs, 0.0)
        assert jnp.array_equal(got, ref), \
            f"max|diff|={float(jnp.max(jnp.abs(got - ref)))}"

    def test_dense_bitwise_pallas_interpret(self):
        _, pd, xs = _layer_and_xs(t=4)
        ref = _decode_chain(pd, xs, use_kernel=True, interpret=True)
        got, _ = _delta_chain(pd, xs, 0.0, interpret=True)
        assert jnp.array_equal(got, ref), \
            f"max|diff|={float(jnp.max(jnp.abs(got - ref)))}"

    def test_theta0_fires_everything(self):
        _, pd, xs = _layer_and_xs()
        _, deltas = _delta_chain(pd, xs, 0.0)
        # at theta=0 every component fires every step (|s - s_hat| >= 0)
        for dx, dh in deltas[1:]:
            assert float(jnp.mean(dx != 0)) > 0.95
            assert float(jnp.mean(dh != 0)) > 0.95


class TestFusedPath:
    @pytest.mark.parametrize("theta", [0.0, 0.05])
    def test_fused_tracks_dense(self, theta):
        _, pd, xs = _layer_and_xs(scale=0.5)
        ref, ref_d = _delta_chain(pd, xs, theta, backend="dense")
        got, got_d = _delta_chain(pd, xs, theta, backend="fused")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        # identical firing: both paths threshold the same memory chain
        for (rx, rh), (gx, gh) in zip(ref_d, got_d):
            assert jnp.array_equal(rx != 0, gx != 0)
            assert jnp.array_equal(rh != 0, gh != 0)

    def test_delta_groups_shapes(self):
        lay, pd, xs = _layer_and_xs()
        st = init_deltarwkv_state(lay, (B,))
        out = deltarwkv_step(lay, st, xs[0], 0.0, 0.0)
        assert out.delta_x.shape == (B, 3 * D)    # r/k/v columns
        assert out.delta_h.shape == (B, D)        # decay-LoRA columns

    def test_theta_gates_firing(self):
        _, pd, xs = _layer_and_xs(scale=0.3)
        _, deltas = _delta_chain(pd, xs, 0.5)
        fired = np.mean([float(jnp.mean(dx != 0)) for dx, _ in deltas[1:]])
        assert fired < 0.7


class TestProgram:
    def test_compile_and_sequence(self):
        model = init_deltarwkv_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="dense", cell="rwkv6")
        assert prog.cell == "rwkv6"
        xs = jax.random.normal(jax.random.PRNGKey(1), (T, B, D))
        ys, final, stats = prog.sequence(xs, 0.0, 0.0)
        assert ys.shape == (T, B, D)
        assert float(stats["gamma_dx"]) == 0.0
        assert float(stats["gamma_dh"]) == 0.0
        ys2, _, stats2 = prog.sequence(xs, 0.25, 0.25)
        assert float(stats2["gamma_dx"]) > 0.1

    def test_state_tag_mismatch_raises(self):
        model = init_deltarwkv_model(jax.random.PRNGKey(0), D, 2, 12)
        dense = compile_delta_program(model, backend="dense", cell="rwkv6")
        fused = compile_delta_program(model, backend="fused", cell="rwkv6")
        x = jnp.zeros((B, D))
        with pytest.raises(ValueError, match="backend"):
            dense.step(fused.init_state((B,)), x)
        with pytest.raises(TypeError, match="DeltaProgramState"):
            dense.step(init_deltarwkv_stack_state(dense.layers, (B,)), x)

    def test_infer_cell(self):
        from repro.core.program import infer_cell
        model = init_deltarwkv_model(jax.random.PRNGKey(0), D, 1, 12)
        assert infer_cell(model) == "rwkv6"


class TestEngine:
    def test_session_accounting_theta0_exact(self):
        model = init_deltarwkv_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="dense", cell="rwkv6")
        task = GruTaskConfig(D, D, 2, 12)
        eng = DeltaStreamEngine(prog, task)
        sid = eng.open_stream()
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (10, D)),
                        np.float32)
        eng.step_many(xs)
        session = eng.close_stream(sid)
        assert session["steps"] == 10
        assert session["gamma_dx"] == 0.0 and session["gamma_dh"] == 0.0
        dims = cell_dims("rwkv6", D, D, 2)
        dense_bytes = dram_traffic_bytes_per_timestep(dims, 0.0, 0.0,
                                                      w_weight_bits=32)
        assert session["mean_weight_bytes_per_step"] == pytest.approx(
            dense_bytes)
        rep = eng.report()
        assert rep["cell"] == "rwkv6"
        assert rep["mean_weight_bytes_per_step"] == pytest.approx(
            dense_bytes)

    def test_thresholded_session_sheds_bytes(self):
        model = init_deltarwkv_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="fused", cell="rwkv6")
        task = GruTaskConfig(D, D, 2, 12)
        eng = DeltaStreamEngine(prog, task,
                                thresholds=ThresholdPolicy(0.25, 0.25))
        # smooth stream so the threshold actually silences components
        steps = 24
        xs = np.cumsum(np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (steps, D)),
            np.float32) * 0.05, axis=0)
        eng.step_many(xs)
        rep = eng.report()
        dims = cell_dims("rwkv6", D, D, 2)
        dense_bytes = dram_traffic_bytes_per_timestep(dims, 0.0, 0.0,
                                                      w_weight_bits=32)
        assert rep["gamma_dx"] > 0.0
        assert rep["mean_weight_bytes_per_step"] < dense_bytes

    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_reduced_recipe_theta0_exact(self, backend):
        """The reduced rwkv6-1.6b serving recipe at theta=0: the engine measures
        no skipped column and prices exactly the dense projection volume,
        on both backends."""
        from repro.configs.rwkv6_1_6b import reduced_delta_recipe
        cfg, model, task = reduced_delta_recipe(jax.random.PRNGKey(0))
        prog = compile_delta_program(model, backend=backend, cell="rwkv6")
        eng = DeltaStreamEngine(prog, task)
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                          (8, cfg.d_model)), np.float32)
        eng.step_many(xs)
        rep = eng.report()
        assert rep["gamma_dx"] == 0.0 and rep["gamma_dh"] == 0.0
        dims = cell_dims("rwkv6", task.input_size, task.hidden_size,
                         task.num_layers)
        assert rep["mean_weight_bytes_per_step"] == \
            dram_traffic_bytes_per_timestep(dims, 0.0, 0.0, w_weight_bits=32)
