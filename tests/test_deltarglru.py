"""Delta-RG-LRU: theta=0 bitwise decode parity, backends, programs, serving.

Same cell-family contract as the GRU/LSTM/RWKV6 suites: at theta=0 the
delta step reproduces :func:`repro.models.rglru.rglru_block_decode`
bit-for-bit (the canonical gate expressions live in
``repro.core.deltarglru``; the models module imports them, and the dense
delta path spells the recurrence exactly as the decode does). The causal
conv's 3-step history rides in the delta layer state and composes with
the thresholding (only the projections delta — the conv consumes their
held outputs). Fused fired-block compaction tracks dense, programs
enforce state conventions, and the engine prices the 2DW + 2W^2
projection volumes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import get_backend, list_backends
from repro.core.deltarglru import (CONV_WIDTH, deltarglru_sequence,
                                   deltarglru_step, init_deltarglru_model,
                                   init_deltarglru_stack,
                                   init_deltarglru_stack_state,
                                   init_deltarglru_state, rglru_layer_dict)
from repro.core.perf_model import dram_traffic_bytes_per_timestep
from repro.core.program import compile_delta_program
from repro.core.sparsity import cell_dims
from repro.core.thresholds import ThresholdPolicy
from repro.models import rglru as mrglru
from repro.models.gru_rnn import GruTaskConfig
from repro.serve.engine import DeltaStreamEngine

D, B, T = 64, 2, 8


def _layer_and_xs(key=2, t=T, b=B, scale=1.0):
    lay = init_deltarglru_stack(jax.random.PRNGKey(key), D, 1)[0]
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, D)) * scale
    return lay, rglru_layer_dict(lay), xs


def _decode_chain(pd, xs):
    """The exact dense decode: per-step ``rglru_block_decode`` with
    carried state (the bitwise reference)."""
    st = mrglru.init_rglru_state(xs.shape[1], D)
    ys = []
    for t in range(xs.shape[0]):
        y, st = mrglru.rglru_block_decode(pd, xs[t][:, None], st)
        ys.append(y[:, 0])
    return jnp.stack(ys)


def _delta_chain(pd, xs, theta=0.0, backend="dense", interpret=None):
    st = mrglru.init_rglru_delta_state(pd, (xs.shape[1],))
    ys, deltas = [], []
    for t in range(xs.shape[0]):
        out = mrglru.rglru_block_decode_delta(pd, xs[t], st, theta, theta,
                                              backend=backend,
                                              interpret=interpret)
        st = out.state
        ys.append(out.h)
        deltas.append((out.delta_x, out.delta_h))
    return jnp.stack(ys), deltas


class TestRegistry:
    def test_backends_registered(self):
        assert set(("dense", "fused")) <= set(list_backends("rglru"))

    def test_spec_fields(self):
        for name in ("dense", "fused"):
            spec = get_backend(name, cell="rglru")
            assert spec.m_init == "zero"
            assert spec.weight_bits == 32
            assert not spec.supports_custom_acts


class TestTheta0Bitwise:
    def test_dense_bitwise(self):
        _, pd, xs = _layer_and_xs()
        ref = _decode_chain(pd, xs)
        got, _ = _delta_chain(pd, xs, 0.0)
        assert jnp.array_equal(got, ref), \
            f"max|diff|={float(jnp.max(jnp.abs(got - ref)))}"

    def test_dense_bitwise_interpret_flag(self):
        # the dense path touches no kernel, so the Pallas mode flag must
        # not perturb the bitwise contract
        _, pd, xs = _layer_and_xs(t=5)
        ref = _decode_chain(pd, xs)
        got, _ = _delta_chain(pd, xs, 0.0, interpret=True)
        assert jnp.array_equal(got, ref)

    def test_conv_history_carries(self):
        # the delta state's conv history must reproduce the decode
        # state's: feed CONV_WIDTH+2 steps so the window fully turns over
        _, pd, xs = _layer_and_xs(t=CONV_WIDTH + 2)
        st_m = mrglru.init_rglru_state(B, D)
        st_d = mrglru.init_rglru_delta_state(pd, (B,))
        for t in range(xs.shape[0]):
            _, st_m = mrglru.rglru_block_decode(pd, xs[t][:, None], st_m)
            out = mrglru.rglru_block_decode_delta(pd, xs[t], st_d, 0.0, 0.0)
            st_d = out.state
        assert jnp.array_equal(st_d.conv, st_m.conv)
        assert jnp.array_equal(st_d.h, st_m.h)


class TestFusedPath:
    @pytest.mark.parametrize("theta", [0.0, 0.05])
    def test_fused_tracks_dense(self, theta):
        _, pd, xs = _layer_and_xs(scale=0.5)
        ref, ref_d = _delta_chain(pd, xs, theta, backend="dense")
        got, got_d = _delta_chain(pd, xs, theta, backend="fused")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)
        for (rx, rh), (gx, gh) in zip(ref_d, got_d):
            assert jnp.array_equal(rx != 0, gx != 0)
            assert jnp.array_equal(rh != 0, gh != 0)

    def test_delta_groups_shapes(self):
        lay, pd, xs = _layer_and_xs()
        st = init_deltarglru_state(lay, (B,))
        out = deltarglru_step(lay, st, xs[0], 0.0, 0.0)
        assert out.delta_x.shape == (B, D)   # layer-input columns
        assert out.delta_h.shape == (B, D)   # post-conv gate columns

    def test_theta_gates_firing(self):
        _, pd, xs = _layer_and_xs(scale=0.3)
        _, deltas = _delta_chain(pd, xs, 0.5)
        fired = np.mean([float(jnp.mean(dx != 0)) for dx, _ in deltas[1:]])
        assert fired < 0.7


class TestProgram:
    def test_compile_and_sequence(self):
        model = init_deltarglru_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="dense", cell="rglru")
        assert prog.cell == "rglru"
        xs = jax.random.normal(jax.random.PRNGKey(1), (T, B, D))
        ys, final, stats = prog.sequence(xs, 0.0, 0.0)
        assert ys.shape == (T, B, D)
        assert float(stats["gamma_dx"]) == 0.0
        assert float(stats["gamma_dh"]) == 0.0
        _, _, stats2 = prog.sequence(xs, 0.25, 0.25)
        assert float(stats2["gamma_dx"]) > 0.1

    def test_state_tag_mismatch_raises(self):
        model = init_deltarglru_model(jax.random.PRNGKey(0), D, 2, 12)
        dense = compile_delta_program(model, backend="dense", cell="rglru")
        fused = compile_delta_program(model, backend="fused", cell="rglru")
        x = jnp.zeros((B, D))
        with pytest.raises(ValueError, match="backend"):
            dense.step(fused.init_state((B,)), x)
        with pytest.raises(TypeError, match="DeltaProgramState"):
            dense.step(init_deltarglru_stack_state(dense.layers, (B,)), x)

    def test_cross_cell_state_raises(self):
        rg = compile_delta_program(
            init_deltarglru_model(jax.random.PRNGKey(0), D, 1, 12),
            backend="dense", cell="rglru")
        from repro.core.deltarwkv import init_deltarwkv_model
        rw = compile_delta_program(
            init_deltarwkv_model(jax.random.PRNGKey(0), D, 1, 12),
            backend="dense", cell="rwkv6")
        with pytest.raises(ValueError, match="cell"):
            rg.step(rw.init_state((B,)), jnp.zeros((B, D)))

    def test_infer_cell(self):
        from repro.core.program import infer_cell
        model = init_deltarglru_model(jax.random.PRNGKey(0), D, 1, 12)
        assert infer_cell(model) == "rglru"


class TestEngine:
    def test_session_accounting_theta0_exact(self):
        model = init_deltarglru_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="fused", cell="rglru")
        task = GruTaskConfig(D, D, 2, 12)
        eng = DeltaStreamEngine(prog, task)
        sid = eng.open_stream()
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (10, D)),
                        np.float32)
        eng.step_many(xs)
        session = eng.close_stream(sid)
        assert session["gamma_dx"] == 0.0 and session["gamma_dh"] == 0.0
        dims = cell_dims("rglru", D, D, 2)
        dense_bytes = dram_traffic_bytes_per_timestep(dims, 0.0, 0.0,
                                                      w_weight_bits=32)
        assert session["mean_weight_bytes_per_step"] == pytest.approx(
            dense_bytes)

    def test_thresholded_session_sheds_bytes(self):
        model = init_deltarglru_model(jax.random.PRNGKey(0), D, 2, 12)
        prog = compile_delta_program(model, backend="dense", cell="rglru")
        task = GruTaskConfig(D, D, 2, 12)
        eng = DeltaStreamEngine(prog, task,
                                thresholds=ThresholdPolicy(0.25, 0.25))
        steps = 24
        xs = np.cumsum(np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (steps, D)),
            np.float32) * 0.05, axis=0)
        eng.step_many(xs)
        rep = eng.report()
        dims = cell_dims("rglru", D, D, 2)
        dense_bytes = dram_traffic_bytes_per_timestep(dims, 0.0, 0.0,
                                                      w_weight_bits=32)
        assert rep["gamma_dx"] > 0.0
        assert rep["mean_weight_bytes_per_step"] < dense_bytes

    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_reduced_recipe_theta0_exact(self, backend):
        """The reduced recurrentgemma-9b serving recipe at theta=0: the engine measures
        no skipped column and prices exactly the dense projection volume,
        on both backends."""
        from repro.configs.recurrentgemma_9b import reduced_delta_recipe
        cfg, model, task = reduced_delta_recipe(jax.random.PRNGKey(0))
        prog = compile_delta_program(model, backend=backend, cell="rglru")
        eng = DeltaStreamEngine(prog, task)
        xs = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                          (8, cfg.d_model)), np.float32)
        eng.step_many(xs)
        rep = eng.report()
        assert rep["gamma_dx"] == 0.0 and rep["gamma_dh"] == 0.0
        dims = cell_dims("rglru", task.input_size, task.hidden_size,
                         task.num_layers)
        assert rep["mean_weight_bytes_per_step"] == \
            dram_traffic_bytes_per_timestep(dims, 0.0, 0.0, w_weight_bits=32)
