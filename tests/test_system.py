"""End-to-end system behaviour: the paper's workloads running through the
full stack (data -> QAT training -> streaming deployment -> perf report),
plus MoE engine cross-validation and sharded-training integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.perf_model import estimate_stack
from repro.core.sparsity import GruDims
from repro.data.synthetic import batch_stream, gas_batch
from repro.dist.sharding import make_mesh
from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.quant.qat import EDGEDRNN_QAT
from repro.serve.engine import GruStreamEngine
from repro.train.optim import AdamConfig, constant_schedule
from repro.train.trainer import (init_train_state, make_gru_train_step,
                                 train_loop)


class TestPaperPipelineEndToEnd:
    """The paper's full deployment story on the SensorsGas-like task:
    pretrain dense -> retrain with deltas + QAT -> stream with batch-1
    engine -> report sparsity + Eq. 7 latency."""

    def test_full_pipeline(self):
        task_dense = GruTaskConfig(14, 32, 2, 1, task="regression")
        params = init_gru_model(jax.random.PRNGKey(0), task_dense)

        # step 1: pretrain dense (paper's cuDNN-GRU pretrain stage)
        step = make_gru_train_step(
            task_dense, AdamConfig(schedule=constant_schedule(3e-3)),
            use_delta=False)
        state = init_train_state(params)
        stream = batch_stream(gas_batch, jax.random.PRNGKey(1), batch=8,
                              t_len=64)
        state, hist_pre = train_loop(step, state, stream, 20)

        # step 2: retrain as DeltaGRU with dual thresholds + QAT
        task_delta = GruTaskConfig(14, 32, 2, 1, task="regression",
                                   theta_x=4 / 256, theta_h=8 / 256)
        step2 = make_gru_train_step(
            task_delta, AdamConfig(schedule=constant_schedule(1e-3)),
            use_delta=True, qat=EDGEDRNN_QAT)
        state2 = init_train_state(state.params)
        stream2 = batch_stream(gas_batch, jax.random.PRNGKey(2), batch=8,
                               t_len=64)
        state2, hist_delta = train_loop(step2, state2, stream2, 15)
        assert hist_delta[-1]["loss"] < hist_pre[0]["loss"]

        # step 3: deploy on the batch-1 streaming engine
        eng = GruStreamEngine(state2.params, task_delta)
        batch = gas_batch(jax.random.PRNGKey(3), batch=1, t_len=128)
        feats = np.asarray(batch["features"][:, 0])
        preds = np.stack([eng.step(f) for f in feats])
        rep = eng.report()

        # the deployed model tracks the latent concentration reasonably
        target = np.asarray(batch["targets"][:, 0, 0])
        corr = np.corrcoef(preds[32:, 0], target[32:])[0, 1]
        assert corr > 0.4

        # temporal sparsity is real and the Eq. 7 model prices it
        assert rep["gamma_dh"] > 0.2
        est = estimate_stack(GruDims(14, 32, 2), rep["gamma_dx"],
                             rep["gamma_dh"])
        assert est.throughput_ops > 2e9  # above dense peak => sparsity win


class TestMoEEngines:
    def test_sorted_equals_onehot(self):
        from repro.models.moe import init_moe, moe_apply, moe_apply_onehot
        p = init_moe(jax.random.PRNGKey(0), 16, 32, 8, pad_to=8)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
        y1, a1 = moe_apply(p, x, top_k=2, capacity_factor=8.0)
        y2, a2 = moe_apply_onehot(p, x, top_k=2, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
        assert float(a1) == pytest.approx(float(a2), rel=1e-5)

    def test_ep_shard_map_equals_sorted(self):
        n = len(jax.devices())
        if n < 4:
            pytest.skip("needs >= 4 devices")
        from repro.dist.sharding import AxisRules, use_mesh
        from repro.models.moe import init_moe, moe_apply, moe_apply_auto
        mesh = make_mesh((n // 2, 2), ("data", "model"))
        p = init_moe(jax.random.PRNGKey(0), 16, 32, 8, pad_to=2)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
        y_ref, a_ref = moe_apply(p, x, top_k=2, capacity_factor=8.0)
        with use_mesh(mesh, AxisRules()):
            y_ep, a_ep = jax.jit(
                lambda p, x: moe_apply_auto(p, x, top_k=2,
                                            capacity_factor=8.0))(p, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   atol=1e-4)
        assert float(a_ep) == pytest.approx(float(a_ref), rel=1e-4)


class TestShardedTraining:
    def test_lm_train_step_on_mesh(self):
        """A reduced arch trains under the production sharding rules on the
        local 8-device mesh — the same code path the dry-run lowers."""
        n = len(jax.devices())
        if n < 4:
            pytest.skip("needs >= 4 devices")
        from repro.data.lm_data import lm_batch
        from repro.dist.sharding import AxisRules, use_mesh
        from repro.launch import specs
        from repro.models.lm import init_lm
        from repro.train.trainer import (init_train_state,
                                         make_lm_train_step_fn)
        cfg = get_config("llama3.2-1b").reduced()
        mesh = make_mesh((n // 2, 2), ("data", "model"))
        rules = AxisRules()
        params = init_lm(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params)
        from repro.data.lm_data import lm_batch as _lb
        batch = _lb(jax.random.PRNGKey(1), cfg, batch=4, seq=16)
        step_fn = make_lm_train_step_fn(
            cfg, AdamConfig(schedule=constant_schedule(1e-3)), grad_accum=2)
        st_sh = specs.train_state_sharding(
            jax.eval_shape(lambda: state), mesh, rules)
        b_sh = specs.batch_sharding(jax.eval_shape(lambda: batch), mesh,
                                    rules)
        with use_mesh(mesh, rules):
            jf = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None))
            state2, metrics = jf(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        # parity with unsharded execution
        state3, metrics3 = jax.jit(step_fn)(state, batch)
        np.testing.assert_allclose(float(metrics["loss"]),
                                   float(metrics3["loss"]), rtol=1e-3)
