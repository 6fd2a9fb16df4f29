"""Distribution + fault-tolerance substrate tests (multi-device via the
pytest-local 8-device CPU override in conftest)."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.elastic import best_mesh, scale_event
from repro.dist.grad_compress import (CompressionConfig, compress,
                                      init_residual)
from repro.dist.sharding import (AxisRules, enforce_divisibility,
                                 infer_param_specs, make_mesh, use_mesh)
from repro.ft import checkpoint as ckpt
from repro.ft.heartbeat import HeartbeatMonitor
from repro.ft.straggler import StragglerDetector


class TestShardingRules:
    def test_resolve_drops_missing_axes(self):
        mesh = make_mesh((max(len(jax.devices()), 1),), ("data",))
        rules = AxisRules()
        spec = rules.resolve("batch", "heads", mesh=mesh)
        assert spec == P("data", None)  # pod/model absent -> dropped

    def test_enforce_divisibility(self):
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
        spec = enforce_divisibility(P("data"), (n * 3,), mesh)
        assert spec == P("data")
        spec = enforce_divisibility(P("data"), (n * 3 + 1,), mesh)
        assert spec == P(None)

    def test_param_rules_match_paths(self):
        n = len(jax.devices())
        mesh = make_mesh((n // 2, 2) if n >= 2 else (1, 1),
                         ("data", "model"))
        params = {"blocks": {"attn": {"w_q": jnp.zeros((8, 16))},
                             "ffn": {"w_down": jnp.zeros((16, 8))}},
                  "embedding": jnp.zeros((32, 8))}
        specs = infer_param_specs(params, rules=AxisRules(), mesh=mesh)
        assert specs["blocks"]["attn"]["w_q"] == P("data", "model")
        assert specs["blocks"]["ffn"]["w_down"] == P("model", "data")
        assert specs["embedding"] == P("model", "data")


class TestGradCompression:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10_000), st.floats(1e-4, 1e-1))
    def test_error_feedback_telescopes(self, seed, theta):
        """sum(sent) + residual == sum(grads): no gradient mass lost."""
        cfg = CompressionConfig(theta=theta)
        key = jax.random.PRNGKey(seed)
        grads_seq = [
            {"w": 0.01 * jax.random.normal(jax.random.fold_in(key, i), (32,))}
            for i in range(5)]
        residual = init_residual(grads_seq[0])
        total_sent = jnp.zeros(32)
        for g in grads_seq:
            sent, residual, _ = compress(g, residual, cfg)
            total_sent = total_sent + sent["w"]
        total_true = sum(g["w"] for g in grads_seq)
        np.testing.assert_allclose(total_sent + residual["w"], total_true,
                                   atol=1e-6)

    def test_compression_ratio_reported(self):
        cfg = CompressionConfig(theta=0.5)
        g = {"w": jnp.array([0.1, 0.9, -0.7, 0.01])}
        sent, res, stats = compress(g, init_residual(g), cfg)
        assert float(stats["fired_fraction"]) == pytest.approx(0.5)
        np.testing.assert_allclose(sent["w"], [0.0, 0.9, -0.7, 0.0])

    def test_quantile_threshold(self):
        cfg = CompressionConfig(quantile=0.75)
        g = {"w": jnp.arange(1.0, 101.0)}
        sent, _, stats = compress(g, init_residual(g), cfg)
        assert float(stats["fired_fraction"]) == pytest.approx(0.26, abs=0.02)


class TestPipelineParallel:
    def test_pipeline_forward_matches_sequential(self):
        n = len(jax.devices())
        if n < 2:
            pytest.skip("needs >= 2 devices")
        from repro.dist.pipeline import pipeline_forward, split_microbatches
        stages = min(n, 4)
        mesh = make_mesh((stages,), ("stage",))
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (stages, 8, 8)) * 0.3

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.fold_in(key, 1), (8, 4, 8))
        xs = split_microbatches(x, 4)
        fwd = pipeline_forward(stage_fn, mesh, "stage", 4)
        got = fwd(ws, xs)
        want = xs
        for i in range(stages):
            want = jax.vmap(lambda xm: stage_fn(ws[i], xm))(want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


class TestElastic:
    def test_best_mesh_clamps(self):
        m = best_mesh(len(jax.devices()), model_parallel=3)
        assert m.size <= len(jax.devices())

    def test_scale_event_plans_remesh(self):
        n = len(jax.devices())
        if n < 4:
            pytest.skip("needs >= 4 devices")
        old = best_mesh(n, model_parallel=2)
        ev = scale_event(old, n // 2, model_parallel=2)
        assert ev["new_shape"]["data"] < ev["old_shape"]["data"]


class TestCheckpoint:
    def test_roundtrip_and_integrity(self, tmp_path):
        state = {"a": jnp.arange(12.0).reshape(3, 4),
                 "nested": {"b": jnp.ones((5,), jnp.int32)}}
        ckpt.save(str(tmp_path), 7, state)
        restored = ckpt.restore(str(tmp_path), state)
        np.testing.assert_array_equal(restored["a"], state["a"])
        np.testing.assert_array_equal(restored["nested"]["b"],
                                      state["nested"]["b"])
        assert ckpt.latest_step(str(tmp_path)) == 7

    def test_async_save_publishes_atomically(self, tmp_path):
        import threading
        state = {"w": jnp.zeros((1000, 100))}
        ev = threading.Event()
        ckpt.save(str(tmp_path), 1, state, async_write=True, _done_event=ev)
        assert ev.wait(30)
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_corruption_detected(self, tmp_path):
        state = {"w": jnp.ones((8,))}
        path = ckpt.save(str(tmp_path), 3, state)
        # corrupt the array file
        import glob
        fn = glob.glob(os.path.join(path, "arr_*.npy"))[0]
        arr = np.load(fn)
        arr[0] = 999.0
        np.save(fn, arr)
        with pytest.raises(IOError):
            ckpt.restore(str(tmp_path), state)

    def test_resharding_restore(self, tmp_path):
        """Checkpoint saved unsharded restores onto a mesh (elastic path)."""
        n = len(jax.devices())
        state = {"w": jnp.arange(float(n * 4)).reshape(n, 4)}
        ckpt.save(str(tmp_path), 1, state)
        mesh = make_mesh((n,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        restored = ckpt.restore(str(tmp_path), state, shardings=sh)
        assert restored["w"].sharding.num_devices == n
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])

    def test_manager_retention(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), every=1, keep=2,
                                     async_write=False)
        for s in range(1, 6):
            mgr.maybe_save(s, {"w": jnp.full((2,), float(s))})
        steps = sorted(int(d.split("_")[-1]) for d in os.listdir(tmp_path)
                       if d.startswith("step_"))
        assert steps == [4, 5]

    def test_sharded_restore_casts_to_target_dtype(self, tmp_path):
        """The sharded restore branch used to skip the dtype cast: an fp32
        save restored onto a bf16/int target kept float32 leaves and flowed
        wrong-width arrays into downstream kernels. Both branches must land
        on the TARGET dtype."""
        n = len(jax.devices())
        state = {"w": jnp.arange(float(n * 4)).reshape(n, 4)}  # fp32 save
        ckpt.save(str(tmp_path), 1, state)
        target = {"w": jnp.zeros((n, 4), jnp.bfloat16)}
        mesh = make_mesh((n,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        sharded = ckpt.restore(str(tmp_path), target, shardings=sh)
        assert sharded["w"].dtype == jnp.bfloat16
        unsharded = ckpt.restore(str(tmp_path), target)
        assert unsharded["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(sharded["w"]),
                                      np.asarray(unsharded["w"]))

    def test_restore_shape_mismatch_raises(self, tmp_path):
        ckpt.save(str(tmp_path), 1, {"w": jnp.zeros((4, 4))})
        with pytest.raises(ValueError, match="logical shape"):
            ckpt.restore(str(tmp_path), {"w": jnp.zeros((2, 4))})

    def test_manager_wait_reraises_background_write_failure(self, tmp_path,
                                                            monkeypatch):
        """A failed async write must surface on the caller's thread: the
        old wait() discarded the event result and never looked at the
        daemon thread's exception, so the 'checkpoint' a restart relied on
        silently never existed."""
        mgr = ckpt.CheckpointManager(str(tmp_path), every=1, keep=2,
                                     async_write=True)
        boom = IOError("disk full")

        def failing_save(*a, **k):
            raise boom
        monkeypatch.setattr(ckpt.np, "save", failing_save)
        assert mgr.maybe_save(1, {"w": jnp.ones((4,))})
        with pytest.raises(IOError, match="disk full"):
            mgr.wait(timeout=30)
        # the failure is consumed: a subsequent wait is clean
        assert mgr.wait(timeout=1)

    def test_manager_wait_times_out_on_hung_write(self, tmp_path,
                                                  monkeypatch):
        """wait() must report a write that did NOT land in time as False
        (the old code returned None regardless), and keep it pending."""
        import threading
        gate = threading.Event()
        real_save = ckpt.np.save

        def slow_save(*a, **k):
            gate.wait(30)
            return real_save(*a, **k)
        monkeypatch.setattr(ckpt.np, "save", slow_save)
        mgr = ckpt.CheckpointManager(str(tmp_path), every=1,
                                     async_write=True)
        mgr.maybe_save(1, {"w": jnp.ones((2,))})
        assert mgr.wait(timeout=0.2) is False    # still in flight
        gate.set()
        assert mgr.wait(timeout=30) is True      # now landed
        assert ckpt.latest_step(str(tmp_path)) == 1


class TestHeartbeatStraggler:
    def test_heartbeat_detects_dead_worker(self):
        clock = [0.0]
        mon = HeartbeatMonitor(deadline_s=5.0, clock=lambda: clock[0])
        mon.register("w0")
        mon.register("w1")
        mon.beat("w0")
        mon.beat("w1")
        clock[0] = 3.0
        mon.beat("w0")
        clock[0] = 7.0
        assert mon.dead_workers() == ["w1"]

    def test_straggler_patience_and_policy(self):
        det = StragglerDetector(factor=2.0, patience=2, policy="drop")
        fleet = {f"w{i}": 1.0 for i in range(8)}
        r = det.observe({**fleet, "w7": 10.0})
        assert r.stragglers == []          # first strike
        r = det.observe({**fleet, "w7": 10.0})
        assert r.stragglers == ["w7"] and r.action == "drop"
        assert det.rescale_factor(8, 1) == pytest.approx(8 / 7)

    def test_straggler_recovers(self):
        det = StragglerDetector(factor=2.0, patience=2, ewma=1.0)
        fleet = {f"w{i}": 1.0 for i in range(4)}
        det.observe({**fleet, "w3": 10.0})
        r = det.observe(fleet)             # back to normal resets strikes
        assert r.stragglers == []


class TestRestart:
    def test_crash_resume_is_bitwise_identical(self, tmp_path):
        """Train 12 steps with a crash at step 7; resumed run must produce
        the same final params as an uninterrupted run."""
        from repro.ft.restart import RestartPolicy, run_resumable
        from repro.models.gru_rnn import GruTaskConfig, init_gru_model
        from repro.train.optim import AdamConfig, constant_schedule
        from repro.train.trainer import init_train_state, make_gru_train_step
        from repro.data.synthetic import gas_batch

        task = GruTaskConfig(14, 16, 1, 1, task="regression")
        step_fn = make_gru_train_step(
            task, AdamConfig(schedule=constant_schedule(1e-3)))

        def make_state():
            return init_train_state(init_gru_model(jax.random.PRNGKey(0),
                                                   task))

        def batches(start):
            def gen():
                i = start
                while True:
                    yield gas_batch(jax.random.fold_in(jax.random.PRNGKey(1),
                                                       i), batch=4, t_len=32)
                    i += 1
            return gen()

        # uninterrupted baseline
        state = make_state()
        it = batches(0)
        for _ in range(12):
            state, _ = step_fn(state, next(it))
        want = state.params

        # crashing run
        crash = {"armed": True}
        def crashing_step(state, batch):
            if crash["armed"] and int(state.step) == 7:
                crash["armed"] = False
                raise RuntimeError("simulated node failure")
            return step_fn(state, batch)

        policy = RestartPolicy(max_restarts=2, ckpt_dir=str(tmp_path),
                               save_every=5)
        got, hist, restarts = run_resumable(make_state, crashing_step,
                                            batches, 12, policy)
        assert restarts == 1
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
