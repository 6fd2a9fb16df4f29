"""Resilient-serving tests (PR 7): device-side frame guard, snapshot/
rollback, engine checkpoint/restore, the supervisor's quarantine/shed/
overload policies, and the seeded chaos soak.

The central invariant, asserted bitwise throughout: faults injected into
SOME streams never perturb the outputs of ANY completed stream. The guard
masks a poisoned frame to the zero-delta silent regime — semantically
identical to host-side ``sanitize_frames`` — and rollback replay is
deterministic, so every completed stream equals a clean same-width
reference run of its (sanitized) frames, even across quarantines, state
corruption, and a mid-soak crash/restore. (Same-width matters: the q8
cell is code-exact batch-vs-solo, but the fp32 head matmul picks up XLA
row-count reassociation jitter, so references run at the SAME tile width
— where slot position and companion values are pinned bitwise-neutral.)
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.program import compile_delta_program
from repro.core.thresholds import ThresholdPolicy
from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.quant.export import quantize_delta_model
from repro.serve.engine import DeltaStreamEngine
from repro.serve.faults import (FaultPlan, SimulatedCrash,
                                corrupt_slot_state, sanitize_frames)
from repro.serve.resilience import (ResiliencePolicy, ResilientStreamServer,
                                    load_sidecar, serve_resumable)
from repro.serve.scheduler import DeltaStreamBatcher


TASK = GruTaskConfig(8, 16, 2, 3, task="regression",
                     theta_x=0.05, theta_h=0.05)


def _program(backend="fused", key=0):
    params = init_gru_model(jax.random.PRNGKey(key), TASK)
    if backend == "fused_q8":
        return quantize_delta_model(params)
    return compile_delta_program(params, backend=backend)


def _frames(t, rng):
    return rng.standard_normal((t, TASK.input_size)).astype(np.float32)


class TestFrameGuard:
    @pytest.mark.parametrize("backend", ["fused", "fused_q8"])
    @pytest.mark.parametrize("kind", [np.nan, np.inf])
    def test_guard_equals_sanitized_feed_bitwise(self, backend, kind):
        """A poisoned feed through the guard must be BITWISE the sanitized
        feed: the guard repeats the previous guarded frame, which is
        exactly what sanitize_frames does host-side."""
        prog = _program(backend)
        rng = np.random.default_rng(0)
        frames = _frames(30, rng)
        frames[5, 2] = kind
        frames[17, :] = kind          # fully poisoned frame
        eng = DeltaStreamEngine(prog, TASK)
        got = np.asarray(eng.step_many(frames))
        assert np.isfinite(got).all()
        ctrl = DeltaStreamEngine(prog, TASK)
        want = np.asarray(ctrl.step_many(sanitize_frames(frames)))
        np.testing.assert_array_equal(got, want)
        assert eng.stats.poison_steps == 2.0
        assert eng.report()["poison_steps"] == 2.0
        assert ctrl.stats.poison_steps == 0.0

    def test_poisoned_frame_zero(self):
        """Frame 0 poisoned: falls back to the zero init frame (last_x
        starts at 0 — still the silent regime vs the delta-memory init)."""
        prog = _program()
        frames = _frames(10, np.random.default_rng(1))
        frames[0, :] = np.nan
        eng = DeltaStreamEngine(prog, TASK)
        got = np.asarray(eng.step_many(frames))
        ctrl = DeltaStreamEngine(prog, TASK)
        want = np.asarray(ctrl.step_many(sanitize_frames(frames)))
        np.testing.assert_array_equal(got, want)

    def test_per_slot_poison_counters_and_companion_isolation(self):
        """Poison lands in ONE slot's counter; companion outputs stay
        bitwise identical to an unpoisoned run."""
        prog = _program("fused_q8")
        rng = np.random.default_rng(2)
        xs = rng.standard_normal((25, 4, 8)).astype(np.float32)
        clean = xs.copy()
        xs[3, 1, 0] = np.nan
        xs[9, 1, :] = np.inf
        eng = DeltaStreamEngine(prog, TASK, n_streams=4)
        got = np.asarray(eng.step_many(xs))
        host = jax.device_get(eng._carry)
        np.testing.assert_array_equal(host["poison_steps"], [0, 2, 0, 0])
        assert eng.stats.poison_steps == 2.0
        ctrl = DeltaStreamEngine(prog, TASK, n_streams=4)
        want = np.asarray(ctrl.step_many(clean))
        for s in (0, 2, 3):
            np.testing.assert_array_equal(got[:, s], want[:, s])

    def test_session_reset_zeroes_poison_and_guard_memory(self):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        xs = np.full((4, 2, 8), np.nan, np.float32)
        eng.step_many(xs)
        assert eng.stats.poison_steps == 8.0
        sid = eng.open_stream()
        host = jax.device_get(eng._carry)
        assert host["poison_steps"][sid] == 0.0
        np.testing.assert_array_equal(host["last_x"][sid], np.zeros(8))
        # lifetime total is NOT reset by session churn
        assert eng.stats.poison_steps == 8.0

    def test_bad_state_counter_flags_corrupted_slot(self):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=3)
        rng = np.random.default_rng(3)
        eng.step_many(rng.standard_normal((5, 3, 8)).astype(np.float32))
        corrupt_slot_state(eng, 1)
        eng.step_many(rng.standard_normal((4, 3, 8)).astype(np.float32))
        host = jax.device_get(eng._carry)
        assert host["bad_state"][1] == 4.0      # every post-corruption step
        assert host["bad_state"][0] == 0.0
        assert host["bad_state"][2] == 0.0
        assert eng.stats.bad_state_steps == 4.0


class TestZeroSync:
    def _count_device_gets(self, monkeypatch):
        calls = {"n": 0}
        real = jax.device_get

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)
        monkeypatch.setattr(jax, "device_get", counting)
        return calls

    def test_hot_loop_and_snapshots_never_sync(self, monkeypatch):
        """step / step_many / open_stream / snapshot / rollback /
        set_theta_h are all device-side: zero host round-trips. stats is
        the single materialization point."""
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        rng = np.random.default_rng(0)
        calls = self._count_device_gets(monkeypatch)
        eng.open_stream()
        eng.step(rng.standard_normal((2, 8)).astype(np.float32))
        eng.step_many(rng.standard_normal((10, 2, 8)).astype(np.float32))
        eng.snapshot_streams()
        eng.step_many(rng.standard_normal((5, 2, 8)).astype(np.float32))
        eng.rollback_stream(0)
        eng.set_theta_h(0.1)
        assert calls["n"] == 0
        for v in eng._carry.values():
            assert isinstance(v, jax.Array)     # nothing fell back to host
        _ = eng.stats
        assert calls["n"] == 1

    def test_supervised_tick_syncs_only_on_check_ticks(self, monkeypatch):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        srv = ResilientStreamServer(DeltaStreamBatcher(eng),
                                    ResiliencePolicy(check_every=4))
        rng = np.random.default_rng(1)
        # long streams: nothing finishes (and so nothing harvests/syncs)
        # during the counted window
        for _ in range(2):
            srv.submit(_frames(100, rng))
        srv.tick()                              # warm-up/admission tick
        calls = self._count_device_gets(monkeypatch)
        for _ in range(3):                      # ticks 2,3: off-cadence
            srv.tick()                          # tick 4: check tick
        assert calls["n"] == 1                  # exactly the check tick


class TestSnapshotRollback:
    def test_rollback_restores_state_and_accounting(self):
        prog = _program("fused_q8")
        eng = DeltaStreamEngine(prog, TASK, n_streams=3)
        for _ in range(3):
            eng.open_stream()
        rng = np.random.default_rng(0)
        eng.step_many(rng.standard_normal((8, 3, 8)).astype(np.float32))
        eng.snapshot_streams([1])
        snap_host = jax.device_get(eng._carry)
        tail = rng.standard_normal((6, 3, 8)).astype(np.float32)
        out_a = np.asarray(eng.step_many(tail))
        eng.rollback_stream(1)
        host = jax.device_get(eng._carry)
        for key in ("fired_x", "fired_h", "lat_s", "w_bytes"):
            assert host[key][1] == snap_host[key][1]
        for key in ("lat_s", "w_bytes"):        # others kept marching
            assert host[key][0] != snap_host[key][0]
        # replay determinism: the rolled-back slot reproduces its outputs
        out_b = np.asarray(eng.step_many(tail))
        np.testing.assert_array_equal(out_b[:, 1], out_a[:, 1])

    def test_rollback_without_snapshot_rewinds_to_session_start(self):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        sid = eng.open_stream()
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((7, 2, 8)).astype(np.float32)
        first = np.asarray(eng.step_many(xs))
        assert eng.rollback_stream(sid) == 0
        again = np.asarray(eng.step_many(xs))
        np.testing.assert_array_equal(again[:, sid], first[:, sid])

    def test_rollback_discards_corruption(self):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        sid = eng.open_stream()
        rng = np.random.default_rng(2)
        eng.step_many(rng.standard_normal((5, 2, 8)).astype(np.float32))
        eng.snapshot_streams([sid])
        corrupt_slot_state(eng, sid)
        eng.step_many(rng.standard_normal((3, 2, 8)).astype(np.float32))
        assert jax.device_get(eng._carry)["bad_state"][sid] > 0
        eng.rollback_stream(sid)
        for leaf in jax.tree_util.tree_leaves(eng.state.stack):
            assert np.isfinite(np.asarray(leaf)).all()
        assert jax.device_get(eng._carry)["bad_state"][sid] == 0.0

    def test_rollback_requires_open_slot(self):
        eng = DeltaStreamEngine(_program(), TASK, n_streams=2)
        with pytest.raises(ValueError, match="not open"):
            eng.rollback_stream(0)
        with pytest.raises(ValueError, match="not open"):
            eng.rollback_stream(5)

    def test_lifetime_aggregates_never_rewound(self):
        """Rollback un-executes a slot's session view but the engine
        lifetime aggregates keep counting real executed work."""
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK, n_streams=2)
        sid = eng.open_stream()
        rng = np.random.default_rng(3)
        eng.step_many(rng.standard_normal((10, 2, 8)).astype(np.float32))
        agg_before = eng.stats.fired_h
        eng.rollback_stream(sid)
        assert eng.stats.fired_h == agg_before
        assert eng.stats.steps == 10


class TestEngineCheckpointRestore:
    @pytest.mark.parametrize("backend", ["fused", "fused_q8"])
    def test_restore_is_exact_and_bitwise(self, backend, tmp_path):
        """Restored engine == uninterrupted engine: same report dict
        (exact accounting continuity) and bitwise-identical subsequent
        outputs, including open-session bookkeeping."""
        prog = _program(backend)
        eng = DeltaStreamEngine(prog, TASK, n_streams=3)
        rng = np.random.default_rng(0)
        sid = eng.open_stream()
        eng.step_many(rng.standard_normal((12, 3, 8)).astype(np.float32))
        eng.snapshot_streams()
        eng.checkpoint(str(tmp_path))
        eng2 = DeltaStreamEngine.restore(str(tmp_path), prog, TASK,
                                         n_streams=3)
        assert eng2.report() == eng.report()
        assert eng2._slot_busy == eng._slot_busy
        assert eng2._slot_opened_at == eng._slot_opened_at
        tail = rng.standard_normal((6, 3, 8)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(eng.step_many(tail)),
                                      np.asarray(eng2.step_many(tail)))
        # the snapshot shadows traveled too: both rollbacks land identically
        eng.rollback_stream(sid)
        eng2.rollback_stream(sid)
        np.testing.assert_array_equal(np.asarray(eng.step_many(tail)),
                                      np.asarray(eng2.step_many(tail)))
        assert eng2.report() == eng.report()

    def test_restore_carries_resilience_counters(self, tmp_path):
        prog = _program()
        eng = DeltaStreamEngine(prog, TASK)
        frames = _frames(10, np.random.default_rng(1))
        frames[4, :] = np.nan
        eng.step_many(frames)
        eng.checkpoint(str(tmp_path))
        eng2 = DeltaStreamEngine.restore(str(tmp_path), prog, TASK)
        assert eng2.stats.poison_steps == 1.0
        assert eng2.stats.steps == 10

    def test_restore_rejects_wrong_geometry(self, tmp_path):
        eng = DeltaStreamEngine(_program(), TASK, n_streams=2)
        eng.checkpoint(str(tmp_path))
        with pytest.raises(ValueError, match="logical shape"):
            DeltaStreamEngine.restore(str(tmp_path), _program(), TASK,
                                      n_streams=4)


class TestSupervisorPolicies:
    def _srv(self, policy, n_streams=2, backend="fused"):
        eng = DeltaStreamEngine(_program(backend), TASK,
                                n_streams=n_streams)
        return ResilientStreamServer(DeltaStreamBatcher(eng), policy)

    def test_bounded_queue_rejects_with_result(self):
        srv = self._srv(ResiliencePolicy(max_queue=2))
        rng = np.random.default_rng(0)
        outcomes = [srv.submit(_frames(50, rng)) for _ in range(6)]
        # 2 admitted straight into slots? no — admission happens on tick;
        # all 6 queue first, so 2 fit the bound and 4 reject
        assert [adm for _, adm in outcomes] == [True] * 2 + [False] * 4
        rejected = [r for r in srv.results if r.status == "rejected"]
        assert len(rejected) == 4
        assert rejected[0].error["reason"] == "queue_full"
        assert srv.counters["rejected"] == 4

    def test_deadline_sheds_queued_not_running(self):
        srv = self._srv(ResiliencePolicy(max_queue=32, deadline_ticks=3))
        rng = np.random.default_rng(1)
        running = [srv.submit(_frames(40, rng))[0] for _ in range(2)]
        waiting = [srv.submit(_frames(40, rng))[0] for _ in range(2)]
        shed = []
        for _ in range(10):
            shed += [r for r in srv.tick() if r.status == "shed"]
        assert sorted(r.uid for r in shed) == waiting
        assert srv.counters["shed"] == 2
        assert shed[0].error["reason"] == "deadline"
        # the admitted streams keep their slots and finish
        active = [r for r in srv.batcher.slots if r is not None]
        assert sorted(r.uid for r in active) == running

    def test_quarantine_reject_frees_slot_with_structured_error(self):
        pol = ResiliencePolicy(quarantine_after=2, on_quarantine="reject",
                               check_every=100)
        srv = self._srv(pol)
        rng = np.random.default_rng(2)
        frames = _frames(20, rng)
        frames[2, :] = np.nan
        frames[4, :] = np.nan
        uid, _ = srv.submit(frames)
        good_uid, _ = srv.submit(_frames(20, rng))
        quarantined = []
        while any(r is not None for r in srv.batcher.slots) \
                or srv.batcher.queue:
            quarantined += [r for r in srv.tick()
                            if r.status == "quarantined"]
        assert [r.uid for r in quarantined] == [uid]
        assert quarantined[0].error["reason"] == "poison_frames"
        assert quarantined[0].stats is not None
        assert srv.counters["quarantined"] == 1
        assert srv.counters["recovered"] == 0
        ok = [r for r in srv.results if r.status == "ok"]
        assert [r.uid for r in ok] == [good_uid]

    def test_quarantine_readmit_recovers_bitwise(self):
        """Sanitize-and-resume: the recovered stream's outputs equal a
        clean same-width run of the sanitized frames, bitwise — rollback
        plus the guard make the poison episode invisible."""
        pol = ResiliencePolicy(quarantine_after=2, on_quarantine="readmit",
                               check_every=4)
        srv = self._srv(pol, backend="fused_q8")
        rng = np.random.default_rng(3)
        frames = _frames(25, rng)
        frames[6, :] = np.nan
        frames[11, 0] = np.inf
        uid, _ = srv.submit(frames)
        done = []
        while not done:
            done = [r for r in srv.tick() if r.status == "ok"]
        assert done[0].uid == uid
        assert done[0].error == {"recovered_after_quarantine": True}
        assert srv.counters["quarantined"] == 1
        assert srv.counters["recovered"] == 1
        ref = DeltaStreamEngine(_program("fused_q8"), TASK, n_streams=2)
        ref.open_stream()
        xs = np.zeros((25, 2, 8), np.float32)
        xs[:, 0] = sanitize_frames(frames)
        want = np.asarray(ref.step_many(xs))[:, 0]
        got = np.stack([np.asarray(o) for o in done[0].outputs])
        np.testing.assert_array_equal(got, want)

    def test_state_corruption_detected_and_recovered(self):
        pol = ResiliencePolicy(check_every=4, on_quarantine="readmit")
        srv = self._srv(pol, backend="fused_q8")
        rng = np.random.default_rng(4)
        frames = _frames(30, rng)
        uid, _ = srv.submit(frames)
        for _ in range(6):
            srv.tick()
        corrupt_slot_state(srv.engine, 0)
        done = []
        while not done:
            done = [r for r in srv.tick() if r.status == "ok"]
        assert srv.counters["quarantined"] == 1
        ref = DeltaStreamEngine(_program("fused_q8"), TASK, n_streams=2)
        ref.open_stream()
        xs = np.zeros((30, 2, 8), np.float32)
        xs[:, 0] = frames
        want = np.asarray(ref.step_many(xs))[:, 0]
        got = np.stack([np.asarray(o) for o in done[0].outputs])
        np.testing.assert_array_equal(got, want)

    def test_corruption_escaping_check_cadence_caught_at_harvest(self):
        """A slot corrupted between check ticks can run to completion
        before the screen sees it; the harvest-time stats (already
        synced) carry bad_state_steps, so the supervisor quarantines
        there instead of shipping NaN outputs — and the readmitted replay
        is bitwise a clean run."""
        pol = ResiliencePolicy(check_every=10000, on_quarantine="readmit")
        srv = self._srv(pol, backend="fused_q8")
        rng = np.random.default_rng(7)
        frames = _frames(12, rng)
        uid, _ = srv.submit(frames)
        for _ in range(3):
            srv.tick()
        corrupt_slot_state(srv.engine, 0)     # finishes before any check
        done = []
        while not done:
            done = [r for r in srv.tick() if r.status == "ok"]
        assert done[0].uid == uid
        assert done[0].error == {"recovered_after_quarantine": True}
        assert srv.counters["quarantined"] == 1
        assert srv.counters["recovered"] == 1
        ref = DeltaStreamEngine(_program("fused_q8"), TASK, n_streams=2)
        ref.open_stream()
        xs = np.zeros((12, 2, 8), np.float32)
        xs[:, 0] = frames
        want = np.asarray(ref.step_many(xs))[:, 0]
        got = np.stack([np.asarray(o) for o in done[0].outputs])
        np.testing.assert_array_equal(got, want)

    def test_corruption_at_harvest_reject_path(self):
        pol = ResiliencePolicy(check_every=10000, on_quarantine="reject")
        srv = self._srv(pol)
        uid, _ = srv.submit(_frames(10, np.random.default_rng(8)))
        for _ in range(2):
            srv.tick()
        corrupt_slot_state(srv.engine, 0)
        done = []
        while not done:
            done = [r for r in srv.tick() if r.status == "quarantined"]
        assert done[0].uid == uid
        assert done[0].error["reason"] == "state_corruption"
        assert done[0].error["detected_at"] == "harvest"
        assert done[0].stats["bad_state_steps"] > 0

    def test_overload_raises_theta_and_drains_back(self):
        """Queue pressure past the watermark raises Θ_h through the
        dynamic controller; draining decays it back to the baseline."""
        pol = ResiliencePolicy(max_queue=256, overload_queue=4,
                               check_every=2, theta_max=0.5)
        srv = self._srv(pol)
        rng = np.random.default_rng(5)
        base = srv.engine.thresholds.theta_h
        for _ in range(30):                     # flood: depth >> watermark
            srv.submit(_frames(12, rng))
        for _ in range(6):
            srv.tick()
        high = srv.engine.theta_h
        assert high > base
        assert srv.counters["theta_raises"] >= 1
        assert srv.theta_peak == pytest.approx(high, rel=1e-6)
        srv.run_until_drained()
        for _ in range(40):                     # idle ticks decay Θ
            srv.tick()
        assert srv.engine.theta_h == pytest.approx(base, abs=1e-6)

    def test_overload_requires_exclusive_theta_control(self):
        eng = DeltaStreamEngine(_program(), TASK,
                                dynamic_target_fired=0.2)
        with pytest.raises(ValueError, match="dynamic"):
            ResilientStreamServer(DeltaStreamBatcher(eng),
                                  ResiliencePolicy(overload_queue=4))
        pol = ThresholdPolicy(theta_x=0.05, per_layer_h=(0.0, 0.4))
        eng2 = DeltaStreamEngine(_program(), TASK, thresholds=pol)
        with pytest.raises(ValueError, match="per-layer"):
            ResilientStreamServer(DeltaStreamBatcher(eng2),
                                  ResiliencePolicy(overload_queue=4))
        with pytest.raises(ValueError, match="per-layer"):
            eng2.set_theta_h(0.3)

    def test_heartbeat_gap_counted(self):
        import time
        pol = ResiliencePolicy(heartbeat_deadline_s=0.05)
        srv = self._srv(pol)
        srv.submit(_frames(30, np.random.default_rng(6)))
        srv.tick()
        time.sleep(0.2)                          # a stall between ticks
        srv.tick()
        assert srv.counters["missed_heartbeats"] >= 1


class TestChaosSoak:
    """The S4 session-churn soak: ~200 random-length streams through 8
    slots on the q8 tile backend, with seeded poison, one slot-state
    corruption, stalls, and a mid-soak crash+restore. Asserts the full
    chaos invariant plus run-to-run determinism of every tick-based
    counter."""

    N_ARRIVALS = 200
    N_STREAMS = 8

    def _arrivals(self):
        rng = np.random.default_rng(1234)
        arrivals, t = [], 0
        for _ in range(self.N_ARRIVALS):
            arrivals.append((t, _frames(int(rng.integers(5, 30)), rng)))
            t += int(rng.integers(0, 4))
        return arrivals

    def _plan(self):
        return FaultPlan(seed=99, poison_streams=(17, 90), inf_streams=(55,),
                         poison_frames=4, corrupt_slot_at=((40, 3),),
                         stall_ticks=(), crash_at_tick=120)

    def _run(self, ckpt_dir):
        prog = _program("fused_q8")
        pol = ResiliencePolicy(max_queue=64, deadline_ticks=60,
                               quarantine_after=3, on_quarantine="readmit",
                               check_every=8, ckpt_dir=ckpt_dir,
                               ckpt_every=32)
        return serve_resumable(prog, TASK, self._arrivals(), pol,
                               n_streams=self.N_STREAMS,
                               fault_plan=self._plan())

    def test_churn_soak_chaos_invariant(self, tmp_path):
        results, srv, restarts = self._run(str(tmp_path / "a"))
        assert restarts == 1                     # the planned crash fired
        assert len(results) == self.N_ARRIVALS   # every arrival terminal
        statuses = {s: sum(1 for r in results.values() if r.status == s)
                    for s in ("ok", "shed", "rejected", "quarantined")}
        assert sum(statuses.values()) == self.N_ARRIVALS
        assert statuses["ok"] >= self.N_ARRIVALS // 2
        # the poisoned streams hit quarantine and recovered in place
        assert srv.counters["quarantined"] >= 2
        assert srv.counters["recovered"] == srv.counters["quarantined"]
        assert srv.counters["poison_frames"] > 0
        rep = srv.report()
        assert rep["engine"]["poison_steps"] > 0
        # a checkpoint was published and its sidecar agrees
        side = load_sidecar(str(tmp_path / "a"))
        assert side is not None and side["tick"] % 32 == 0

        # THE chaos invariant: every completed stream — poisoned,
        # corrupted, or clean, on either side of the crash — is bitwise a
        # clean same-width reference run of its sanitized frames
        plan = self._plan()
        ref = DeltaStreamEngine(_program("fused_q8"), TASK,
                                n_streams=self.N_STREAMS)
        checked = 0
        for i, (_, frames) in enumerate(self._arrivals()):
            r = results[i]
            if r.status != "ok":
                continue
            fed = sanitize_frames(plan.poison_stream(i, frames))
            ref.reset()
            sid = ref.open_stream()
            xs = np.zeros((len(fed), self.N_STREAMS, TASK.input_size),
                          np.float32)
            xs[:, sid] = fed
            want = np.asarray(ref.step_many(xs))[:, sid]
            got = np.stack([np.asarray(o) for o in r.outputs])
            np.testing.assert_array_equal(
                got, want, err_msg=f"arrival {i} diverged")
            checked += 1
        assert checked == statuses["ok"]

        # determinism: the identical seeded soak reproduces every
        # tick-based counter and status exactly
        results2, srv2, restarts2 = self._run(str(tmp_path / "b"))
        assert restarts2 == restarts
        wall_keys = ("straggler_flags", "missed_heartbeats")
        c1 = {k: v for k, v in srv.counters.items() if k not in wall_keys}
        c2 = {k: v for k, v in srv2.counters.items() if k not in wall_keys}
        assert c1 == c2
        assert {i: r.status for i, r in results.items()} == \
               {i: r.status for i, r in results2.items()}
        assert srv2.report()["engine"]["steps"] == rep["engine"]["steps"]


class TestServeResumableRestore:
    def test_no_crash_no_restart(self, tmp_path):
        prog = _program()
        rng = np.random.default_rng(0)
        arrivals = [(0, _frames(8, rng)) for _ in range(6)]
        pol = ResiliencePolicy(ckpt_dir=str(tmp_path), ckpt_every=4)
        results, srv, restarts = serve_resumable(prog, TASK, arrivals, pol,
                                                 n_streams=2)
        assert restarts == 0
        assert all(r.status == "ok" for r in results.values())

    def test_crash_without_checkpoint_dir_replays_all(self):
        prog = _program()
        rng = np.random.default_rng(1)
        arrivals = [(0, _frames(8, rng)) for _ in range(4)]
        plan = FaultPlan(crash_at_tick=5)
        pol = ResiliencePolicy()                 # no ckpt_dir
        results, srv, restarts = serve_resumable(prog, TASK, arrivals, pol,
                                                 n_streams=2,
                                                 fault_plan=plan)
        assert restarts == 1
        assert all(r.status == "ok" for r in results.values())

    def test_crash_budget_exhaustion_propagates(self, tmp_path):
        prog = _program()
        rng = np.random.default_rng(2)
        arrivals = [(0, _frames(30, rng)) for _ in range(4)]

        class AlwaysCrash(FaultPlan):
            def maybe_crash(self, tick):
                if tick == 5:
                    raise SimulatedCrash("hard fault, every incarnation")
        pol = ResiliencePolicy(max_restarts=2, ckpt_dir=str(tmp_path))
        with pytest.raises(SimulatedCrash):
            serve_resumable(prog, TASK, arrivals, pol, n_streams=2,
                            fault_plan=AlwaysCrash())

    def test_overload_flood_completes_every_stream(self):
        """A fault-free flood through ``serve_resumable`` with the
        overload controller on: Θ_h rises above its baseline under queue
        pressure, every stream still completes, and idle ticks bring Θ_h
        back to its baseline."""
        rng = np.random.default_rng(1235)
        flood, t = [], 0
        for _ in range(24):
            flood.append((t, _frames(int(rng.integers(5, 30)), rng)))
            t += int(rng.integers(0, 2))
        pol = ResiliencePolicy(max_queue=256, deadline_ticks=None,
                               check_every=4, overload_queue=4,
                               theta_max=0.5)
        results, srv, restarts = serve_resumable(_program("fused_q8"), TASK,
                                                 flood, pol, n_streams=8)
        assert restarts == 0
        assert len(results) == len(flood)
        assert all(r.status == "ok" for r in results.values())
        base = float(srv._theta_base)
        assert srv.theta_peak > base
        for _ in range(pol.check_every * 12):
            srv.tick()
        # exact at the carry's float32 precision
        assert np.float32(srv.engine.theta_h) == np.float32(base)
