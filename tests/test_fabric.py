"""Distributed serving fabric: mesh-sharded fleet, async router, elastic
rebalance.

The contract under test, in three rings:

* **fleet** — a :class:`ShardedStreamFleet` tick over 8 host devices is
  BITWISE the standalone same-width engine per shard (the PR 6/7
  fixed-tile rule lifted onto a mesh), and malformed fleets are rejected
  with actionable errors (nearest valid widths, n >= 1 meshes);
* **router** — the two accounting books close exactly: every submitted
  uid reaches exactly one terminal (``submitted == completed + rejected
  + shed + quarantined + outstanding``) and every frame the router
  staged is a step the engines executed (``frames_out ==
  harvested_steps``), in fabric mode and in both pool flavors;
* **rebalance** — a mid-load scale-down drain-checkpoints the dying
  shard (restorable by PR 7's ``DeltaStreamEngine.restore``), replays
  its streams from frame 0 on survivors, and every completed stream —
  replayed or surviving — still matches a clean same-width reference
  run bitwise.

Runs on the conftest's forced 8-device host platform.
"""
import jax
import numpy as np
import pytest

from repro.core.program import compile_delta_program
from repro.dist.elastic import best_mesh, scale_event
from repro.dist.serving import ShardedStreamFleet
from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.quant.export import quantize_delta_model
from repro.serve.engine import DeltaStreamEngine
from repro.serve.loadgen import (fabric_parity, poisson_arrivals,
                                 run_fabric_load)
from repro.serve.resilience import ResiliencePolicy, ResilientStreamServer
from repro.serve.router import RouterPolicy, StreamRouter
from repro.serve.scheduler import DeltaStreamBatcher

TASK = GruTaskConfig(8, 16, 2, 3, task="regression",
                     theta_x=0.05, theta_h=0.05)


def _program(backend="fused", key=0):
    params = init_gru_model(jax.random.PRNGKey(key), TASK)
    if backend == "fused_q8":
        return quantize_delta_model(params)
    return compile_delta_program(params, backend=backend)


def _fleet(backend="fused_q8", n_shards=4, streams_per_shard=2):
    return ShardedStreamFleet(_program(backend), TASK,
                              n_streams=n_shards * streams_per_shard,
                              mesh=best_mesh(n_shards, model_parallel=1))


def _assert_parity(arrivals, results, fleet):
    """Every completed stream bitwise equals a clean same-width reference
    run (``fabric_parity`` raises on the first that does not)."""
    completed = sum(r.status == "ok" for r in results.values())
    assert completed, "nothing completed; the parity check would be vacuous"
    assert fabric_parity(arrivals, results, fleet) == completed


class TestElasticValidation:
    def test_best_mesh_rejects_nonpositive(self):
        with pytest.raises(ValueError, match="n_devices"):
            best_mesh(0)
        with pytest.raises(ValueError, match="n_devices"):
            best_mesh(-3)

    def test_best_mesh_none_takes_all_devices(self):
        # regression: `n_devices or avail` treated an EXPLICIT 0 as "all";
        # only None may mean "use every local device"
        mesh = best_mesh(None, model_parallel=1)
        assert mesh.shape["data"] == len(jax.devices())

    def test_scale_event_rejects_scale_to_zero(self):
        mesh = best_mesh(4, model_parallel=1)
        with pytest.raises(ValueError, match="n_devices"):
            scale_event(mesh, 0)
        with pytest.raises(ValueError, match="n_devices"):
            scale_event(mesh, -1)


class TestFleet:
    def test_indivisible_widths_named_in_error(self):
        with pytest.raises(ValueError) as ei:
            ShardedStreamFleet(_program(), TASK, n_streams=30,
                               mesh=best_mesh(8, model_parallel=1))
        msg = str(ei.value)
        assert "24 (3/shard)" in msg and "32 (4/shard)" in msg

    def test_fleet_needs_data_axis(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
        with pytest.raises(ValueError, match="data"):
            ShardedStreamFleet(_program(), TASK, n_streams=8, mesh=mesh)

    @pytest.mark.parametrize("backend", ["fused", "fused_q8"])
    def test_sharded_step_bitwise_vs_single_device(self, backend):
        """Each shard of the 8-way mesh tick equals a standalone engine of
        the per-shard tile width fed that shard's rows — bitwise, fp32 and
        q8 (the tentpole's core invariant)."""
        fleet = _fleet(backend, n_shards=8, streams_per_shard=2)
        rng = np.random.default_rng(0)
        xs = rng.standard_normal(
            (12, fleet.n_streams, TASK.input_size)).astype(np.float32)
        got = np.asarray(fleet.step_many(xs))
        b = fleet.streams_per_shard
        for s in range(fleet.n_shards):
            ref = fleet.reference_engine()
            want = np.asarray(ref.step_many(xs[:, s * b:(s + 1) * b]))
            assert want.tobytes() == got[:, s * b:(s + 1) * b].tobytes(), \
                (backend, s)

    def test_session_accounting_and_report(self):
        fleet = _fleet(n_shards=4, streams_per_shard=2)
        sid = fleet.open_stream(2)
        assert fleet.shard_of(sid) == 2
        assert fleet.active_slots(2) == 1 and fleet.active_slots() == 1
        rng = np.random.default_rng(1)
        for _ in range(5):
            fleet.step(rng.standard_normal(
                (fleet.n_streams, TASK.input_size)).astype(np.float32))
        stats = fleet.close_stream(sid)
        assert stats["steps"] == 5 and stats["shard"] == 2
        assert fleet.active_slots() == 0
        rep = fleet.report()
        assert rep["n_shards"] == 4 and rep["ticks"] == 5
        assert len(rep["per_shard"]) == 4


def _run_load(router, arrivals, **kw):
    return run_fabric_load(router, arrivals, **kw)


class TestRouter:
    def _arrivals(self, n=30, seed=3):
        return poisson_arrivals(n, 3.0, min_len=3, max_len=8,
                                input_size=TASK.input_size, seed=seed)

    def test_fabric_conservation_and_parity(self):
        fleet = _fleet(n_shards=4, streams_per_shard=2)
        router = StreamRouter(fleet, RouterPolicy(max_queue=4))
        arrivals = self._arrivals()
        summary = _run_load(router, arrivals)
        cons = router.conservation()
        assert cons["conserved"] and cons["queued"] == 0 \
            and cons["in_flight"] == 0
        assert cons["submitted"] == len(arrivals) \
            == cons["completed"] + cons["rejected"] + cons["shed"]
        assert cons["frames_conserved"] and cons["frames_out"] > 0
        _assert_parity(arrivals, summary.results, fleet)
        # per-shard books sum exactly to the fleet-wide totals
        rep = router.report()
        for key in ("submitted", "completed", "rejected", "frames_out",
                    "harvested_steps"):
            assert sum(b[key] for b in rep["per_shard"]) == cons[key], key

    def test_jsq_spreads_an_idle_fleet(self):
        fleet = _fleet(n_shards=4, streams_per_shard=2)
        router = StreamRouter(fleet, RouterPolicy())
        frames = np.ones((3, TASK.input_size), np.float32)
        shards = []
        for _ in range(4):
            router.submit(frames)
        for q_id, q in enumerate(router.queues):
            shards += [q_id] * len(q)
        assert sorted(shards) == [0, 1, 2, 3]

    def test_reject_on_full_queue_is_a_terminal_result(self):
        fleet = _fleet(n_shards=2, streams_per_shard=1)
        router = StreamRouter(fleet, RouterPolicy(max_queue=1))
        frames = np.ones((3, TASK.input_size), np.float32)
        outcomes = [router.submit(frames)[1] for _ in range(4)]
        assert outcomes == [True, True, False, False]
        rejected = [r for r in router.results if r.status == "rejected"]
        assert len(rejected) == 2
        assert all(r.error["reason"] == "queue_full" for r in rejected)
        router.run_until_drained()
        assert router.conservation()["conserved"]

    def test_deadline_sheds_queued_not_running(self):
        fleet = _fleet(n_shards=2, streams_per_shard=1)
        router = StreamRouter(fleet, RouterPolicy(max_queue=8,
                                                  deadline_ticks=2))
        frames = np.ones((20, TASK.input_size), np.float32)
        for _ in range(6):
            router.submit(frames)
        done = router.run_until_drained()
        by = {s: sum(1 for r in done if r.status == s)
              for s in ("ok", "shed")}
        assert by["ok"] == 2 and by["shed"] == 4  # slots run, queue starves
        cons = router.conservation()
        assert cons["conserved"] and cons["shed"] == 4

    def test_nonfinite_admission_matches_batcher_semantics(self):
        fleet = _fleet(n_shards=2, streams_per_shard=1)
        router = StreamRouter(fleet, RouterPolicy())
        bad = np.ones((3, TASK.input_size), np.float32)
        bad[1, 0] = np.nan
        with pytest.raises(ValueError, match="non-finite"):
            router.submit(bad)

    def test_pool_mode_batcher_conservation(self):
        workers = [DeltaStreamBatcher(
            DeltaStreamEngine(_program(), TASK, n_streams=2))
            for _ in range(3)]
        router = StreamRouter(workers, RouterPolicy(max_queue=4))
        arrivals = self._arrivals(n=20, seed=5)
        summary = _run_load(router, arrivals)
        cons = router.conservation()
        assert cons["conserved"] and cons["frames_conserved"]
        assert cons["submitted"] == 20
        assert all(r.status in ("ok", "rejected")
                   for r in summary.results.values())
        # the router's book agrees with each worker's own counters
        assert sum(w.counters["harvested"] for w in workers) \
            == cons["completed"]

    def test_pool_mode_resilient_statuses_pass_through(self):
        workers = [ResilientStreamServer(
            DeltaStreamBatcher(DeltaStreamEngine(_program(), TASK,
                                                 n_streams=2)),
            ResiliencePolicy(max_queue=8, quarantine_after=1,
                             on_quarantine="reject"))
            for _ in range(2)]
        router = StreamRouter(workers, RouterPolicy(
            max_queue=8, on_nonfinite="quarantine"))
        arrivals = self._arrivals(n=12, seed=7)
        bad = arrivals[4][1].copy()
        bad[0, 0] = np.inf
        arrivals[4] = (arrivals[4][0], bad)
        summary = _run_load(router, arrivals)
        statuses = sorted(r.status for r in summary.results.values())
        assert statuses.count("quarantined") == 1  # worker policy surfaced
        cons = router.conservation()
        assert cons["conserved"] and cons["quarantined"] == 1

    def test_pool_rejects_unknown_worker_type(self):
        with pytest.raises(TypeError, match="not a"):
            StreamRouter([object()])

    def test_scale_down_is_fabric_only(self):
        workers = [DeltaStreamBatcher(
            DeltaStreamEngine(_program(), TASK, n_streams=2))]
        router = StreamRouter(workers)
        with pytest.raises(RuntimeError, match="fabric-mode"):
            router.scale_down(0)


class TestRebalance:
    def test_replayed_streams_complete_bitwise(self, tmp_path):
        """The chaos invariant end to end: a shard dies mid-load with
        streams queued and in flight; its drain checkpoint restores on a
        single device; the displaced streams replay on survivors and every
        completed stream still matches a clean reference bitwise."""
        fleet = _fleet(n_shards=4, streams_per_shard=2)
        router = StreamRouter(fleet, RouterPolicy(max_queue=8))
        arrivals = poisson_arrivals(28, 4.0, min_len=4, max_len=10,
                                    input_size=TASK.input_size, seed=11)
        summary = _run_load(router, arrivals, scale_down_at=3,
                            scale_down_shard=1, ckpt_dir=str(tmp_path))
        assert summary.scale_info is not None
        assert fleet.n_shards == 3 and router.n_shards == 3
        cons = router.conservation()
        assert cons["conserved"] and cons["frames_conserved"]
        assert cons["rebalanced"] > 0
        replayed = [r for r in summary.results.values() if r.replayed]
        assert len(replayed) == cons["rebalanced"]
        assert all(r.status == "ok" for r in replayed)
        _assert_parity(arrivals, summary.results, fleet)
        # the drain checkpoint is a real PR 7 checkpoint: restorable into
        # a standalone engine of the shard's tile width
        eng = DeltaStreamEngine.restore(str(tmp_path), fleet.program, TASK,
                                        n_streams=fleet.streams_per_shard)
        assert eng.n_streams == fleet.streams_per_shard

    def test_displaced_latency_keeps_original_submit_tick(self, tmp_path):
        fleet = _fleet(n_shards=2, streams_per_shard=2)
        router = StreamRouter(fleet, RouterPolicy(max_queue=8))
        frames = np.ones((6, TASK.input_size), np.float32)
        uids = [router.submit(frames)[0] for _ in range(4)]
        router.tick()
        info = router.scale_down(0, ckpt_dir=str(tmp_path))
        assert info["replayed"] > 0
        done = router.run_until_drained()
        by_uid = {r.uid: r for r in done}
        for uid in uids:
            r = by_uid[uid]
            assert r.status == "ok" and r.submit_tick == 0
            if r.replayed:  # replay cost visible in the tick latency
                assert r.latency_ticks >= 6

    def test_cannot_scale_below_one_shard(self):
        fleet = _fleet(n_shards=2, streams_per_shard=1)
        router = StreamRouter(fleet)
        router.scale_down(0)
        with pytest.raises(ValueError, match="below one shard"):
            router.scale_down(0)


class TestObservabilityHooks:
    def _batcher(self, n_streams=2):
        return DeltaStreamBatcher(
            DeltaStreamEngine(_program(), TASK, n_streams=n_streams))

    def test_batcher_hooks_and_counters(self):
        b = self._batcher()
        frames = np.ones((4, TASK.input_size), np.float32)
        for _ in range(3):
            b.submit(frames, on_nonfinite="allow")
        assert b.counters["submitted"] == 3
        assert b.queue_depth() == 3 and b.active_slots() == 0
        assert b.free_slots() == 0  # 2 slots, 3 queued: nothing spare
        b.run_until_drained()
        assert b.queue_depth() == 0 and b.active_slots() == 0
        assert b.counters["admitted"] == 3
        assert b.counters["harvested"] == 3
        assert b.counters["ticks"] > 0

    def test_resilient_server_reads_pressure_through_hooks(self):
        """The overload watermark consumes the batcher's observability
        hook, not the private deque: a stubbed queue_depth alone drives
        admission and the Θ watermark."""
        b = self._batcher()
        srv = ResilientStreamServer(b, ResiliencePolicy(max_queue=4))
        assert srv.queue_depth() == 0 and srv.free_slots() == 2
        b.queue_depth = lambda: 99  # stub the hook; the deque stays empty
        frames = np.ones((4, TASK.input_size), np.float32)
        uid, admitted = srv.submit(frames)
        assert not admitted
        assert srv.results[-1].error["reason"] == "queue_full"
        assert srv.results[-1].error["depth"] == 99
