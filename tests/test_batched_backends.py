"""Stream tiles: one weight pass serves a [B, ...] tile of streams.

A backend is registered once per kernel; whether a call runs one stream
or a tile is the shape of its input. The contract under test:

* A tile is the same math as its streams run alone. q8 is code-exact to
  each stream served at ``[T, 1, I]``. fp32 is bitwise to each stream
  served in a tile of the same width (here: the stream replicated), and
  within float tolerance at ``[T, 1, I]``: XLA's matmul row results move
  ~1 ulp with the number of rows, which the q8 grid absorbs.
* Union compaction must not leak between streams: at a FIXED tile width,
  swapping the companion streams (which changes the set of fired columns
  the tile fetches) leaves a stream's outputs bit-identical in fp32 and
  code-exact in q8.
* The serving engine never swaps the program. Its ``report()`` prices a
  tile (one weight pass at the union firing) where ``n_streams > 1`` and
  the kernel compacts on the union (``BackendSpec.union_compaction``),
  and keeps each stream's served-alone accounting unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deltagru import deltagru_sequence, init_gru_stack
from repro.core.deltalstm import deltalstm_sequence, init_lstm_stack
from repro.core.perf_model import estimate_batched_tile, union_sparsity
from repro.core.program import compile_delta_program
from repro.core.sparsity import GruDims
from repro.models.gru_rnn import GruTaskConfig, init_gru_model, init_lstm_model
from repro.quant.export import quantize_stack
from repro.serve.engine import DeltaStreamEngine, GruStreamEngine
from repro.serve.scheduler import GruStreamBatcher

THETAS = [(0.0, 0.0), (0.05, 0.1)]
CELLS = ("gru", "lstm")
FUSED = ("fused", "fused_q8", "fused_q4")


def _gru_stack_and_xs(key=0, i=14, h=32, layers=2, t=16, b=4, scale=0.5):
    params = init_gru_stack(jax.random.PRNGKey(key), i, h, layers)
    xs = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(key), 1),
                           (t, b, i)) * scale
    return params, xs


def _lstm_stack_and_xs(key=2):
    params = init_lstm_stack(jax.random.PRNGKey(key), 12, 24, 2)
    xs = jax.random.normal(jax.random.PRNGKey(key + 1), (14, 3, 12)) * 0.5
    return params, xs


def _assert_fp32_tile_equals_solo(sequence, params, xs, tx, th, interpret):
    """Each stream of a fused fp32 tile: bitwise to the stream replicated
    across a tile of the same width, and within float tolerance of the
    stream run alone at [T, 1, I]."""
    got, _, _ = sequence(params, xs, tx, th, backend="fused",
                         interpret=interpret)
    b = xs.shape[1]
    for s in range(b):
        own = jnp.tile(xs[:, s:s + 1], (1, b, 1))
        same_width, _, _ = sequence(params, own, tx, th, backend="fused",
                                    interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got)[:, s],
                                      np.asarray(same_width)[:, 0])
        alone, _, _ = sequence(params, xs[:, s:s + 1], tx, th,
                               backend="fused", interpret=interpret)
        np.testing.assert_allclose(np.asarray(got)[:, s],
                                   np.asarray(alone)[:, 0], atol=1e-6)


class TestTileParity:
    @pytest.mark.parametrize("interpret", [None, True])
    @pytest.mark.parametrize("tx,th", THETAS)
    def test_gru_fp32_bit_identical_to_fused_on_same_tile(self, interpret,
                                                          tx, th):
        """One [T, B, I] GRU tile through fused equals each stream run
        alone, in jnp-ref AND Pallas-interpret modes."""
        params, xs = _gru_stack_and_xs(key=1, b=4)
        _assert_fp32_tile_equals_solo(deltagru_sequence, params, xs, tx, th,
                                      interpret)

    @pytest.mark.parametrize("interpret", [None, True])
    @pytest.mark.parametrize("tx,th", THETAS)
    def test_lstm_fp32_bit_identical_to_fused_on_same_tile(self, interpret,
                                                           tx, th):
        params, xs = _lstm_stack_and_xs()
        _assert_fp32_tile_equals_solo(deltalstm_sequence, params, xs, tx, th,
                                      interpret)

    @pytest.mark.parametrize("cell", CELLS)
    def test_q8_code_exact_to_fused_q8_on_same_tile(self, cell):
        """One [T, B, I] tile through fused_q8 and fused_q4 is code-exact
        to each stream run alone at [T, 1, I]: outputs and final state."""
        if cell == "gru":
            params, xs = _gru_stack_and_xs(key=4, b=3)
        else:
            params, xs = _lstm_stack_and_xs(key=5)
        for backend in ("fused_q8", "fused_q4"):
            prog = compile_delta_program(params, backend=backend, cell=cell)
            got, final, _ = prog.sequence(xs, 0.05, 0.1)
            for s in range(xs.shape[1]):
                want, final_s, _ = prog.sequence(xs[:, s:s + 1], 0.05, 0.1)
                np.testing.assert_array_equal(np.asarray(got)[:, s],
                                              np.asarray(want)[:, 0])
                for a, b in zip(jax.tree_util.tree_leaves(final),
                                jax.tree_util.tree_leaves(final_s)):
                    np.testing.assert_array_equal(np.asarray(a)[s],
                                                  np.asarray(b)[0])


class TestCompanionStreamIndependence:
    """Union compaction widens the fetched column set with the tile — it
    must never change any stream's MATH. At fixed tile width, replacing
    the companion streams (heterogeneous firing: loud companions fire
    blocks the quiet stream never touches) leaves the quiet stream's
    outputs bit-identical in fp32 and code-exact in q8."""

    def _tiles(self, key=7, t=16, b=3, i=14, scale_loud=3.0):
        k = jax.random.PRNGKey(key)
        quiet = jnp.cumsum(
            jax.random.normal(jax.random.fold_in(k, 0), (t, 1, i)) * 0.02,
            axis=0)
        comp_a = jax.random.normal(jax.random.fold_in(k, 1),
                                   (t, b - 1, i)) * scale_loud
        comp_b = jax.random.normal(jax.random.fold_in(k, 2),
                                   (t, b - 1, i)) * scale_loud
        return (jnp.concatenate([quiet, comp_a], axis=1),
                jnp.concatenate([quiet, comp_b], axis=1))

    @pytest.mark.parametrize("interpret", [None, True])
    def test_fp32_stream0_bitwise_under_companion_swap(self, interpret):
        params = init_gru_stack(jax.random.PRNGKey(8), 14, 32, 2)
        xs_a, xs_b = self._tiles()
        ya, _, _ = deltagru_sequence(params, xs_a, 0.05, 0.1,
                                     backend="fused",
                                     interpret=interpret)
        yb, _, _ = deltagru_sequence(params, xs_b, 0.05, 0.1,
                                     backend="fused",
                                     interpret=interpret)
        np.testing.assert_array_equal(np.asarray(ya)[:, 0],
                                      np.asarray(yb)[:, 0])
        # the companions really did differ (the swap was not a no-op)
        assert not np.array_equal(np.asarray(ya)[:, 1:],
                                  np.asarray(yb)[:, 1:])

    def test_q8_stream0_code_exact_under_companion_swap(self):
        params = init_gru_stack(jax.random.PRNGKey(9), 14, 32, 2)
        xs_a, xs_b = self._tiles(key=10)
        ya, _, _ = deltagru_sequence(params, xs_a, 0.05, 0.1,
                                     backend="fused_q8")
        yb, _, _ = deltagru_sequence(params, xs_b, 0.05, 0.1,
                                     backend="fused_q8")
        np.testing.assert_array_equal(np.asarray(ya)[:, 0],
                                      np.asarray(yb)[:, 0])

    def test_q8_batch_code_exact_to_solo_streams(self):
        """The Q8.8 grid absorbs XLA's cross-width reassociation jitter:
        every stream of a heterogeneous tile is code-exact to the same
        stream served alone."""
        params, xs = _gru_stack_and_xs(key=11, b=4)
        qparams, layouts = quantize_stack(params)
        prog = compile_delta_program(qparams, backend="fused_q8",
                                     layouts=layouts)
        got, _, _ = prog.sequence(xs, 0.05, 0.1)
        for s in range(xs.shape[1]):
            want, _, _ = prog.sequence(xs[:, s:s + 1], 0.05, 0.1)
            np.testing.assert_array_equal(np.asarray(got)[:, s],
                                          np.asarray(want)[:, 0])

    def test_fp32_batch_close_to_solo_streams(self):
        """fp32 batch-vs-solo is NOT a bitwise property (XLA matmul row
        results move ~1 ulp with the row count), but it is tight."""
        params, xs = _gru_stack_and_xs(key=12, b=4)
        got, _, _ = deltagru_sequence(params, xs, 0.05, 0.1,
                                      backend="fused")
        for s in range(xs.shape[1]):
            want, _, _ = deltagru_sequence(params, xs[:, s:s + 1], 0.05, 0.1,
                                           backend="fused")
            np.testing.assert_allclose(np.asarray(got)[:, s],
                                       np.asarray(want)[:, 0], atol=1e-5)


class TestUnionPerfModel:
    def test_union_sparsity_independent_streams(self):
        assert union_sparsity(1.0, 8) == 1.0
        assert union_sparsity(0.0, 8) == 0.0
        assert union_sparsity(0.9, 2) == pytest.approx(0.81)
        # union only ever fires MORE columns than one stream
        for b in (1, 2, 8):
            assert union_sparsity(0.7, b) <= 0.7

    def test_estimate_batched_tile_amortizes_weight_bytes(self):
        dims = GruDims(64, 128, 2)
        e1 = estimate_batched_tile(dims, 0.9, 0.9, 1)
        e8 = estimate_batched_tile(dims, 0.9, 0.9, 8)
        # the tile fetch grows with the union...
        assert e8["tile_weight_bytes"] > e1["tile_weight_bytes"]
        # ...but never past dense, so bytes/stream falls strictly
        assert e8["weight_bytes_per_stream"] < e1["tile_weight_bytes"]
        assert e8["throughput_ops"] > e1["throughput_ops"]


class TestEngineRouting:
    def _task(self, theta=0.05):
        return GruTaskConfig(8, 16, 2, 3, task="regression",
                             theta_x=theta, theta_h=theta)

    @pytest.mark.parametrize("n_streams", [1, 3])
    @pytest.mark.parametrize("backend", ("dense",) + FUSED)
    @pytest.mark.parametrize("cell", CELLS)
    def test_tile_pricing_follows_n_streams(self, cell, backend, n_streams):
        """The engine serves the program it was given at any width, names
        it in report(), and prices a tile exactly where n_streams > 1 and
        the kernel compacts on the union (every fused backend)."""
        task = self._task()
        init = init_gru_model if cell == "gru" else init_lstm_model
        prog = compile_delta_program(init(jax.random.PRNGKey(0), task),
                                     backend=backend, cell=cell)
        eng = DeltaStreamEngine(prog, task, n_streams=n_streams)
        assert eng.program is prog
        rng = np.random.default_rng(1)
        eng.step_many(rng.normal(size=(6, n_streams, 8)).astype(np.float32))
        rep = eng.report()
        assert rep["backend"] == eng.backend == backend
        tile = n_streams > 1 and backend != "dense"
        assert rep["weight_fetch"] == ("tile" if tile else "stream")
        tile_keys = {"union_gamma_dx", "union_gamma_dh",
                     "tile_est_latency_us", "tile_weight_bytes_per_step",
                     "weight_bytes_per_stream_per_step"}
        assert tile_keys & rep.keys() == (tile_keys if tile else set())
        if tile:
            assert rep["weight_bytes_per_stream_per_step"] == (
                rep["tile_weight_bytes_per_step"] / n_streams)

    @pytest.mark.parametrize("n_streams", [1, 3])
    @pytest.mark.parametrize("cell", CELLS)
    def test_matched_firing_bytes_ladder(self, cell, n_streams):
        """At matched firing the streamed bytes follow the weight width
        exactly, q4 = 0.5 q8 = 0.125 fp32, per stream and per tile. Firing
        is matched across widths by construction: one layer, every input
        component jumps by >= 1.0 each frame on the Q8.8 grid (all fire
        past theta_x = 0.25), and theta_h = 4 exceeds any hidden step."""
        task = GruTaskConfig(8, 16, 1, 3, task="regression",
                             theta_x=0.25, theta_h=4.0)
        init = init_gru_model if cell == "gru" else init_lstm_model
        params = init(jax.random.PRNGKey(3), task)
        rng = np.random.default_rng(4)
        mag = rng.integers(128, 257, size=(n_streams, 8)) / 256.0
        sign = np.where(np.arange(10) % 2 == 0, 1.0, -1.0)
        xs = (sign[:, None, None] * mag[None]).astype(np.float32)
        reps = {}
        for backend in FUSED:
            eng = DeltaStreamEngine(
                compile_delta_program(params, backend=backend, cell=cell),
                task, n_streams=n_streams)
            eng.step_many(xs)
            reps[backend] = rep = eng.report()
            assert rep["gamma_dx"] == 0.0 and rep["gamma_dh"] == 1.0
        per_stream = {b: r["mean_weight_bytes_per_step"]
                      for b, r in reps.items()}
        assert per_stream["fused_q4"] > 0
        assert per_stream["fused_q4"] == 0.5 * per_stream["fused_q8"] \
            == 0.125 * per_stream["fused"]
        if n_streams > 1:
            tile = {b: r["tile_weight_bytes_per_step"]
                    for b, r in reps.items()}
            assert tile == per_stream   # the union fires what each fires
            assert tile["fused_q4"] == 0.5 * tile["fused_q8"] \
                == 0.125 * tile["fused"]
            for b, r in reps.items():
                assert r["weight_bytes_per_stream_per_step"] == (
                    tile[b] / n_streams)

    @pytest.mark.parametrize("backend", FUSED)
    @pytest.mark.parametrize("cell", CELLS)
    def test_replicated_tile_fetch_equals_one_stream(self, cell, backend):
        """A tile of one stream replicated three times fetches exactly
        what the stream fetches alone (union compaction collapses
        identical streams), so each stream pays a third of it."""
        task = self._task()
        init = init_gru_model if cell == "gru" else init_lstm_model
        prog = compile_delta_program(init(jax.random.PRNGKey(5), task),
                                     backend=backend, cell=cell)
        rng = np.random.default_rng(6)
        walk = np.cumsum(rng.normal(size=(12, 1, 8)) * 0.1,
                         axis=0).astype(np.float32)
        solo = DeltaStreamEngine(prog, task)
        solo.step_many(walk)
        tile = DeltaStreamEngine(prog, task, n_streams=3)
        tile.step_many(np.tile(walk, (1, 3, 1)))
        r1, r3 = solo.report(), tile.report()
        assert r3["union_gamma_dx"] == r1["gamma_dx"]
        assert r3["union_gamma_dh"] == r1["gamma_dh"]
        assert r3["tile_weight_bytes_per_step"] == \
            r1["mean_weight_bytes_per_step"]
        assert r3["weight_bytes_per_stream_per_step"] == \
            r1["mean_weight_bytes_per_step"] / 3

    def test_tile_report_prices_union_firing(self):
        """Tile economics in report(): the union fires at least as much as
        the per-stream mean (union gamma <= mean gamma), the tile fetch
        sits between one stream's fetch and N of them, and bytes/stream
        beats the served-alone mean on heterogeneous traffic."""
        task = self._task()
        params = init_gru_model(jax.random.PRNGKey(1), task)
        n, t = 3, 24
        rng = np.random.default_rng(2)
        xs = rng.normal(size=(t, n, 8)).astype(np.float32)
        eng = GruStreamEngine(params, task, n_streams=n)
        eng.step_many(xs)
        rep = eng.report()
        assert rep["steps"] == t
        assert rep["union_gamma_dx"] <= rep["gamma_dx"] + 1e-6
        assert rep["union_gamma_dh"] <= rep["gamma_dh"] + 1e-6
        per_stream_mean = rep["mean_weight_bytes_per_step"]
        tile = rep["tile_weight_bytes_per_step"]
        assert per_stream_mean <= tile <= n * per_stream_mean + 1e-6
        assert rep["weight_bytes_per_stream_per_step"] == pytest.approx(
            tile / n, rel=1e-6)
        # heterogeneous random streams don't fire identical columns, so
        # sharing the fetch is a strict per-stream win
        assert rep["weight_bytes_per_stream_per_step"] < per_stream_mean

    def test_stream_engine_report_has_no_tile_fields(self):
        task = self._task()
        params = init_gru_model(jax.random.PRNGKey(0), task)
        eng = GruStreamEngine(params, task)
        eng.step(np.zeros(8, np.float32))
        rep = eng.report()
        for key in ("union_gamma_dx", "tile_weight_bytes_per_step",
                    "weight_bytes_per_stream_per_step"):
            assert key not in rep

    def test_step_equals_step_many_on_routed_engine(self):
        task = self._task()
        params = init_gru_model(jax.random.PRNGKey(3), task)
        n, t = 3, 12
        rng = np.random.default_rng(4)
        xs = rng.normal(size=(t, n, 8)).astype(np.float32)
        e1 = GruStreamEngine(params, task, n_streams=n)
        outs1 = np.stack([np.asarray(e1.step(x)) for x in xs])
        e2 = GruStreamEngine(params, task, n_streams=n)
        outs2 = np.asarray(e2.step_many(xs))
        np.testing.assert_allclose(outs1, outs2, atol=1e-6)
        r1, r2 = e1.report(), e2.report()
        for key in ("steps", "gamma_dx", "gamma_dh", "union_gamma_dx",
                    "union_gamma_dh", "tile_weight_bytes_per_step",
                    "mean_est_latency_us"):
            assert r1[key] == pytest.approx(r2[key], rel=1e-5), key

    def test_batcher_slot_recycling_isolated_on_tile_backend(self):
        """Slot recycling through the batcher on a tile-fetch engine: a
        quiet successor admitted into a loud predecessor's slot reports
        only its own served-alone accounting, even though both
        rode tiles whose union fetch the predecessor dominated."""
        task = self._task()
        params = init_gru_model(jax.random.PRNGKey(5), task)
        prog = compile_delta_program(params)
        eng = GruStreamEngine(prog, task, n_streams=2)
        assert eng.program is prog and eng.report()["weight_fetch"] == "tile"
        cb = GruStreamBatcher(eng)
        rng = np.random.default_rng(6)
        loud = [(3.0 * rng.normal(size=(6, 8))).astype(np.float32)
                for _ in range(2)]
        quiet = np.cumsum(rng.normal(size=(6, 8)) * 0.02,
                          axis=0).astype(np.float32)
        uids = [cb.submit(s) for s in loud] + [cb.submit(quiet)]
        by_uid = {r.uid: r for r in cb.run_until_drained()}
        got = by_uid[uids[2]].stats
        solo = GruStreamEngine(params, task)
        solo.step_many(quiet)
        want = solo.report()
        assert got["steps"] == 6
        assert got["gamma_dh"] == pytest.approx(want["gamma_dh"], abs=1e-5)
        assert got["w_bytes"] == pytest.approx(
            want["mean_weight_bytes_per_step"] * 6, rel=1e-3)
        assert by_uid[uids[0]].stats["w_bytes"] > 3 * got["w_bytes"]
