"""A single-stream ``DeltaStreamEngine`` dispatches one compiled call per
step: the ``[I]`` -> ``[1, I]`` reshape and the ``[0]`` slice of the output
run inside the jitted step (and inside the scan for a ``[T, I]`` chunk).

These tests hold that step to the multi-stream computation it wraps: the
engine's raw ``_one_step_fn`` / ``_steps_fn`` under ``jax.jit``, fed one
stream slot, with the slot taken outside. Outputs, state and carry must
match bit for bit, whatever the frame's shape and kind.

The engine holds its state and carry packed, one flat buffer per dtype,
so a batch-1 tick moves four buffers across the jit boundary; the packed
calls (steps, the session reset, snapshot and rollback) are held to the
raw closures on the unpacked trees, bit for bit, and the ``state`` /
``_carry`` views, ``host_carry()`` and checkpoints to the unpacked trees.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import checkpoint as ft_checkpoint
from repro.models.gru_rnn import (GruTaskConfig, init_gru_model,
                                  init_lstm_model)
from repro.serve.engine import DeltaStreamEngine

I_DIM, O_DIM, T = 8, 3, 6
TASK = GruTaskConfig(I_DIM, 16, 2, O_DIM, task="regression",
                     theta_x=0.1, theta_h=0.1)
INIT = {"gru": init_gru_model, "lstm": init_lstm_model}


def _engine(backend, cell="gru", n_streams=1):
    params = INIT[cell](jax.random.PRNGKey(0), TASK)
    return DeltaStreamEngine(params, TASK, backend=backend,
                             n_streams=n_streams)


def _frames():
    return np.random.default_rng(0).normal(size=(T, I_DIM)) \
        .astype(np.float32)


def _assert_trees_equal(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _assert_same(eng, state, carry):
    _assert_trees_equal((eng.state, eng._carry), (state, carry))


@pytest.mark.parametrize("backend", ["fused", "fused_q8"])
@pytest.mark.parametrize("kind", ["numpy", "device"])
@pytest.mark.parametrize("shape", ["[I]", "[1, I]"])
def test_step_matches_the_raw_step_bit_for_bit(backend, kind, shape):
    eng, ref = _engine(backend), _engine(backend)
    raw = jax.jit(ref._one_step_fn)
    state, carry = ref.state, ref._carry
    for f in _frames():
        x = f if shape == "[I]" else f[None]
        x = x if kind == "numpy" else jnp.asarray(x)
        got = eng.step(x)
        out, state, carry = raw(state, carry, jnp.asarray(f[None]))
        assert got.shape == (O_DIM,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(out[0]))
    _assert_same(eng, state, carry)


@pytest.mark.parametrize("backend", ["fused", "fused_q8"])
@pytest.mark.parametrize("kind", ["numpy", "device"])
def test_step_many_matches_the_raw_scan_bit_for_bit(backend, kind):
    eng, ref = _engine(backend), _engine(backend)
    xs = _frames()
    got = eng.step_many(xs if kind == "numpy" else jnp.asarray(xs))
    outs, state, carry = jax.jit(ref._steps_fn)(
        ref.state, ref._carry, jnp.asarray(xs[:, None, :]))
    assert got.shape == (T, O_DIM)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(outs[:, 0]))
    _assert_same(eng, state, carry)


@pytest.mark.parametrize("bad", [(I_DIM + 1,), (2, I_DIM), (1, 1, I_DIM)])
def test_wrong_frame_shape_still_raises(bad):
    eng = _engine("fused")
    with pytest.raises(ValueError, match=re.escape(
            f"shape [{I_DIM}] or [1, {I_DIM}], got {bad}")):
        eng.step(np.zeros(bad, np.float32))


@pytest.mark.parametrize("n_streams", [1, 8])
@pytest.mark.parametrize("backend", ["fused", "fused_q8", "fused_q4"])
@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_packed_steps_match_the_raw_closures_bit_for_bit(cell, backend,
                                                          n_streams):
    eng = _engine(backend, cell, n_streams)
    ref = _engine(backend, cell, n_streams)
    raw, raw_scan = jax.jit(ref._one_step_fn), jax.jit(ref._steps_fn)
    state, carry = ref.state, ref._carry
    xs = np.random.default_rng(1).normal(
        size=(T, n_streams, I_DIM)).astype(np.float32)
    # a single-stream engine takes and returns frames without the slot axis
    slot = (lambda a: a[..., 0, :]) if n_streams == 1 else (lambda a: a)
    for f in xs[:3]:
        got = eng.step(slot(f))
        out, state, carry = raw(state, carry, jnp.asarray(f))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(slot(out)))
    got = eng.step_many(slot(xs[3:]))
    outs, state, carry = raw_scan(state, carry, jnp.asarray(xs[3:]))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(slot(outs)))
    _assert_same(eng, state, carry)


@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_a_batch1_tick_moves_four_buffers(cell):
    eng = _engine("fused_q8", cell)
    assert len(eng._bufs) == 1 and eng._whole is None
    x = np.zeros((I_DIM,), np.float32)
    lowered = eng._step1.lower(eng._whole, eng._bufs, x)
    leaves = jax.tree_util.tree_leaves
    n = len(leaves(lowered.args_info)) + len(leaves(lowered.out_info))
    assert n == eng.report()["step_buffers"] == 4


def test_assigned_views_read_back_exactly():
    eng = _engine("fused_q8", n_streams=2)
    rng = np.random.default_rng(2)

    def noise(a):
        return jnp.asarray(rng.normal(size=a.shape).astype(a.dtype))

    state = jax.tree_util.tree_map(noise, eng.state)
    carry = jax.tree_util.tree_map(noise, eng._carry)
    eng.state = state
    _assert_trees_equal(eng.state, state)
    eng._carry = carry
    _assert_same(eng, state, carry)
    _assert_trees_equal(eng.host_carry(), jax.device_get(eng._carry))
    with pytest.raises(ValueError, match="layout"):
        eng._carry = {**carry, "fired_x": jnp.zeros((3,), jnp.float32)}
    with pytest.raises(ValueError, match="layout"):
        eng._carry = {k: v for k, v in carry.items() if k != "theta_h"}
    _assert_same(eng, state, carry)


@pytest.mark.parametrize("cell", ["gru", "lstm"])
def test_sessions_theta_and_rollback_match_the_raw_closures(cell):
    """``open_stream``'s masked reset, ``snapshot_streams``,
    ``set_theta_h`` and ``rollback_stream`` on the packed buffers give
    the rows the raw reset / merge closures give on the unpacked trees."""
    n = 4
    eng = _engine("fused_q8", cell, n)
    ref = _engine("fused_q8", cell, n)
    one = jax.jit(ref._one_step_fn)
    reset, merge = jax.jit(ref._reset_streams_fn), jax.jit(ref._merge_rows_fn)
    state, carry = ref.state, ref._carry
    snap = (state, carry)
    xs = np.random.default_rng(3).normal(size=(8, n, I_DIM)) \
        .astype(np.float32)

    def step_both(frames):
        nonlocal state, carry
        for f in frames:
            eng.step(f)
            _, state, carry = one(state, carry, jnp.asarray(f))

    step_both(xs[:2])
    sid = eng.open_stream()
    mask = jnp.asarray(np.arange(n) == sid)
    state, carry = reset(state, carry, mask)
    snap = merge(*snap, state, carry, mask)
    _assert_same(eng, state, carry)
    step_both(xs[2:5])
    eng.snapshot_streams([sid])
    snap = merge(*snap, state, carry, mask)
    step_both(xs[5:])
    eng.set_theta_h(0.3)
    carry = {**carry, "theta_h": jnp.float32(0.3)}
    assert eng.theta_h == pytest.approx(0.3)
    assert eng.rollback_stream(sid) == 3
    state, carry = merge(state, carry, *snap, mask)
    _assert_same(eng, state, carry)
    _assert_trees_equal((eng._snap_state, eng._snap_carry), snap)
    _assert_trees_equal(eng.host_carry(), jax.device_get(carry))


def test_a_checkpoint_of_unpacked_trees_restores_and_continues(tmp_path):
    """A checkpoint holds the unpacked trees (the format engines wrote
    before the state was packed): one written from the raw closures'
    trees restores into the packed engine, which then steps on bit for
    bit with the raw closures."""
    n = 2
    ref = _engine("fused_q8", n_streams=n)
    one = jax.jit(ref._one_step_fn)
    state, carry = ref.state, ref._carry
    xs = np.random.default_rng(4).normal(size=(7, n, I_DIM)) \
        .astype(np.float32)
    for f in xs[:4]:
        _, state, carry = one(state, carry, jnp.asarray(f))
    ft_checkpoint.save(str(tmp_path), 4, {
        "state": state, "carry": carry, "snap_state": ref.state,
        "snap_carry": ref._carry,
        "meta": {"n_steps": np.int64(4),
                 "slot_busy": np.zeros((n,), bool),
                 "slot_opened_at": np.zeros((n,), np.int64),
                 "snap_steps": np.zeros((n,), np.int64)}})
    eng = DeltaStreamEngine.restore(str(tmp_path), ref.program, TASK,
                                    n_streams=n)
    _assert_same(eng, state, carry)
    _assert_trees_equal((eng._snap_state, eng._snap_carry),
                        (ref.state, ref._carry))
    for f in xs[4:]:
        got = eng.step(f)
        out, state, carry = one(state, carry, jnp.asarray(f))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(out))
    _assert_same(eng, state, carry)
    assert eng.report()["steps"] == 7
