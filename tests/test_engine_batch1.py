"""A single-stream ``DeltaStreamEngine`` dispatches one compiled call per
step: the ``[I]`` -> ``[1, I]`` reshape and the ``[0]`` slice of the output
run inside the jitted step (and inside the scan for a ``[T, I]`` chunk).

These tests hold that step to the multi-stream computation it wraps: the
engine's raw ``_one_step_fn`` / ``_steps_fn`` under ``jax.jit``, fed one
stream slot, with the slot taken outside. Outputs, state and carry must
match bit for bit, whatever the frame's shape and kind.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.gru_rnn import GruTaskConfig, init_gru_model
from repro.serve.engine import DeltaStreamEngine

I_DIM, O_DIM, T = 8, 3, 6


def _engine(backend):
    task = GruTaskConfig(I_DIM, 16, 2, O_DIM, task="regression",
                         theta_x=0.1, theta_h=0.1)
    params = init_gru_model(jax.random.PRNGKey(0), task)
    return DeltaStreamEngine(params, task, backend=backend)


def _frames():
    return np.random.default_rng(0).normal(size=(T, I_DIM)) \
        .astype(np.float32)


def _assert_same(eng, state, carry):
    leaves = jax.tree_util.tree_leaves
    for got, want in zip(leaves((eng.state, eng._carry)),
                         leaves((state, carry)), strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


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
