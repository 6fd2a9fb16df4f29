"""Delta Mamba-2 / attention hybrid (Granite-4.0-H): decode against the
plain reference, theta=0 bitwise parity, programs and serving sessions.

At a tiny width with seeded random weights: the delta step over a token
sequence agrees with the benchmark's plain whole-sequence reference
(``bench/reference/granite4h_f32.py``, which imports nothing of the
program) and, bit for bit at theta=0, with the in-repo plain decode
:func:`repro.core.deltamamba2h.mamba2h_decode`. The engine keeps token
frames int32, updates the stacked state (SSM state, conv history, KV
cache) per slot, refuses a token past ``max_context``, and carries the
state through snapshot/rollback and checkpoints.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.deltamamba2h import (Mamba2hConfig, init_mamba2h_params,
                                     init_mamba2h_stack_state,
                                     mamba2h_decode, proj_volumes)
from repro.core.program import compile_delta_program
from repro.core.sparsity import cell_dims
from repro.core.thresholds import ThresholdPolicy
from repro.models.gru_rnn import GruTaskConfig
from repro.serve.engine import DeltaStreamEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = Mamba2hConfig(layer_types=("mamba", "attention", "mamba"), d_model=32,
                    vocab=256, mamba_heads=4, mamba_head_dim=16, d_state=16,
                    n_groups=1, d_conv=4, attn_heads=4, kv_heads=2,
                    attn_head_dim=8, intermediate=64, max_context=64)
TASK = GruTaskConfig(1, CFG.d_model, CFG.n_layers, CFG.vocab, task="lm")
# decode and whole-sequence sums differ in the last float32 place on the
# CPU, and the bfloat16 parts of a product's input can round apart: the
# tiny-width logits move by ~2e-6 of their RMS
REF_GAP = 1e-4


def _reference():
    path = os.path.join(ROOT, "bench", "reference", "granite4h_f32.py")
    spec = importlib.util.spec_from_file_location("granite4h_f32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _weights_dict(p) -> dict:
    return {"embed": p.embed, "norm_f": p.norm_f,
            "mamba": p.mamba._asdict(), "attn": p.attn._asdict(),
            "mlp": p.mlp._asdict()}


def _arch() -> dict:
    return {f: getattr(CFG, f) for f in CFG.__dataclass_fields__}


@pytest.fixture(scope="module")
def params():
    return init_mamba2h_params(jax.random.PRNGKey(3), CFG)


@pytest.fixture(scope="module")
def prog(params):
    return compile_delta_program({"mamba2h": [params]}, backend="dense",
                                 cell="mamba2h")


def _tokens(n, seed=0, streams=None):
    shape = (n, 1) if streams is None else (n, streams, 1)
    return np.random.default_rng(seed).integers(0, CFG.vocab, shape
                                                ).astype(np.int32)


def _engine(prog, **kw):
    return DeltaStreamEngine(prog, TASK, **kw)


def _plain(prog, toks):
    dec = jax.jit(lambda s, t: mamba2h_decode(prog.layers, s, t))
    st = init_mamba2h_stack_state(prog.layers, (1,))
    out = []
    for t in toks:
        y, st = dec(st, jnp.asarray(t)[None])
        out.append(np.asarray(y[0]))
    return np.stack(out)


def test_engine_decode_matches_the_plain_reference(params, prog):
    toks = _tokens(48)
    eng = _engine(prog)
    got = np.stack([np.asarray(eng.step(t)) for t in toks])
    want = _reference().replay(_weights_dict(params), [toks],
                               arch=_arch())[0]
    gap = np.max(np.abs(got - want)) / np.sqrt(np.mean(want ** 2))
    assert got.shape == (48, CFG.vocab) and got.dtype == np.float32
    assert gap < REF_GAP, gap


def test_theta0_step_is_bitwise_the_plain_decode(prog):
    toks = _tokens(24, seed=1)

    def step(s, t):
        y, s, _ = prog.step(s, t, 0.0, 0.0)
        return prog.apply_head(y), s

    step = jax.jit(step)
    st = prog.init_state((1,))
    got = []
    for t in toks:
        y, st = step(st, jnp.asarray(t)[None])
        got.append(np.asarray(y[0]))
    np.testing.assert_array_equal(np.stack(got), _plain(prog, toks))


def test_sequence_and_step_many_equal_repeated_step(prog):
    toks = _tokens(16, seed=2)
    eng = _engine(prog)
    one = np.stack([np.asarray(eng.step(t)) for t in toks])
    many = np.asarray(_engine(prog).step_many(toks))
    np.testing.assert_allclose(many, one, rtol=0, atol=1e-6)
    ys, final, stats = prog.sequence(jnp.asarray(toks)[:, None])
    np.testing.assert_allclose(np.asarray(prog.apply_head(ys[:, 0])), one,
                               rtol=0, atol=1e-6)
    assert int(final.stack.kv_len[0]) == 16
    assert float(stats["gamma_dx"]) < 0.5      # theta 0: changed inputs fire


def test_theta_above_zero_skips_columns(prog):
    th = ThresholdPolicy(0.05, 0.05)
    eng = DeltaStreamEngine(prog, TASK, thresholds=th)
    for t in _tokens(12, seed=4):
        eng.step(t)
    rep = eng.report()
    assert rep["gamma_dx"] > 0.0 and rep["gamma_dh"] > 0.0


def test_open_stream_resets_only_its_slot(prog):
    eng = _engine(prog, n_streams=3)
    sids = [eng.open_stream() for _ in range(3)]
    for t in _tokens(5, seed=5, streams=3):
        eng.step(t)
    before = jax.device_get(eng.state.stack)
    eng.close_stream(sids[1])
    assert eng.open_stream() == sids[1]
    after = jax.device_get(eng.state.stack)
    for name in ("ssm", "conv", "k", "v", "kv_len"):
        old, new = getattr(before, name), getattr(after, name)
        assert not np.any(np.asarray(new[1], np.float32)), name
        np.testing.assert_array_equal(new[[0, 2]], old[[0, 2]])
        assert np.any(np.asarray(old[1], np.float32)), name


def test_token_past_max_context_is_refused(prog):
    eng = _engine(prog)
    eng.step_many(_tokens(CFG.max_context - 1))
    eng.step(_tokens(1)[0])
    assert eng.report()["kv_len"] == [CFG.max_context]
    state = eng.state
    with pytest.raises(ValueError, match="max_context"):
        eng.step(_tokens(1)[0])
    with pytest.raises(ValueError, match="max_context"):
        eng.step_many(_tokens(2))
    assert eng.state is state                  # refused before the device


def test_engine_keeps_the_state_whole_and_donated(prog):
    """A family with a KV cache packs only its carry (one buffer per dtype:
    float32 and the int32 frame memory); its state leaves cross the step
    whole and donated, so the cache's buffer is reused from tick to
    tick."""
    eng = _engine(prog)
    toks = _tokens(3, seed=8)
    eng.step(toks[0])
    state = eng.state
    assert [b.dtype for b in eng._bufs] == [jnp.float32, jnp.int32]
    assert eng._layout.treedef == jax.tree_util.tree_structure(eng._carry)
    k = state.stack.k
    ptr = k.unsafe_buffer_pointer()
    eng.step(toks[1])
    assert k.is_deleted()
    assert eng.state.stack.k.unsafe_buffer_pointer() == ptr
    leaves = jax.tree_util.tree_leaves
    held = len(leaves(eng.state)) + 2
    assert eng.report()["step_buffers"] == len(leaves(prog)) + 2 * (1 + held)
    host = eng.host_carry()
    want = jax.device_get(eng._carry)
    assert host.keys() == want.keys()
    for key in want:
        assert host[key].dtype == want[key].dtype
        np.testing.assert_array_equal(host[key], want[key])


def test_int32_ids_survive_the_engine(prog):
    eng = _engine(prog)
    toks = _tokens(6, seed=6)
    for t in toks:
        eng.step(t)
    last = eng._carry["last_x"]
    assert last.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(last), toks[-1:])
    # an id outside the vocabulary is guarded like a non-finite frame: the
    # stream's last good id stands in for it, and the step is counted
    eng.step(np.array([CFG.vocab + 7], np.int32))
    assert eng.stats.poison_steps == 1.0
    np.testing.assert_array_equal(np.asarray(eng._carry["last_x"]),
                                  toks[-1:])


def test_snapshot_rollback_and_checkpoint_carry_the_state(prog, tmp_path):
    toks = _tokens(10, seed=7)
    eng = _engine(prog, n_streams=1)
    sid = eng.open_stream()
    for t in toks[:4]:
        eng.step(t[None])
    eng.snapshot_streams([sid])
    want = [np.asarray(eng.step(t[None])) for t in toks[4:7]]
    assert eng.rollback_stream(sid) == 4
    assert eng.report()["kv_len"] == [4]
    again = [np.asarray(eng.step(t[None])) for t in toks[4:7]]
    np.testing.assert_array_equal(np.stack(again), np.stack(want))
    eng.checkpoint(str(tmp_path))
    back = DeltaStreamEngine.restore(str(tmp_path), prog, TASK)
    for a, b in zip(jax.tree_util.tree_leaves(eng.state),
                    jax.tree_util.tree_leaves(back.state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_array_equal(np.asarray(back.step(toks[7][None])),
                                  np.asarray(eng.step(toks[7][None])))
    assert back.close_stream(sid)["kv_len"] == 8


def test_eq7_prices_the_bf16_projection_volumes(prog):
    eng = _engine(prog)
    xw, hw = proj_volumes(prog.layers)
    d, f = CFG.d_model, CFG.intermediate
    assert xw == (2 * d * CFG.in_proj_dim + d * (4 + 2 * 2) * 8
                  + 3 * d * 2 * f)
    assert hw == 2 * CFG.d_inner * d + 32 * d + 3 * f * d
    dims = cell_dims("mamba2h", 1, d, 3, layers=prog.layers)
    assert (dims.x_weight_volume, dims.h_weight_volume) == (xw, hw)
    eng.step(_tokens(1)[0])
    rep = eng.report()
    assert rep["weight_bits"] == 16
    assert rep["mean_weight_bytes_per_step"] == pytest.approx(2 * (xw + hw))
    with pytest.raises(ValueError, match="layers"):
        cell_dims("mamba2h", 1, d, 3)
