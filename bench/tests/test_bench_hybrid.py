"""The hybrid LM cell's counts and files: the weights ``step_mfu`` reads,
the bytes a decode tick needs (``granite4h.step_hbm_roofline``), the token
frames, and the reference's fired columns at theta 0."""
import importlib.util
import os

import numpy as np

from harness import spec, traffic

CELL = "granite4h-bf16-b1-decode"


def _bytes():
    path = os.path.join(spec.BENCH, "metrics", "_hybrid_bytes.py")
    s = importlib.util.spec_from_file_location("bench_hybrid_bytes", path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_granite_weights_and_tick_bytes():
    cfg = spec.load_cell(CELL).config
    assert spec.load_family(cfg).dense_weights(cfg) == 3_191_396_096
    b = _bytes()
    state, kv, logits = b.stream_bytes(cfg)
    assert state == 36 * 64 * 64 * 128 * 4 + 36 * 3 * 4352 * 4
    assert kv == 4 * 2 * 8 * 64 * 2 and logits == 100_352 * 4
    at_3000 = b.tick_bytes(cfg, [3000])
    assert at_3000 == 2 * 3_191_396_096 + 2 * state + kv * 3001 + logits
    assert round(at_3000 / 1e9, 2) == 6.56
    assert round(at_3000 / 8.19e11 * 1e3, 1) == 8.0
    assert b.tick_bytes(cfg, []) == 0.0


def test_token_frames_are_int32_ids_from_the_seed():
    cell = spec.load_cell(CELL)
    t = cell.traffic
    a = traffic.build(t, cell.config, 2**31 + 5, 1.0, t["hop_ms"] * 1e-3)
    b = traffic.build(t, cell.config, 2**31 + 5, 1.0, t["hop_ms"] * 1e-3)
    (f,) = a.frames
    assert f.dtype == np.int32 and f.shape == (250 * 2, 1)
    assert 0 <= f.min() and f.max() < cell.config["vocab_size"]
    np.testing.assert_array_equal(f, b.frames[0])


def test_reference_counts_the_columns_that_changed(shrink):
    cell = shrink(spec.load_cell(CELL))
    cfg, fam = cell.config, cell.family()
    w = fam.weights(cfg, 1)
    toks = np.array([[3], [3], [9], [4]], np.int32)
    ticks = [np.array([0, 1, -1, 2])]
    outs, union, counts = cell.reference().replay(
        w, [toks], **fam.reference_kwargs(cfg), serve_ticks=ticks,
        n_ticks=3)
    assert outs[0].shape == (4, cfg["vocab_size"])
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    inner = {"mamba": cfg["mamba_n_heads"] * cfg["mamba_d_head"],
             "attention": d}
    assert [u.shape for u in union] == [
        (3, 2 * d + inner[k] + f) for k in cfg["layer_types"]]
    for u, c in zip(union, counts):
        np.testing.assert_array_equal(u.sum(axis=1), c)
        assert u[0].all()             # the first token moves every stream
