"""The counts behind the kernel roofline, from the reference replay: at
theta 0 every real column fires, so one tick of 2L-768H needs the whole
unpadded stack volume."""
import importlib.util
import os

import numpy as np

from harness import spec

BENCH = spec.BENCH


def _work():
    path = os.path.join(BENCH, "metrics", "_work.py")
    s = importlib.util.spec_from_file_location("bench_work", path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_theta_zero_fires_the_whole_unpadded_stack():
    cell = spec.load_cell("gru768-q8-b1-speech")
    cfg = cell.config
    ref = cell.reference()
    w = cell.family().weights(cfg, 0)
    rng = np.random.default_rng(0)
    streams = [rng.standard_normal((3, 40)).astype(np.float32)
               for _ in range(2)]
    ticks = [np.array([0, 1, 2]), np.array([0, 1, -1])]
    _, union, counts = ref.replay(w, streams, 0.0, 0.0, serve_ticks=ticks,
                                  n_ticks=3, chunk=4)
    weights, nbytes, ops = _work().needed(union, counts, 3, 768, 8)
    assert weights.shape == (3, 2)
    assert int(weights[0].sum()) == 5_400_576
    assert 3 * 768 * (40 + 768) + 3 * 768 * (768 + 768) == 5_400_576
    # the kernel pads the input to its 128-column block; that is not work
    assert 3 * 768 * (128 + 768) + 3 * 768 * (768 + 768) == 5_603_328
    assert np.array_equal(nbytes, weights)          # int8: a byte a weight
    # two streams served in tick 0 and 1, one in tick 2
    assert np.array_equal(ops.sum(axis=1), 2 * 5_400_576 * np.array([2, 2, 1]))
    assert int(_work().needed(union, counts, 3, 768, 4)[1][0].sum()) \
        == 5_400_576 // 2
    assert cell.family().dense_weights(cfg) == 5_400_576 + 768 * 12


def test_least_seconds_takes_the_larger_bound():
    union = [np.ones((1, 10), np.int32)]
    counts = [np.array([10])]
    w = _work()
    # 10 columns x 30 rows = 300 weights, 600 ops per stream-column set
    t = w.least_seconds(union, counts, 3, 10, 8, peak_ops=1e3,
                        peak_bytes=1e3)
    assert t == max(2 * 30 * 10 / 1e3, 300 / 1e3)
