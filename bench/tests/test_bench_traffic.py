"""The generator's arithmetic: arrivals on the tick grid, lognormal
lengths, the stationary start, and frames drawn from the seed."""
import numpy as np
import pytest

from harness import spec, traffic

HOP = 0.01
GRU768 = spec.load_cell("gru768-q8-b1-speech").config
GAS256 = spec.load_cell("gas256-q8-b1-closed").config


def _mix(name="s128-speech"):
    return spec.load_cell("gru768-q8-" + name).traffic


def test_poisson_arrivals_sit_on_the_grid_at_the_rate():
    t = _mix()
    s = traffic.build(t, GRU768, seed=3, seconds=30.0, hop_s=HOP)
    rate = t["arrivals"]["rate_per_s"]
    lengths = traffic.length_set(t["arrivals"]["length"], 4096)
    n0 = min(t["slots"], round(rate * lengths.mean() * HOP))
    later = s.arrival_tick[n0:]
    assert np.all(np.diff(s.arrival_tick) >= 0)
    assert later.dtype.kind == "i" and later.max() <= 31.0 / HOP
    assert len(later) == round(rate * 31.0)
    gaps = np.diff(later) * HOP
    assert abs(gaps.mean() - 1.0 / rate) < 0.1 / rate
    assert 0.9 < np.std(gaps) * rate < 1.1      # exponential: cv of 1


def test_seeds_reorder_the_same_work():
    t = _mix()
    a = traffic.build(t, GRU768, seed=1, seconds=10.0, hop_s=HOP)
    b = traffic.build(t, GRU768, seed=2**31 + 99, seconds=10.0, hop_s=HOP)
    assert sorted(a.lengths) == sorted(b.lengths)
    assert not np.array_equal(a.lengths, b.lengths)
    assert not np.array_equal(a.arrival_tick, b.arrival_tick)
    assert not np.array_equal(a.frames[0][:1], b.frames[0][:1])
    c = traffic.build(t, GRU768, seed=1, seconds=10.0, hop_s=HOP)
    assert all(np.array_equal(x, y) for x, y in zip(a.frames, c.frames))


def test_lognormal_lengths():
    spec_ = {"median": 100, "sigma": 1.2, "min": 20, "max": 3000}
    n = traffic.length_set(spec_, 4001)
    assert n.min() == 20 and n.max() == 3000
    assert np.median(n) == 100
    z = np.log(n[(n > 20) & (n < 3000)] / 100.0)
    inner = np.mean((n > 20) & (n < 3000))
    assert inner > 0.8
    assert abs(np.std(np.log(n / 100.0)) - 1.2) < 0.15
    assert np.all(np.abs(z) < 1.2 * 3.5)


def test_stationary_start_holds_rate_times_mean_length():
    t = _mix()
    s = traffic.build(t, GRU768, seed=5, seconds=10.0, hop_s=HOP)
    lengths = traffic.length_set(t["arrivals"]["length"], 4096)
    want = min(t["slots"], round(t["arrivals"]["rate_per_s"]
                                 * lengths.mean() * HOP))
    n0 = int(np.sum(s.arrival_tick == 0))
    assert want <= n0 <= want + 2           # and arrivals due on tick 0
    # residuals are length-biased: longer on average than a fresh stream
    assert s.lengths[:n0].mean() > 0.6 * lengths.mean()
    res = traffic.residual_set(np.array([10, 10]), 1000)
    assert res.min() == 1 and res.max() == 10
    assert abs(res.mean() - 5.5) < 0.1


@pytest.mark.parametrize("kind,i_dim", [("speech", 40), ("sensor", 14)])
def test_frames_are_seeded_and_smooth(kind, i_dim):
    cell = {"speech": "gru768-q8-b1-speech",
            "sensor": "gas256-q8-b1-closed"}[kind]
    t = spec.load_cell(cell).traffic
    p = t["frames"]
    x = traffic.FRAMES[kind](np.random.default_rng(0), 3000, i_dim, p)
    y = traffic.FRAMES[kind](np.random.default_rng(0), 3000, i_dim, p)
    assert x.shape == (3000, i_dim) and x.dtype == np.float32
    assert np.array_equal(x, y) and np.isfinite(x).all()
    # most frame-to-frame changes stay under the 0.25 threshold, so Eq. 2
    # leaves most columns silent
    quiet = np.mean(np.abs(np.diff(x, axis=0)) < 0.25)
    assert 0.6 < quiet < 0.999


def test_one_stream_lasts_the_run():
    t = spec.load_cell("gru768-q8-b1-speech").traffic
    s = traffic.build(t, GRU768, seed=0, seconds=20.0, hop_s=HOP)
    assert len(s.frames) == 1 and len(s.frames[0]) == 2100
    c = spec.load_cell("gas256-q8-b1-closed").traffic
    s = traffic.build(c, GAS256, seed=0, seconds=2.0, hop_s=HOP)
    assert len(s.frames[0]) == c["max_frames_per_s"] * 3
