"""The one traffic generator: reads a mix's parameters, returns its schedule.

A mix file (``bench/traffic/<mix>.json``) names the entry point that serves
it, the loop (``paced`` on a wall-clock tick grid, or ``closed``), the
arrivals and the frames. Everything here is drawn from ``--seed``:

* ``arrivals.kind = "one_stream"``: one stream that lasts the whole run.
* ``arrivals.kind = "poisson"``: open-loop arrivals at ``rate_per_s`` with
  lognormal lengths. Every seed gets the same multiset of inter-arrival
  gaps and of lengths (the midpoint quantiles of the exponential and of the
  clipped lognormal), so seeds never change the amount of work; their
  order is drawn from the seed. With ``stationary_start`` the run starts
  from
  the population a steady system holds at a random instant: about
  ``rate * mean length`` streams, with residual lengths from the
  length-biased residual law, all due at tick 0.
* ``frames.kind = "speech"``: filter-bank-like frames after the
  TIDIGITS-like generator of the repository (``digit_batch``): utterances
  of 1-7 digits, each a smooth two-formant trajectory of 8-12 frames with
  2-frame gaps, separated by silences, over a per-stream room tone and
  white noise.
* ``frames.kind = "sensor"``: gas-sensor-like frames after ``gas_batch``: a
  slow Ornstein-Uhlenbeck concentration driving 14 power-law sensors with
  a random-walk baseline drift and noise.
* any other ``frames.kind``: the frame kind ``bench/traffic/kinds/<kind>.py``
  (``frames(rng, n, config, params)``), in the shape of the
  configuration's family's ``frame_spec`` and in the kind's own dtype.

The two built-in kinds are float32 ``[n, width]`` at the width of the
family's ``frame_spec``; a ``room_tone`` in their parameters adds a
per-stream constant to each stream's frames.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np
from scipy.signal import lfilter

from harness import spec


@dataclass
class Schedule:
    """``arrival_tick[s]`` is the tick on which frame 0 of stream ``s`` is
    due (frame ``j`` is due on tick ``arrival_tick[s] + j``); streams are
    in arrival order."""

    arrival_tick: np.ndarray
    frames: list

    @property
    def lengths(self) -> np.ndarray:
        return np.array([len(f) for f in self.frames], np.int64)


def speech_frames(rng: np.random.Generator, n: int, i_dim: int,
                  p: dict) -> np.ndarray:
    """``[n, i_dim]`` continuous speech-like frames (no room tone)."""
    lo_d, hi_d = p["digits"]
    lo_f, hi_f = p["digit_frames"]
    lo_s, hi_s = p["silence_frames"]
    gap = int(p["digit_gap"])
    digit, rel = [], []
    total = 0
    while total < n:
        for _ in range(int(rng.integers(lo_d, hi_d + 1))):
            d = int(rng.integers(0, 11))
            dur = int(rng.integers(lo_f, hi_f + 1))
            digit.append(np.full(dur, d))
            rel.append(np.arange(dur) / dur)
            digit.append(np.full(gap, -1))
            rel.append(np.zeros(gap))
            total += dur + gap
        sil = int(rng.integers(lo_s, hi_s + 1))
        digit.append(np.full(sil, -1))
        rel.append(np.zeros(sil))
        total += sil
    d = np.concatenate(digit)[:n]
    t = np.concatenate(rel)[:n].astype(np.float32)
    inside = (d >= 0)[:, None]
    d = np.maximum(d, 0).astype(np.float32)
    mel = np.arange(i_dim, dtype=np.float32)[None]
    c1 = (4.0 + 2.5 * (d % 4) + 6.0 * t)[:, None]
    c2 = (18.0 + 1.7 * (d % 7) - 4.0 * t
          + 3.0 * np.sin(2 * np.pi * t * (1 + d % 3)))[:, None]
    w1 = (1.5 + 0.3 * (d % 2))[:, None]
    tpl = (2.0 * np.exp(-0.5 * np.square((mel - c1) / w1))
           + 1.5 * np.exp(-0.5 * np.square((mel - c2) / 2.0)))
    x = np.where(inside, tpl, 0.0)
    x += p["noise"] * rng.standard_normal((n, i_dim))
    return x.astype(np.float32)


def sensor_frames(rng: np.random.Generator, n: int, i_dim: int,
                  p: dict) -> np.ndarray:
    """``[n, i_dim]`` gas-sensor-like frames of one stream."""
    k, mean = p["ou_rate"], p["ou_mean"]
    c0 = mean + p["c0_std"] * rng.standard_normal()
    u = k * mean + p["ou_noise"] * rng.standard_normal(n)
    conc = np.abs(lfilter([1.0], [1.0, -(1.0 - k)], u,
                          zi=[(1.0 - k) * c0])[0])
    a = 0.5 + rng.uniform(size=i_dim)
    pw = 0.4 + 0.5 * rng.uniform(size=i_dim)
    drift = np.cumsum(p["drift"] * rng.standard_normal((n, i_dim)), axis=0)
    x = a * np.power(conc[:, None] + 1e-3, pw) + drift
    x += p["noise"] * rng.standard_normal((n, i_dim))
    return x.astype(np.float32)


FRAMES = {"speech": speech_frames, "sensor": sensor_frames}


def _frames(rng, lengths, config, fp) -> list:
    """Cut one long signal into streams; with a built-in kind each stream
    has its own tone."""
    total = int(np.sum(lengths))
    if fp["kind"] not in FRAMES:
        x = spec.load_kind(fp["kind"]).frames(rng, total, config, fp)
        return [np.ascontiguousarray(f)
                for f in np.split(x, np.cumsum(lengths)[:-1])]
    (i_dim,), _ = spec.load_family(config).frame_spec(config)
    x = FRAMES[fp["kind"]](rng, total, i_dim, fp)
    out, at = [], 0
    for n in lengths:
        f = x[at:at + n]
        if fp.get("room_tone"):
            f = f + fp["room_tone"] * rng.standard_normal(i_dim).astype(
                np.float32)
        out.append(np.ascontiguousarray(f, np.float32))
        at += n
    return out


def length_set(spec: dict, n: int) -> np.ndarray:
    """The ``n`` midpoint quantiles of the clipped lognormal law."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)


def residual_set(lengths: np.ndarray, n: int) -> np.ndarray:
    """``n`` midpoint quantiles of the residual length of a stream in
    service at a random instant, when lengths follow ``lengths``: it picks a
    stream with odds in proportion to its length and a uniform point of
    it (the remaining frames, 1..L)."""
    if n == 0:
        return np.zeros(0, np.int64)
    top = int(lengths.max())
    ge = np.array([(lengths >= r).sum() for r in range(1, top + 1)],
                  np.float64)
    cdf = np.cumsum(ge) / ge.sum()
    u = (np.arange(n) + 0.5) / n
    return (np.searchsorted(cdf, u) + 1).astype(np.int64)


def build(traffic: dict, config: dict, seed: int, seconds: float,
          hop_s: float) -> Schedule:
    """The schedule of a run of ``seconds`` after ``pre_window_s``, with
    frames for the configuration ``config``."""
    rng = np.random.default_rng(seed)
    arr, fp = traffic["arrivals"], traffic["frames"]
    pre = traffic["pre_window_s"]
    if arr["kind"] == "one_stream":
        if traffic["loop"] == "closed":
            n = int(traffic["max_frames_per_s"] * (pre + seconds))
        else:
            n = int(round((pre + seconds) / hop_s))
        return Schedule(np.zeros(1, np.int64),
                        _frames(rng, np.array([n]), config, fp))
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    rate, horizon = arr["rate_per_s"], pre + seconds
    n = max(1, int(round(rate * horizon)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= horizon / gaps.sum()
    t = np.cumsum(rng.permutation(gaps)) - gaps.min()
    ticks = np.floor(t / hop_s).astype(np.int64)
    lengths = rng.permutation(length_set(arr["length"], n))
    if arr.get("stationary_start"):
        mean_len = float(length_set(arr["length"], 4096).mean())
        n0 = min(int(traffic["slots"]),
                 int(round(rate * mean_len * hop_s)))
        res = rng.permutation(residual_set(
            length_set(arr["length"], 4096), n0))
        ticks = np.concatenate([np.zeros(n0, np.int64), ticks])
        lengths = np.concatenate([res, lengths])
    return Schedule(ticks, _frames(rng, lengths, config, fp))
