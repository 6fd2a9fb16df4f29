"""Open-loop load generation for the distributed serving fabric.

:func:`poisson_arrivals` draws a seeded open-loop Poisson arrival
schedule (exponential inter-arrival gaps, floored to router ticks) of
short-lived streams; :func:`run_fabric_load` replays it against a
:class:`~repro.serve.router.StreamRouter` — arrivals land at their
scheduled tick regardless of system state (open loop: backpressure shows
up as rejections, not as a slowed generator), with an optional elastic
scale-down fired mid-load at a FIXED tick.

Everything the generator decides is tick-counted and seeded, so a run's
entire event history (placements, rejections, sheds, the rebalance, every
latency-in-ticks) reproduces exactly on any machine — that is what lets
``benchmarks/loadgen_fabric.py`` gate the counts as hard integers in CI.
Wall-clock only ever appears as a measurement (tick walls, throughput).
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

__all__ = ["poisson_arrivals", "run_fabric_load", "fabric_parity",
           "LoadRunSummary"]


def poisson_arrivals(n_streams: int, rate_per_tick: float, *,
                     min_len: int, max_len: int, input_size: int,
                     seed: int = 0) -> list[tuple[int, np.ndarray]]:
    """A seeded open-loop Poisson arrival schedule.

    Returns ``[(arrival_tick, frames [T, I]), ...]`` sorted by tick, with
    stream lengths uniform on ``[min_len, max_len]`` and standard-normal
    frames — short-lived streams, the serving fabric's target traffic.
    """
    if n_streams < 1 or rate_per_tick <= 0 or min_len < 1 \
            or max_len < min_len:
        raise ValueError(
            f"bad load shape: n_streams={n_streams}, "
            f"rate_per_tick={rate_per_tick}, len=[{min_len}, {max_len}]")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_per_tick, size=n_streams)
    ticks = np.floor(np.cumsum(gaps)).astype(int)
    lens = rng.integers(min_len, max_len + 1, size=n_streams)
    return [(int(t), rng.standard_normal((int(ln), input_size))
             .astype(np.float32))
            for t, ln in zip(ticks, lens)]


@dataclass
class LoadRunSummary:
    """What one load run produced (results keyed by arrival index)."""

    results: dict                 # arrival index -> RouterResult
    peak_concurrent: int          # max(in service + queued) over the run
    peak_concurrent_full: int     # same, while the FULL fleet was alive
    peak_active: int              # max slots simultaneously in service
    ticks: int
    scale_info: dict | None      # fleet.remove_shard info (if fired)
    tick_wall_s: list             # wall seconds of each router.tick()


def run_fabric_load(router, arrivals, *, scale_down_at: int | None = None,
                    scale_down_shard: int = 0, ckpt_dir: str | None = None,
                    max_ticks: int = 100000, on_tick=None
                    ) -> LoadRunSummary:
    """Replay an arrival schedule through a router until drained.

    ``scale_down_at`` fires ``router.scale_down(scale_down_shard)`` at
    that exact tick (before that tick's arrivals) — the simulated device
    loss. ``on_tick(router, tick)`` is an observation hook.
    """
    results: dict[int, object] = {}
    uid2arr: dict[int, int] = {}
    scale_info = None
    peak = peak_full = peak_active = 0
    walls: list[float] = []
    i = 0
    while True:
        tick = router.tick_no
        if scale_down_at is not None and tick == scale_down_at \
                and scale_info is None:
            scale_info = router.scale_down(scale_down_shard,
                                           ckpt_dir=ckpt_dir)
        while i < len(arrivals) and arrivals[i][0] <= tick:
            uid, admitted = router.submit(arrivals[i][1])
            uid2arr[uid] = i
            if not admitted:
                results[i] = router.results[-1]
            i += 1
        t0 = time.perf_counter()
        done = router.tick()
        walls.append(time.perf_counter() - t0)
        for res in done:
            results[uid2arr[res.uid]] = res
        active = router.active_slots()
        concurrent = active + router.queue_depth()
        peak = max(peak, concurrent)
        peak_active = max(peak_active, active)
        if scale_info is None:
            peak_full = max(peak_full, concurrent)
        if on_tick is not None:
            on_tick(router, tick)
        if i >= len(arrivals) and router.idle():
            break
        if router.tick_no >= max_ticks:
            raise RuntimeError(
                f"load run exceeded max_ticks={max_ticks}: "
                f"{router.queue_depth()} queued + {router.in_flight()} "
                "in flight")
    assert len(results) == len(arrivals), \
        (len(results), len(arrivals))  # every arrival reached a terminal
    return LoadRunSummary(results=results, peak_concurrent=peak,
                          peak_concurrent_full=peak_full,
                          peak_active=peak_active, ticks=router.tick_no,
                          scale_info=scale_info, tick_wall_s=walls)


def fabric_parity(arrivals, results, fleet) -> int:
    """Bitwise-compare every completed stream of a fabric load run
    (``results`` from :func:`run_fabric_load`) against a clean standalone
    engine at the fleet's per-shard tile width; returns how many matched
    and raises ``AssertionError`` on the first that does not.

    Up to one tile width of streams shares each reference run: at a fixed
    tile width, companion streams are bitwise-neutral, so one
    ``step_many`` checks B streams."""
    b = fleet.streams_per_shard
    i_dim = fleet.dims.input_size
    ref = fleet.reference_engine()
    completed = [(i, r) for i, r in sorted(results.items())
                 if r.status == "ok"]
    parity_ok = 0
    for base in range(0, len(completed), b):
        group = completed[base:base + b]
        t_max = max(len(arrivals[i][1]) for i, _ in group)
        xs = np.zeros((t_max, b, i_dim), np.float32)
        for j, (i, _) in enumerate(group):
            frames = arrivals[i][1]
            xs[:len(frames), j] = frames
            # pad with the last frame: zero delta, and causality means the
            # real prefix's outputs are unaffected
            xs[len(frames):, j] = frames[-1]
        ref.reset()
        want = np.asarray(ref.step_many(xs))
        for j, (i, r) in enumerate(group):
            got = np.stack([np.asarray(o) for o in r.outputs])
            assert want[:len(got), j].tobytes() == got.tobytes(), \
                f"fabric parity: arrival {i} (shard {r.shard}, " \
                f"replayed={r.replayed}, {len(got)} frames) diverged " \
                "from its clean same-width reference"
            parity_ok += 1
    return parity_ok
