"""Deterministic fault injection for the resilient serving tier.

Everything here is SEEDED — a :class:`FaultPlan` maps ``(seed, stream
index, tick)`` to faults with no ambient randomness, so a chaos soak run
twice produces bit-identical fault schedules and a test can assert EXACT
recovery counts (``tests/test_resilience.py::TestChaosSoak``).

Fault classes (each maps to a real edge-deployment failure the paper's
target environment — a sensor-fed PS/PL SoC — actually sees):

* **poisoned frames** — NaN/Inf components in the input stream (sensor
  glitch, DMA underrun). Injected by :meth:`FaultPlan.poison_stream`;
  neutralized device-side by the engine's frame guard.
* **slot-state corruption** — non-finite values written directly into one
  stream's recurrent state (:func:`corrupt_slot_state` — the software
  stand-in for an SEU/bit-flip in BRAM). Detected by the engine's
  ``bad_state`` counter, repaired by snapshot rollback.
* **stalled ticks** — the serve loop blocks (CPU contention; the paper's
  Table IV PetaLinux tail). Surfaced by heartbeat age / straggler flags.
* **simulated crash** — :class:`SimulatedCrash` raised at a planned tick;
  ``serve.resilience.serve_resumable`` restarts from the published
  checkpoint.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


class SimulatedCrash(RuntimeError):
    """An injected process death (preemption / power loss / OOM-kill)."""


def sanitize_frames(frames: np.ndarray) -> np.ndarray:
    """Replace non-finite FRAMES (whole rows) with the previous finite
    frame — exactly the engine guard's zero-delta semantics, applied
    host-side. A bad frame 0 falls back to zeros (the delta-memory init
    convention, still the silent regime). Returns a new array.
    """
    frames = np.array(frames, np.float32)
    good = np.isfinite(frames).all(axis=-1)
    last = np.zeros((frames.shape[-1],), np.float32)
    for t in range(frames.shape[0]):
        if good[t]:
            last = frames[t]
        else:
            frames[t] = last
    return frames


def corrupt_slot_state(engine, sid: int):
    """Write NaN into every float leaf of ONE stream slot's stack state.

    The injection half of the ``bad_state`` detection path: the engine's
    jitted step flags the slot on its next step, and the resilience
    supervisor rolls it back to the last snapshot. Companion slots are
    untouched (masked write, same mechanism as the session reset).
    """
    n = engine.n_streams
    if not (0 <= sid < n):
        raise ValueError(f"stream {sid} out of range")
    mask = jnp.asarray(np.arange(n) == sid)

    def nanify(leaf):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        m = mask.reshape((n,) + (1,) * (leaf.ndim - 1))
        return jnp.where(m, jnp.nan, leaf)

    stack = jax.tree_util.tree_map(nanify, engine.state.stack)
    engine.state = dataclasses.replace(engine.state, stack=stack)


@dataclass(frozen=True)
class FaultPlan:
    """A seeded, declarative chaos schedule.

    ``poison_streams`` / ``inf_streams``: stream (arrival) indices whose
    frame sequences get ``poison_frames`` NaN / Inf frames each, at
    positions drawn from ``default_rng(seed * 1000 + index)`` — fully
    reproducible per stream, independent of arrival order.

    ``corrupt_slot_at``: ``((tick, sid), ...)`` direct state-corruption
    events. ``stall_ticks``: ticks on which the harness sleeps
    ``stall_s``. ``crash_at_tick``: raise :class:`SimulatedCrash` ONCE at
    that tick (one-shot — the restarted loop passes it unharmed, like a
    real transient fault).
    """

    seed: int = 0
    poison_streams: tuple = ()
    inf_streams: tuple = ()
    poison_frames: int = 2
    corrupt_slot_at: tuple = ()
    stall_ticks: tuple = ()
    stall_s: float = 0.05
    crash_at_tick: int | None = None
    _crash_fired: list = field(default_factory=list, repr=False,
                               compare=False)

    def poison_stream(self, index: int, frames: np.ndarray) -> np.ndarray:
        """Return a poisoned copy of ``frames`` if stream ``index`` is in
        the plan, else ``frames`` unchanged."""
        kind = (np.nan if index in self.poison_streams
                else np.inf if index in self.inf_streams else None)
        if kind is None:
            return frames
        frames = np.array(frames, np.float32)
        rng = np.random.default_rng(self.seed * 1000 + index)
        t_idx = rng.choice(frames.shape[0],
                           size=min(self.poison_frames, frames.shape[0]),
                           replace=False)
        c_idx = rng.integers(0, frames.shape[1], size=len(t_idx))
        frames[t_idx, c_idx] = kind
        return frames

    def corruptions(self, tick: int) -> list:
        """Slot ids to corrupt at ``tick``."""
        return [sid for t, sid in self.corrupt_slot_at if t == tick]

    def is_stall(self, tick: int) -> bool:
        return tick in self.stall_ticks

    def maybe_crash(self, tick: int):
        """Raise :class:`SimulatedCrash` at the planned tick, once."""
        if (self.crash_at_tick is not None and tick == self.crash_at_tick
                and not self._crash_fired):
            self._crash_fired.append(tick)
            raise SimulatedCrash(f"injected crash at tick {tick}")
