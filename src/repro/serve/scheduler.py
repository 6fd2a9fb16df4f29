"""Request schedulers over the serving engines' fixed slot counts.

``ContinuousBatcher`` — continuous batching over ``LmEngine`` decode slots:
requests queue up; whenever slots free up, the scheduler pads the newest
wave of prompts to a common length, prefills them into the free slots
(slotwise-merging the caches so in-flight slots are untouched), and keeps
stepping all active slots each tick. Finished slots (EOS or budget) are
harvested and recycled. Per-slot ragged positions are native to the ring
KVCache (see models.attention.KVCache).

``GruStreamBatcher`` (alias ``DeltaStreamBatcher``) — the same
admission/harvest loop over ``DeltaStreamEngine`` stream sessions (the
EdgeDRNN heavy-traffic mode), for any compiled cell family (GRU or LSTM
programs alike): queued streaming requests are admitted into free
``n_streams`` slots via ``open_stream()`` (per-slot masked reset), every
tick feeds one frame per active stream through ONE batched engine step
(one weight fetch serves all streams), and exhausted streams are
harvested via ``close_stream()`` — which also returns that stream's own
firing/latency accounting. Millions of short-lived streams recycle
through a fixed set of slots without ever rebuilding the engine.
"""
from __future__ import annotations

import collections
import itertools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.engine import DeltaStreamEngine, GruStreamEngine, LmEngine

# Host spans of the batcher tick, written into the profiler's trace (see
# the engine's); the engine step between them writes its own.
_span = jax.profiler.TraceAnnotation


@dataclass
class Request:
    uid: int
    prompt: list
    max_new_tokens: int = 16
    eos_id: int | None = None
    output: list = field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Slot-based scheduler. Admission prefills the incoming wave through
    the batch prefill path (fixed shapes, jit-friendly) and then restores
    the live slots' cache rows — prefill writes every slot's cache, so
    without the slotwise merge an admission into a partially occupied
    batch would corrupt the in-flight requests."""

    def __init__(self, engine: LmEngine, pad_id: int = 0):
        self.engine = engine
        self.pad_id = pad_id
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Request | None] = [None] * engine.batch
        self._uid = itertools.count()
        self._last_tokens = np.zeros((engine.batch, 1), np.int32)

    def submit(self, prompt: list, max_new_tokens: int = 16,
               eos_id: int | None = None) -> int:
        uid = next(self._uid)
        self.queue.append(Request(uid, list(prompt), max_new_tokens, eos_id))
        return uid

    def _admit(self):
        free = [i for i, s in enumerate(self.slots) if s is None]
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not free or not self.queue:
            return
        wave = []
        for slot in free:
            if not self.queue:
                break
            req = self.queue.popleft()
            self.slots[slot] = req
            wave.append((slot, req))
        if not wave:
            return
        # Prefill the wave through the whole-batch prefill path (fixed
        # shapes). Live slots get a pad-only "prompt" whose cache writes
        # are garbage — snapshot their cache rows first and merge them
        # back after, so admission never perturbs in-flight requests.
        old_caches = self.engine.caches if live else None
        max_len = max(len(r.prompt) for _, r in wave)
        tokens = np.full((self.engine.batch, max_len), self.pad_id, np.int32)
        for slot, req in wave:
            tokens[slot, -len(req.prompt):] = req.prompt
        logits = self.engine.prefill(jnp.asarray(tokens))
        if live:
            keep = np.zeros((self.engine.batch,), bool)
            keep[live] = True
            self.engine.caches = _merge_caches_slotwise(
                old_caches, self.engine.caches, jnp.asarray(keep))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for slot, req in wave:
            req.output.append(int(nxt[slot]))
            self._last_tokens[slot, 0] = int(nxt[slot])

    def step(self) -> list[Request]:
        """One scheduler tick: admit, decode, harvest. Returns finished."""
        self._admit()
        if not any(self.slots):
            return []
        logits = self.engine.decode_step(jnp.asarray(self._last_tokens))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        finished = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i])
            req.output.append(tok)
            self._last_tokens[i, 0] = tok
            if ((req.eos_id is not None and tok == req.eos_id)
                    or len(req.output) >= req.max_new_tokens):
                req.done = True
                finished.append(req)
                self.slots[i] = None
        return finished

    def run_until_drained(self, max_ticks: int = 1000,
                          strict: bool = True) -> list[Request]:
        """Tick until queue and slots are empty; returns finished requests.

        With ``strict`` (the default) an exhausted tick budget raises
        ``RuntimeError`` instead of silently returning a partial result —
        the old behavior dropped still-queued/in-flight requests on the
        floor with no signal whatsoever. ``strict=False`` restores the
        partial return for callers that genuinely want best-effort.
        """
        done = []
        for _ in range(max_ticks):
            done += self.step()
            if not self.queue and not any(self.slots):
                return done
        if strict and (self.queue or any(self.slots)):
            raise RuntimeError(
                f"run_until_drained truncated at max_ticks={max_ticks}: "
                f"{len(self.queue)} queued + "
                f"{sum(s is not None for s in self.slots)} in-flight "
                f"requests undrained ({len(done)} finished); raise "
                "max_ticks or pass strict=False for a partial result")
        return done


def _merge_caches_slotwise(old, new, keep):
    """Take ``old``'s rows for slots where ``keep`` is True, else ``new``.

    Cache leaves are stacked ``[n_layers, B, ...]`` (see
    ``models.blocks.init_caches``), so the slot (batch) axis is axis 1.
    """
    def sel(o, n):
        m = keep.reshape((1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(m, o, n)

    return jax.tree_util.tree_map(sel, old, new)


@dataclass
class StreamRequest:
    """A queued streaming inference request: a finite frame sequence."""

    uid: int
    frames: np.ndarray                       # [T, I]
    outputs: list = field(default_factory=list)
    stats: dict | None = None                # per-stream engine accounting
    done: bool = False
    cursor: int = 0
    # admission-time taint: the sequence contained non-finite frames and
    # the submitter chose on_nonfinite="quarantine" — the resilience
    # supervisor watches these streams with a tighter leash
    suspect: bool = False


class GruStreamBatcher:
    """Admission/harvest scheduler over ``DeltaStreamEngine`` sessions
    (any cell family — the engine's program carries the cell).

    Mirrors :class:`ContinuousBatcher`: ``submit()`` queues a frame
    sequence, each :meth:`step` tick admits queued requests into free
    stream slots (``open_stream`` masked-resets exactly that slot), feeds
    one frame per active stream through ONE batched engine step — the
    heavy-traffic property: weights are fetched once per tick for every
    active stream — and harvests exhausted streams (``close_stream``
    returns their per-stream gamma/latency/byte accounting into
    ``req.stats``). Idle slots are fed their last admitted frame (zero
    delta — the silent regime, virtually free under Eq. 7).
    """

    def __init__(self, engine: DeltaStreamEngine):
        self.engine = engine
        self.queue: collections.deque[StreamRequest] = collections.deque()
        self.slots: list[StreamRequest | None] = [None] * engine.n_streams
        self._uid = itertools.count()
        self._idle_x = np.zeros((engine.n_streams, engine.dims.input_size),
                                np.float32)
        # Observability counters (exact event counts, monotone): the
        # router/load-generator/overload-watermark read load through these
        # and the depth/slot hooks below instead of poking private state.
        self.counters = {"submitted": 0, "admitted": 0, "harvested": 0,
                         "ticks": 0}

    # -- observability hooks ----------------------------------------------

    def queue_depth(self) -> int:
        """Requests admitted to the batcher but not yet in a slot."""
        return len(self.queue)

    def active_slots(self) -> int:
        """Stream slots currently carrying an in-flight request."""
        return sum(1 for r in self.slots if r is not None)

    def free_slots(self) -> int:
        """Slots an external placer may count on THIS tick: engine slots
        not in flight, minus queued requests that will claim them first."""
        return max(0, self.engine.n_streams - self.active_slots()
                   - len(self.queue))

    def submit(self, frames, on_nonfinite: str = "reject") -> int:
        """Queue a ``[T, I]`` (T >= 1) frame sequence; returns its uid.

        ``on_nonfinite`` decides what to do with sequences containing
        NaN/Inf frames (a poisoned sensor feed):

        * ``"reject"`` (default) — raise ``ValueError`` at admission. The
          old behavior fed the poison straight into the engine, where
          (pre-guard) one bad frame permanently corrupted the slot's
          recurrent state AND every companion stream's accounting.
        * ``"quarantine"`` — admit but tag ``req.suspect``; the engine's
          frame guard masks the bad frames and the resilience supervisor
          (``serve.resilience``) rolls back / quarantines on its policy.
        * ``"allow"`` — admit untagged (the device-side guard still
          protects the state; only the supervisor's tighter watch is
          waived).
        """
        if on_nonfinite not in ("reject", "quarantine", "allow"):
            raise ValueError(f"on_nonfinite={on_nonfinite!r} not in "
                             "('reject', 'quarantine', 'allow')")
        frames = np.asarray(frames, np.float32)
        if (frames.ndim != 2 or frames.shape[0] == 0
                or frames.shape[-1] != self.engine.dims.input_size):
            raise ValueError(
                f"frames must be [T >= 1, {self.engine.dims.input_size}], "
                f"got {frames.shape}")
        suspect = bool(not np.isfinite(frames).all())
        if suspect and on_nonfinite == "reject":
            raise ValueError(
                "frame sequence contains non-finite values; sanitize "
                "(serve.faults.sanitize_frames), or submit with "
                "on_nonfinite='quarantine'/'allow'")
        uid = next(self._uid)
        self.queue.append(StreamRequest(
            uid, frames, suspect=suspect and on_nonfinite == "quarantine"))
        self.counters["submitted"] += 1
        return uid

    def _admit(self):
        while self.queue and self.engine.free_streams:
            req = self.queue.popleft()
            sid = self.engine.open_stream()
            self.slots[sid] = req
            self.counters["admitted"] += 1

    def step(self) -> list[StreamRequest]:
        """One tick: admit, one batched engine step, harvest. Returns
        finished requests (with ``stats`` filled).

        The tick itself is zero-sync: per-frame outputs are kept as device
        slices and only materialized to the host when a stream finishes
        (harvest decisions are cursor-based, never value-based), so the
        engine's device-side hot loop is never forced to drain per tick.
        """
        if self.queue:
            with _span("batcher.admit"):
                self._admit()
        self.counters["ticks"] += 1
        active = [(sid, req) for sid, req in enumerate(self.slots)
                  if req is not None]
        if not active:
            return []
        with _span("batcher.stage"):
            x = self._idle_x
            for sid, req in active:
                x[sid] = req.frames[req.cursor]
            # Hand the engine a SNAPSHOT (numpy copy, synchronous), never
            # the persistent per-tick buffer: the engine's step is
            # dispatched asynchronously and jax's host->device ingestion of
            # a numpy buffer is itself deferred, so an aliased buffer
            # mutated by the NEXT tick's frame writes nondeterministically
            # bled future frames into the in-flight step under load (the
            # batcher parity tests flaked with exactly that cross-tick
            # corruption).
            x = x.copy()
        y = self.engine.step(x)
        finished = []
        with _span("batcher.slice"):
            out = jnp.reshape(y, (self.engine.n_streams, -1))
            for sid, req in active:
                req.outputs.append(out[sid])     # device slice, no sync
                req.cursor += 1
                if req.cursor >= len(req.frames):
                    finished.append((sid, req))
        if finished:
            with _span("batcher.harvest"):
                # one sync per tick, shared by every stream that finished
                host_carry = self.engine.host_carry()
                for sid, req in finished:
                    req.stats = self.engine.close_stream(
                        sid, host_carry=host_carry)
                    req.outputs = list(np.asarray(jnp.stack(req.outputs)))
                    req.done = True
                    self.slots[sid] = None
        self.counters["harvested"] += len(finished)
        return [req for _, req in finished]

    def run_until_drained(self, max_ticks: int = 100000,
                          strict: bool = True):
        """Tick until queue and slots are empty; returns finished requests.

        ``strict`` (default): raise ``RuntimeError`` when the tick budget
        runs out with work still queued/in-flight — previously the
        truncation was silent and the lost requests simply vanished from
        the return. ``strict=False`` keeps the partial-result behavior.
        """
        done = []
        for _ in range(max_ticks):
            done += self.step()
            if not self.queue and not any(r is not None for r in self.slots):
                return done
        in_flight = sum(r is not None for r in self.slots)
        if strict and (self.queue or in_flight):
            raise RuntimeError(
                f"run_until_drained truncated at max_ticks={max_ticks}: "
                f"{len(self.queue)} queued + {in_flight} in-flight "
                f"requests undrained ({len(done)} finished); raise "
                "max_ticks or pass strict=False for a partial result")
        return done


# Cell-agnostic alias (the batcher has always been engine-shaped, and the
# engine now serves any compiled delta-RNN cell).
DeltaStreamBatcher = GruStreamBatcher
