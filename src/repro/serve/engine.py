"""Serving engines.

``LmEngine`` — batched prefill + decode for any registry arch (jitted steps,
ring caches with per-slot lengths for continuous batching).

``DeltaStreamEngine`` — the paper's deployment mode: streaming delta-RNN
inference with live temporal-sparsity accounting and the Eq. 7 latency
model, i.e. a software EdgeDRNN. The **primary entry point is a compiled
program** of ANY registered cell family — build one with
:func:`repro.core.program.compile_delta_program` (GRU or LSTM) or
:func:`repro.quant.export.quantize_delta_model` (the int8 export of
either family; :func:`repro.core.program.compile_deltagru` and
:func:`repro.quant.export.quantize_gru_model` are the GRU spellings) and
hand it to ``DeltaStreamEngine(program, task)`` — cell, backend, packed
layouts, and the delta-memory state convention all travel inside the
program, so they cannot be mismatched. The legacy
``DeltaStreamEngine(params_dict, task, backend=..., layouts=...)``
spelling still works as a thin shim that compiles a program internally
(the dict's ``"gru"`` / ``"lstm"`` key picks the cell), and
``GruStreamEngine`` remains as an alias of the class.

The engine supports the dual thresholds (including per-layer
:class:`~repro.core.thresholds.ThresholdPolicy` overrides, threaded into
the jitted step), the dynamic-threshold controller (paper Sec. VI future
work), every backend registered for the program's cell
(both cells: ``dense | fused | fused_q8 | fused_batch | fused_q8_batch``
— the ``fused_q8*`` paths stream int8 packed weights and run the paper's
fixed-point pipeline via the cell-agnostic :mod:`repro.kernels.delta_q8`
core), chunked ``step_many`` streaming, and a batched multi-stream mode:
with ``n_streams > 1`` the engine **auto-routes** a ``fused`` /
``fused_q8`` program onto its ``*_batch`` tile sibling
(:meth:`~repro.core.program.DeltaProgram.with_backend` — same packed
weights, bit-identical outputs) so ONE weight fetch per step serves the
whole stream tile, and the Eq. 7 accounting gains tile-level terms
priced on the **union** firing across streams. On top of the slots sits a
**session API** for heavy traffic:
:meth:`~DeltaStreamEngine.open_stream` claims a free slot and
masked-resets only that stream's state,
:meth:`~DeltaStreamEngine.close_stream` frees it and returns that
stream's own firing/latency/byte accounting —
``serve.scheduler.GruStreamBatcher`` drives millions of short-lived
streams through these slots. The Eq. 7 model carries a bytes-per-op term:
latency and weight-traffic estimates price the streamed weight width of
the program's backend and the cell's gate count (3 rows per fetched
column for GRU, 4 for LSTM).

The hot loop is zero-sync: firing statistics (per stream), the Eq. 7
latency estimate, and the dynamic-Θ controller all live *inside* the
jitted step as a device carry — nothing forces a host round-trip until
:attr:`stats` or :meth:`report` is read, and those materialize the carry
exactly once. (The seed called ``float(fx)``/``float(fh)`` and a host-side
``estimate_stack`` every timestep: three blocking transfers per frame,
which capped streaming throughput at Python-dispatch rate.) The state and
carry cross each compiled call packed, one flat buffer per dtype, since
the host pays per buffer passed: a batch-1 GRU/LSTM tick moves four (the
frame and the packed buffer in, the output and the packed buffer out;
``report()["step_buffers"]``). ``state`` and ``_carry`` read and assign
the unpacked pytrees, and :meth:`host_carry` reads the carry with one
transfer.

Because the streams are long-lived and the state is recurrent, the engine
also carries a **resilience layer** (all device-side, zero-sync like the
stats):

* a *frame guard* inside the jitted step — a frame containing any
  non-finite component is replaced by that stream's previous (guarded)
  frame, i.e. masked into the zero-delta silent regime, so one poisoned
  sensor reading can never permanently corrupt the hidden state; guarded
  frames are counted in a per-slot ``poison_steps`` carry, and a per-slot
  ``bad_state`` counter tracks steps whose *post-step stack state* went
  non-finite (direct state corruption — the guard makes this impossible
  from inputs alone);
* per-slot **snapshot/rollback** (:meth:`snapshot_streams` /
  :meth:`rollback_stream`) — the same masked-select mechanism as the
  session reset, against a device-resident shadow copy of the slot rows;
* whole-engine **checkpoint/restore** (:meth:`checkpoint` /
  :meth:`restore`) over :mod:`repro.ft.checkpoint`, carrying the exact
  accounting aggregates so a restarted server's :meth:`report` continues
  where the crashed one stopped.

``serve.resilience.ResilientStreamServer`` drives these into a
quarantine/shed/restart policy; ``serve.faults.FaultPlan`` is the
deterministic chaos harness that exercises them.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.perf_model import (EDGEDRNN, AcceleratorSpec,
                                   dram_traffic_bytes_per_timestep,
                                   estimate_stack, spec_for_backend,
                                   stack_latency_s)
from repro.core.program import (DeltaProgram, compile_delta_program,
                                infer_cell)
from repro.ft import checkpoint as ft_checkpoint
from repro.core.sparsity import cell_dims
from repro.core.thresholds import ThresholdPolicy, dynamic_threshold
from repro.models.gru_rnn import GruTaskConfig
from repro.models.lm import init_lm_caches, lm_decode, lm_prefill

Array = jax.Array
# Host spans of the engine tick, written into the profiler's trace (on the
# device ops' clock) and nearly free when no profiler is collecting.
_span = jax.profiler.TraceAnnotation


class _FlatLayout:
    """A static layout of a pytree as one flat 1-D buffer per dtype.

    Buffers follow the order in which their dtypes first appear among the
    leaves. A buffer starts with the leaves that lead with the stream
    axis (``n`` rows), as the row-major ``[n, width]`` block of their
    ``[n, -1]`` views side by side; the other leaves (the scalar
    aggregates) follow it raveled. ``pack`` and ``unpack`` are data
    movement only (concatenates, static slices and reshapes), so they run
    inside a compiled call or, on host numpy buffers, without one.

    The block keeps each leaf's ``[n, -1]`` view whole: XLA's CPU fusion
    then plans the step's own ops as it does with no pack at all. Raveled
    leaves gave it reshapes to fuse into, where it recomputed some values
    twice, once for the output and once for a later op, and the two
    copies could round apart (the LSTM ``fused`` oracle moved in the last
    place).
    """

    def __init__(self, tree, n: int):
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.n = n
        self.dtypes: list = []
        self.widths: list = []     # per buffer: the block's width
        self.slots: list = []      # per leaf: (buffer, in block?, offset)
        self.shapes = [tuple(leaf.shape) for leaf in leaves]
        tails: list = []           # per buffer: the raveled leaves' size
        for leaf, shape in zip(leaves, self.shapes):
            dtype = jnp.dtype(leaf.dtype)
            if dtype not in self.dtypes:
                self.dtypes += [dtype]
                self.widths += [0]
                tails += [0]
            b = self.dtypes.index(dtype)
            size = math.prod(shape)
            if shape[:1] == (n,):
                self.slots.append((b, True, self.widths[b]))
                self.widths[b] += size // n
            else:
                self.slots.append((b, False, tails[b]))
                tails[b] += size

    def pack(self, tree) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(f"tree {treedef} does not match the engine's "
                             f"layout {self.treedef}")
        rows = [[] for _ in self.dtypes]
        tails = [[] for _ in self.dtypes]
        for leaf, (b, row, _), shape in zip(leaves, self.slots,
                                            self.shapes):
            leaf = jnp.asarray(leaf)
            if (tuple(leaf.shape) != shape
                    or jnp.dtype(leaf.dtype) != self.dtypes[b]):
                raise ValueError(
                    f"leaf {leaf.dtype}{list(leaf.shape)} does not match "
                    f"the engine's layout {self.dtypes[b]}{list(shape)}")
            if row:
                rows[b].append(leaf.reshape(self.n, -1))
            else:
                tails[b].append(leaf.reshape(-1))
        return tuple(
            jnp.concatenate(([jnp.concatenate(r, axis=1).reshape(-1)]
                             if r else []) + t)
            for r, t in zip(rows, tails))

    def unpack(self, bufs):
        """The tree of ``bufs``: device arrays inside a trace, or the host
        numpy buffers of one ``device_get``."""
        n = self.n
        leaves = []
        for (b, row, off), shape in zip(self.slots, self.shapes):
            size = math.prod(shape)
            if row:
                block = bufs[b][:n * self.widths[b]].reshape(n, -1)
                leaf = block[:, off:off + size // n]
            else:
                start = n * self.widths[b] + off
                leaf = bufs[b][start:start + size]
            leaves.append(leaf.reshape(shape))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


class LmEngine:
    """Prefill/decode engine over a fixed slot count (the decode batch)."""

    def __init__(self, params, cfg: ModelConfig, batch: int, max_len: int):
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.caches = init_lm_caches(cfg, batch, max_len)
        self._prefill = jax.jit(
            lambda p, t, c, kw: lm_prefill(p, cfg, t, c, **kw))
        self._decode = jax.jit(lambda p, t, c: lm_decode(p, cfg, t, c))

    def prefill(self, tokens: Array, **modality) -> Array:
        """Prefill all slots with (padded) prompts; returns last logits."""
        logits, self.caches = self._prefill(self.params, tokens, self.caches,
                                            dict(modality))
        return logits

    def decode_step(self, tokens: Array) -> Array:
        """One decode step for every slot. ``tokens: [B, 1]``."""
        logits, self.caches = self._decode(self.params, tokens, self.caches)
        return logits

    def generate_greedy(self, tokens: Array, steps: int, **modality):
        """Greedy generation; returns ``[B, steps]`` new tokens."""
        logits = self.prefill(tokens, **modality)
        out = []
        cur = jnp.argmax(logits[:, -1:], axis=-1)
        for _ in range(steps):
            out.append(cur)
            logits = self.decode_step(cur)
            cur = jnp.argmax(logits[:, -1:], axis=-1)
        return jnp.concatenate(out, axis=1)


@dataclass
class StreamStats:
    """Aggregate (stream-averaged) accounting, one device sync per read.

    The ``ufired_*`` / ``tile_*`` fields are the batched-tile terms:
    union firing across the stream tile and the Eq. 7 latency/bytes of
    the ONE weight pass that serves it (meaningful on
    ``weight_fetch="tile"`` backends; for a single stream they equal the
    per-stream terms).
    """

    steps: int = 0
    fired_x: float = 0.0
    fired_h: float = 0.0
    est_latency_s: float = 0.0
    w_bytes: float = 0.0
    ufired_x: float = 0.0
    ufired_h: float = 0.0
    tile_est_latency_s: float = 0.0
    tile_w_bytes: float = 0.0
    # resilience counters (engine-lifetime TOTALS across all streams):
    # frames the guard masked to the silent regime / steps whose post-step
    # stack state went non-finite
    poison_steps: float = 0.0
    bad_state_steps: float = 0.0

    @property
    def gamma_dx(self) -> float:
        return 1.0 - self.fired_x / max(self.steps, 1)

    @property
    def gamma_dh(self) -> float:
        return 1.0 - self.fired_h / max(self.steps, 1)

    @property
    def union_gamma_dx(self) -> float:
        return 1.0 - self.ufired_x / max(self.steps, 1)

    @property
    def union_gamma_dh(self) -> float:
        return 1.0 - self.ufired_h / max(self.steps, 1)


def _held_part(whole: str, bufs: str, part: int) -> property:
    """Part ``part`` (0: state, 1: carry) of the held pair kept in the
    attributes ``whole`` / ``bufs``, read and assigned as its pytree: a
    read unpacks the buffers, an assignment packs them again."""
    def get(self):
        return self._view(getattr(self, whole), getattr(self, bufs))[part]

    def put(self, value):
        pair = list(self._view(getattr(self, whole), getattr(self, bufs)))
        pair[part] = value
        w, b = self._hold(*pair)
        setattr(self, whole, w)
        setattr(self, bufs, b)
    return property(get, put)


class DeltaStreamEngine:
    """Streaming delta-RNN inference (the EdgeDRNN deployment mode).

    Args:
      program: a compiled :class:`~repro.core.program.DeltaProgram` of any
        cell family (must carry a head, i.e. compiled from an
        ``init_gru_model`` / ``init_lstm_model`` params dict) — the
        primary spelling. A raw params dict is also accepted and compiled
        internally with the legacy ``backend=`` / ``layouts=`` kwargs
        (default backend: ``"fused"``; the dict's ``"gru"`` / ``"lstm"``
        key picks the cell).
      task: network config (sizes + default thresholds).
      thresholds: static dual-threshold policy override. Per-layer
        ``per_layer_x`` / ``per_layer_h`` overrides are threaded into the
        jitted step (mutually exclusive with the dynamic controller,
        which adjusts ONE scalar Θ_h).
      accel: accelerator spec for the Eq. 7 latency model.
      dynamic_target_fired: if set, the closed-loop Θ_h controller runs
        *inside* the jitted step, tracking this firing-fraction target.
      backend / layouts: legacy shim kwargs, only meaningful with a params
        dict; passing them alongside a program is an error (the program
        already fixes both).
      n_streams: number of independent stream slots batched through one
        kernel (the heavy-traffic mode: weights are fetched once per step
        for all slots). ``step``/``step_many`` then take ``[N, I]`` /
        ``[T, N, I]``. Slots double as serving sessions via
        :meth:`open_stream` / :meth:`close_stream`. When a pack-compatible
        ``*_batch`` tile backend is registered for the program's backend
        (``fused`` / ``fused_q8`` both cells), ``n_streams > 1`` routes
        the program onto it — outputs are bit-identical, and
        :meth:`report` additionally prices the tile economics: one weight
        fetch per step at the UNION firing across streams, with
        ``weight_bytes_per_stream_per_step = tile bytes / n_streams``.
        The per-stream session accounting keeps its historical meaning
        (what each stream would cost served alone on a batch-1 device).

    The Eq. 7 latency model prices the *streamed weight width* of the
    program's backend (:func:`repro.core.perf_model.spec_for_backend`):
    the fp32 backends pay 4 bytes/weight over the spec's DRAM bus while
    ``fused_q8`` streams the paper's INT8 — and the cell's gate count
    (``dims.gates``: 3 for GRU, 4 for LSTM) scales the weight volume each
    fired delta column fetches, so :attr:`accel` (and every latency/bytes
    figure in :meth:`report`) reflects what the backend actually fetches,
    not the training-time fiction.
    """

    _PER_STREAM_KEYS = ("fired_x", "fired_h", "lat_s", "w_bytes",
                        "poison_steps", "bad_state")

    def __init__(self, program, task: GruTaskConfig,
                 thresholds: ThresholdPolicy | None = None,
                 accel: AcceleratorSpec = EDGEDRNN,
                 dynamic_target_fired: float | None = None,
                 backend: str | None = None,
                 layouts=None,
                 n_streams: int = 1):
        if isinstance(program, DeltaProgram):
            if backend is not None and backend != program.backend:
                raise ValueError(
                    f"backend={backend!r} conflicts with the compiled "
                    f"program's backend {program.backend!r}; drop the kwarg")
            if layouts is not None:
                raise ValueError("layouts= is meaningless with a compiled "
                                 "program — it already holds its packs")
        else:
            # legacy shim: params dict + knob kwargs -> compile here
            program = compile_delta_program(program,
                                            backend=backend or "fused",
                                            cell=infer_cell(program),
                                            layouts=layouts)
        if not program.has_head:
            raise ValueError(
                "DeltaStreamEngine needs a program with a classifier head; "
                "compile from an init_gru_model / init_lstm_model params "
                "dict")
        # Multi-stream routing: a tile of streams should pay ONE weight
        # fetch per step, so swap onto the pack-compatible "*_batch"
        # sibling when one is registered (same packed layouts, same math
        # — outputs stay bit-identical). Backends with no batched sibling
        # (e.g. "dense") keep per-stream pricing.
        if (n_streams > 1
                and program.spec.weight_fetch != "tile"):
            try:
                program = program.with_backend(program.backend + "_batch")
            except ValueError:
                pass
        self._tile_fetch = program.spec.weight_fetch == "tile"
        self.program = program
        self.params = list(program.layers)   # legacy attr (the cell stack)
        self.head = (program.head, program.head_b)
        self.task = task
        self.cell = program.cell
        self.accel = spec_for_backend(accel, program.backend,
                                      cell=program.cell)
        self.backend = program.backend
        self.n_streams = n_streams
        self.thresholds = thresholds or ThresholdPolicy(task.theta_x,
                                                        task.theta_h)
        self.theta_x = self.thresholds.theta_x
        self.dynamic_target = dynamic_target_fired
        self.dims = cell_dims(program.cell, task.input_size,
                              task.hidden_size, task.num_layers,
                              layers=program.layers)
        # token frames stay int32 end to end; a family with a KV cache
        # refuses a token past it (counted here, on the host)
        self._frame_dtype = program.frame_dtype
        self._max_context = program.max_context
        # per-layer thresholds ride as static tuples inside the jitted
        # step; the dynamic controller steers ONE scalar theta_h, so the
        # two are mutually exclusive rather than silently combined.
        self._per_layer = self.thresholds.has_per_layer
        if self._per_layer:
            if dynamic_target_fired is not None:
                raise ValueError(
                    "per-layer thresholds and the dynamic-theta controller "
                    "are mutually exclusive: the controller adjusts one "
                    "scalar theta_h, which would silently override the "
                    "per-layer policy")
            self._theta_x_layers, self._theta_h_layers = \
                self.thresholds.layer_thetas(task.num_layers)
        else:
            self._theta_x_layers = self._theta_h_layers = None

        def _nonfinite_rows(tree):
            """Per-stream flag: any non-finite value in any float leaf of
            the stack state (``[N]`` float; int leaves — the q8 code
            domains — are always finite and skipped)."""
            flags = jnp.zeros((n_streams,), jnp.float32)
            for leaf in jax.tree_util.tree_leaves(tree):
                if not jnp.issubdtype(leaf.dtype, jnp.floating):
                    continue
                bad = jnp.any(~jnp.isfinite(
                    leaf.reshape((n_streams, -1))), axis=-1)
                flags = jnp.maximum(flags, bad.astype(jnp.float32))
            return flags

        def _one_step(prog, state, carry, x):
            """One timestep, stats + controller on-device (no host sync).

            Firing fractions are tracked **per stream** (``[N]`` carry
            vectors); the Eq. 7 latency / byte terms are linear in the
            firing fractions, so stream means reproduce the old aggregate
            accounting exactly.

            The frame guard runs first: a frame with ANY non-finite
            component is replaced by that stream's previous guarded frame
            (``last_x`` carry), which is exactly the zero-delta silent
            regime — every delta-memory component either fired last step
            (so the repeated frame deltas to 0) or sits below Θ already.
            Non-finite inputs therefore never reach the kernels or the
            recurrent state, and the per-slot ``poison_steps`` counter
            records the masking without any host round-trip.
            """
            finite = jnp.all(prog.frame_ok(x), axis=-1)         # [N]
            x = jnp.where(finite[:, None], x, carry["last_x"])
            poison = 1.0 - finite.astype(jnp.float32)            # [N]
            tx = (self._theta_x_layers if self._per_layer
                  else self.theta_x)
            th = (self._theta_h_layers if self._per_layer
                  else carry["theta_h"])
            y, new_state, deltas = prog.step(state, x, tx, th)
            bad = _nonfinite_rows(prog.watched(new_state))       # [N]
            out = prog.apply_head(y)
            fx = jnp.mean(jnp.stack(
                [jnp.mean((dx != 0).astype(jnp.float32), axis=-1)
                 for dx, _ in deltas]), axis=0)                   # [N]
            fh = jnp.mean(jnp.stack(
                [jnp.mean((dh != 0).astype(jnp.float32), axis=-1)
                 for _, dh in deltas]), axis=0)                   # [N]
            theta_h = carry["theta_h"]
            if self.dynamic_target is not None:
                theta_h = dynamic_threshold(theta_h, jnp.mean(fh),
                                            self.dynamic_target)
            # Eq. 7 latency / weight bytes for this step's actual firing
            # fractions, per stream
            lat = stack_latency_s(self.dims, 1.0 - fx, 1.0 - fh, self.accel)
            wb = dram_traffic_bytes_per_timestep(
                self.dims, 1.0 - fx, 1.0 - fh,
                w_weight_bits=self.accel.w_weight_bits)
            # tile economics: a column is fetched when ANY stream fired
            # it — the batched kernels compact on this union, so the
            # shared weight pass is priced at the union firing fractions
            ufx = jnp.mean(jnp.stack(
                [jnp.mean(jnp.any(dx != 0, axis=0).astype(jnp.float32))
                 for dx, _ in deltas]))
            ufh = jnp.mean(jnp.stack(
                [jnp.mean(jnp.any(dh != 0, axis=0).astype(jnp.float32))
                 for _, dh in deltas]))
            tile_lat = stack_latency_s(self.dims, 1.0 - ufx, 1.0 - ufh,
                                       self.accel)
            tile_wb = dram_traffic_bytes_per_timestep(
                self.dims, 1.0 - ufx, 1.0 - ufh,
                w_weight_bits=self.accel.w_weight_bits)
            new_carry = {
                # per-stream accumulators ([N]): session accounting; these
                # are zeroed slotwise by open_stream's masked reset
                "fired_x": carry["fired_x"] + fx,
                "fired_h": carry["fired_h"] + fh,
                "lat_s": carry["lat_s"] + lat,
                "w_bytes": carry["w_bytes"] + wb,
                # engine-lifetime aggregates (scalars): never touched by
                # session opens, so stats/report() stay exact however many
                # short-lived streams recycled through the slots
                "agg_fired_x": carry["agg_fired_x"] + jnp.mean(fx),
                "agg_fired_h": carry["agg_fired_h"] + jnp.mean(fh),
                "agg_lat_s": carry["agg_lat_s"] + jnp.mean(lat),
                "agg_w_bytes": carry["agg_w_bytes"] + jnp.mean(wb),
                # tile-level lifetime aggregates (scalars): union firing
                # + the once-per-tile weight pass it prices; reported
                # only on tile-fetch backends but carried uniformly
                "agg_ufired_x": carry["agg_ufired_x"] + ufx,
                "agg_ufired_h": carry["agg_ufired_h"] + ufh,
                "agg_tile_lat_s": carry["agg_tile_lat_s"] + tile_lat,
                "agg_tile_w_bytes": carry["agg_tile_w_bytes"] + tile_wb,
                # resilience carry: the guard's frame memory plus per-slot
                # poison / state-corruption counters (session-scoped, so
                # they zero on open_stream like the other per-stream keys)
                # and never-reset lifetime TOTALS (sums, not means — these
                # are exact event counts, not rate estimates)
                "last_x": x,
                "poison_steps": carry["poison_steps"] + poison,
                "bad_state": carry["bad_state"] + bad,
                "agg_poison_steps": carry["agg_poison_steps"]
                                    + jnp.sum(poison),
                "agg_bad_state": carry["agg_bad_state"] + jnp.sum(bad),
                "theta_h": theta_h,
            }
            return out, new_state, new_carry

        def _step(prog, state, carry, x):
            return _one_step(prog, state, carry, x)

        def _steps(prog, state, carry, xs):
            def body(sc, x):
                state, carry = sc
                out, state, carry = _one_step(prog, state, carry, x)
                return (state, carry), out

            (state, carry), outs = jax.lax.scan(body, (state, carry), xs)
            return outs, state, carry

        n = n_streams

        def _reset_streams(prog, state, carry, mask):
            """Masked per-slot reset: fresh state + zeroed accounting for
            slots where ``mask`` is True; everything else untouched."""
            fresh = prog.init_state((n,))

            def sel(cur, new):
                m = mask.reshape((n,) + (1,) * (cur.ndim - 1))
                return jnp.where(m, new, cur)

            state = jax.tree_util.tree_map(sel, state, fresh)
            carry = dict(carry)
            for k in self._PER_STREAM_KEYS:
                carry[k] = jnp.where(mask, 0.0, carry[k])
            carry["last_x"] = jnp.where(mask[:, None],
                                        jnp.zeros((), carry["last_x"].dtype),
                                        carry["last_x"])
            return state, carry

        def _merge_rows(prog, dst_state, dst_carry, src_state, src_carry,
                        mask):
            """Take ``src``'s slot rows where ``mask`` is True, ``dst``'s
            elsewhere — the snapshot/rollback primitive (used in both
            directions). Only the per-stream carry entries move; the
            engine-lifetime aggregates and Θ_h always keep ``dst``'s
            values, so a rollback never un-counts steps that really
            executed and never disturbs the global threshold."""
            del prog
            def sel(cur, new):
                m = mask.reshape((n,) + (1,) * (cur.ndim - 1))
                return jnp.where(m, new, cur)

            state = jax.tree_util.tree_map(sel, dst_state, src_state)
            carry = dict(dst_carry)
            for k in self._PER_STREAM_KEYS:
                carry[k] = jnp.where(mask, src_carry[k], dst_carry[k])
            carry["last_x"] = jnp.where(mask[:, None], src_carry["last_x"],
                                        dst_carry["last_x"])
            return state, carry

        # Raw (un-jitted) closures over the *local* tile width.  The
        # sharded fleet (`dist/serving.ShardedStreamFleet`) re-wraps these
        # under `shard_map`, where each device traces them at the
        # per-shard block shapes — per-stream vectors become ``[B]``
        # slices and the lifetime aggregates become ``[1]`` slices of a
        # per-shard vector; the closures are shape-polymorphic in both.
        bound = lambda f: functools.partial(f, program)
        self._one_step_fn = bound(_one_step)
        self._steps_fn = bound(_steps)
        self._reset_streams_fn = bound(_reset_streams)
        self._merge_rows_fn = bound(_merge_rows)

        # Batch 1: the ``[I]`` -> ``[1, I]`` reshape and the ``[0]`` slice
        # of the output run inside the compiled step, so a single-stream
        # tick dispatches ONE call (an eager op on the host costs a whole
        # dispatch). ``x`` may be ``[I]`` or ``[1, I]``; ``xs`` is
        # ``[T, I]``.
        def _step1(prog, state, carry, x):
            out, state, carry = _one_step(prog, state, carry,
                                          x.reshape(1, -1))
            return out[0], state, carry

        def _steps1(prog, state, carry, xs):
            outs, state, carry = _steps(prog, state, carry, xs[:, None, :])
            return outs[:, 0], state, carry

        # The engine holds its state and carry as a pair ``(whole, bufs)``
        # and the compiled calls take and return that pair: a call costs
        # the host a fixed time per argument and result buffer (~70 us on
        # a TPU v5e host, PERF.md §5), far more than a batch-1 step's
        # device work, so ``bufs`` packs the leaves into one flat buffer
        # per dtype, unpacked and packed again inside the call. A family
        # with a KV cache (``in_place``) keeps its state leaves ``whole``,
        # donated and updated in place (a concatenate would copy the cache
        # every token), and packs only the carry; elsewhere ``whole`` is
        # None.
        self._packs_state = not program.in_place
        fresh = jax.eval_shape(lambda: (
            program.init_state(batch_shape=(n,)), self._fresh_carry()))
        layout = _FlatLayout(fresh if self._packs_state else fresh[1],
                             n)
        self._layout = layout
        self._pack = jax.jit(layout.pack)
        self._unpack = jax.jit(layout.unpack)

        def held(f):
            """``f(prog, state, carry, *a) -> (*outs, state, carry)`` on
            the held pair: ``(prog, whole, bufs, *a) -> (*outs, whole,
            bufs)``."""
            def g(prog, whole, bufs, *a):
                *outs, state, carry = f(prog, *self._view(whole, bufs), *a)
                return (*outs, *self._hold(state, carry))
            return g

        def _merge_held(prog, dst_whole, dst_bufs, src_whole, src_bufs,
                        mask):
            return self._hold(*_merge_rows(
                prog, *self._view(dst_whole, dst_bufs),
                *self._view(src_whole, src_bufs), mask))

        if program.in_place:
            # A family with a KV cache: its weights go in as arguments
            # (closed over, a multi-GB model would be copied into the
            # executable as constants) and its whole state, argument 1, is
            # donated, so the cache and the SSM state are updated in place
            # instead of copied every token (on a merge, the destination
            # rows' state: the shadow on a snapshot, the live state on a
            # rollback).
            def jit(f):
                jf = jax.jit(f, donate_argnums=(1,))
                return lambda *a: jf(self.program, *a)
        else:
            # The GRU / LSTM programs keep their weights closed over, as
            # constants: passed as arguments, XLA sums their products in
            # another order, and the outputs move in the last place (the
            # benchmark's golden digests and the bit-for-bit tests of
            # tests/test_engine_batch1.py pin them).
            def jit(f):
                return jax.jit(bound(f))
        self._step = jit(held(_step))
        self._steps = jit(held(_steps))
        self._step1 = jit(held(_step1))
        self._steps1 = jit(held(_steps1))
        self._reset_streams = jit(held(_reset_streams))
        self._merge_rows = jit(_merge_held)
        self.reset()

    # -- the held state ---------------------------------------------------

    state = _held_part("_whole", "_bufs", 0)
    _carry = _held_part("_whole", "_bufs", 1)
    _snap_state = _held_part("_snap_whole", "_snap_bufs", 0)
    _snap_carry = _held_part("_snap_whole", "_snap_bufs", 1)

    def _view(self, whole, bufs):
        """The ``(state, carry)`` pytrees of a held pair; inside a trace
        the unpack is inlined, outside it is one compiled call."""
        held = self._unpack(bufs)
        return held if self._packs_state else (whole, held)

    def _hold(self, state, carry):
        """The held pair ``(whole, bufs)`` of a ``(state, carry)``."""
        if self._packs_state:
            return None, self._pack((state, carry))
        return state, self._pack(carry)

    def host_carry(self) -> dict:
        """The live carry on the host as numpy arrays: ONE ``device_get``
        of the packed buffers (the state's part too, where it is packed),
        split by the static layout with no further device call."""
        held = self._layout.unpack(jax.device_get(self._bufs))
        return held[1] if self._packs_state else held

    # -- hot path ---------------------------------------------------------

    def step(self, x: np.ndarray | Array) -> Array:
        """Process one timestep.

        ``x: [I]`` (single stream) or ``[n_streams, I]``; returns ``[O]`` /
        ``[n_streams, O]``. The returned array is a device array — reading
        it (or :attr:`stats`) is what synchronizes, not the call itself.
        A single-stream engine takes ``[I]`` or ``[1, I]`` and makes ONE
        compiled call: the reshape and the ``[0]`` slice run inside it.

        The shape is validated like :meth:`step_many`'s: an earlier
        revision did ``x.reshape(self.n_streams, -1)``, which silently
        scrambled frames across streams whenever a wrong-but-divisible
        shape (e.g. a single ``[I]`` vector on a multi-stream engine) was
        handed in.

        A host numpy frame is SNAPSHOTTED on entry with a *synchronous*
        ``np.array`` copy. ``jnp.asarray`` zero-copy aliases a host
        buffer on CPU backends — and even ``jnp.array``'s ingestion is
        deferred past the async step dispatch — so an aliased input that
        the caller mutates before the device reads it (exactly what a
        scheduler reusing one frame buffer per tick does) would
        nondeterministically corrupt the stream under load. Device
        arrays are immutable and skip the copy, keeping the zero-sync
        hot path.
        """
        with _span("engine.h2d"):
            if isinstance(x, np.ndarray):
                x = np.array(x, self._frame_dtype)
            x = jnp.asarray(x, self._frame_dtype)
            i_dim = self.dims.input_size
            single = self.n_streams == 1
            if x.shape != (self.n_streams, i_dim) and not (
                    single and x.shape == (i_dim,)):
                want = (f"[{i_dim}]" if single
                        else f"[{self.n_streams}, {i_dim}]")
                raise ValueError(
                    f"engine has n_streams={self.n_streams}; step needs one "
                    f"frame per stream slot, shape {want}"
                    f"{f' or [1, {i_dim}]' if single else ''}, "
                    f"got {tuple(x.shape)} — reshaping would silently "
                    "cross-contaminate stream slots")
        if self._max_context is not None:
            self._check_context(1)
        with _span("engine.launch"):
            step = self._step1 if single else self._step
            out, self._whole, self._bufs = step(self._whole, self._bufs, x)
        self._n_steps += 1
        return out

    def step_many(self, xs: np.ndarray | Array) -> Array:
        """Process a chunk of timesteps in ONE device call (``lax.scan``).

        ``xs: [T, I]`` or ``[T, n_streams, I]``; returns ``[T, O]`` /
        ``[T, n_streams, O]``. Zero per-timestep Python dispatch — the whole
        chunk, including stats/controller updates, runs on-device. A host
        numpy chunk is snapshotted on entry (see :meth:`step` — jax's
        deferred ingestion of a caller-owned buffer races with the async
        dispatch).
        """
        with _span("engine.h2d"):
            if isinstance(xs, np.ndarray):
                xs = np.array(xs, self._frame_dtype)
            xs = jnp.asarray(xs, self._frame_dtype)
            squeeze = xs.ndim == 2
            if squeeze:
                if self.n_streams != 1:
                    raise ValueError(
                        f"engine has n_streams={self.n_streams}; step_many "
                        f"needs [T, {self.n_streams}, I], got {xs.shape} "
                        "(a 2-D chunk would silently broadcast one "
                        "stream's input to all streams)")
            elif xs.shape[1] != self.n_streams:
                raise ValueError(
                    f"chunk stream dim {xs.shape[1]} != n_streams="
                    f"{self.n_streams} (xs: {xs.shape})")
        if self._max_context is not None:
            self._check_context(xs.shape[0])
        with _span("engine.launch"):
            steps = self._steps1 if squeeze else self._steps
            outs, self._whole, self._bufs = steps(self._whole, self._bufs,
                                                  xs)
        self._n_steps += xs.shape[0]
        return outs

    def _tokens(self, sid: int) -> int:
        """Frames slot ``sid`` has taken since its session (or the engine)
        started: for a family with a KV cache, the tokens the cache holds."""
        return self._n_steps - self._slot_opened_at[sid]

    def _check_context(self, n_new: int) -> None:
        """Refuse, before any device work, a step that would take an open
        stream (every slot, where no session is open) past the cache's
        ``max_context`` tokens: the cache never wraps."""
        live = [s for s, busy in enumerate(self._slot_busy) if busy] \
            or range(self.n_streams)
        for sid in live:
            if self._tokens(sid) + n_new > self._max_context:
                raise ValueError(
                    f"stream slot {sid} holds {self._tokens(sid)} tokens; "
                    f"{n_new} more would pass its KV cache of "
                    f"{self._max_context} positions (max_context), which "
                    "does not wrap")

    # -- per-stream sessions ----------------------------------------------

    @property
    def free_streams(self) -> list:
        """Slot ids not currently claimed by an open session."""
        return [i for i, busy in enumerate(self._slot_busy) if not busy]

    def open_stream(self) -> int:
        """Claim a free slot for a new stream session.

        Masked-resets ONLY that slot — its stack state returns to the
        program's init convention and its accounting accumulators zero,
        while every other stream runs on undisturbed. Returns the slot id
        to feed/read on the ``step``/``step_many`` stream axis. Raises
        ``RuntimeError`` when all ``n_streams`` slots are busy (queue the
        request — see ``serve.scheduler.GruStreamBatcher``).
        """
        free = self.free_streams
        if not free:
            raise RuntimeError(
                f"all {self.n_streams} stream slots are busy; close one "
                "or queue through GruStreamBatcher")
        sid = free[0]
        mask = np.zeros((self.n_streams,), bool)
        mask[sid] = True
        self._whole, self._bufs = self._reset_streams(
            self._whole, self._bufs, jnp.asarray(mask))
        self._slot_busy[sid] = True
        self._slot_opened_at[sid] = self._n_steps
        # seed the slot's rollback target with its fresh session state, so
        # a rollback issued before any explicit snapshot rewinds to the
        # session start instead of a stale previous occupant
        self.snapshot_streams([sid])
        return sid

    def close_stream(self, sid: int, host_carry=None) -> dict:
        """Release a session slot; returns THAT stream's accounting.

        One host sync (the per-stream carry vectors materialize once).
        The slot is immediately reusable by the next :meth:`open_stream`.
        ``host_carry`` lets a scheduler harvesting several streams in one
        tick fetch the carry once (:meth:`host_carry`) and share it
        across the closes instead of syncing per stream.
        """
        if not (0 <= sid < self.n_streams) or not self._slot_busy[sid]:
            raise ValueError(f"stream {sid} is not open")
        host = host_carry if host_carry is not None else self.host_carry()
        steps = self._tokens(sid)
        fired_x = float(host["fired_x"][sid])
        fired_h = float(host["fired_h"][sid])
        lat = float(host["lat_s"][sid])
        wb = float(host["w_bytes"][sid])
        self._slot_busy[sid] = False
        out = {
            "stream": sid,
            "steps": steps,
            "gamma_dx": 1.0 - fired_x / max(steps, 1),
            "gamma_dh": 1.0 - fired_h / max(steps, 1),
            "est_latency_s": lat,
            "mean_est_latency_us": 1e6 * lat / max(steps, 1),
            "w_bytes": wb,
            "mean_weight_bytes_per_step": wb / max(steps, 1),
            "poison_steps": float(host["poison_steps"][sid]),
            "bad_state_steps": float(host["bad_state"][sid]),
        }
        if self._max_context is not None:
            out["kv_len"] = steps
        return out

    # -- resilience: snapshot / rollback / checkpoint ----------------------

    def snapshot_streams(self, sids: list | None = None):
        """Copy the named slots' live rows into the rollback shadow.

        ``sids=None`` snapshots every currently open session. Pure device
        work (the same masked select as the session reset) — no host sync,
        so a supervisor can snapshot on a cadence without breaking the
        zero-sync hot loop. A caller is responsible for only snapshotting
        slots it believes healthy; snapshotting a corrupted slot would
        make the corruption the rollback target.
        """
        if sids is None:
            sids = [i for i, busy in enumerate(self._slot_busy) if busy]
        if not sids:
            return
        mask = np.zeros((self.n_streams,), bool)
        for sid in sids:
            if not (0 <= sid < self.n_streams):
                raise ValueError(f"stream {sid} out of range")
            mask[sid] = True
        self._snap_whole, self._snap_bufs = self._merge_rows(
            self._shadow(), self._snap_bufs, self._whole, self._bufs,
            jnp.asarray(mask))
        for sid in sids:
            self._snap_steps[sid] = self._n_steps - self._slot_opened_at[sid]

    def rollback_stream(self, sid: int) -> int:
        """Rewind ONE slot to its last snapshot (session start if none).

        Restores the slot's stack state, guard frame memory, and session
        accounting from the shadow; every other slot and the lifetime
        aggregates are untouched (steps that really executed stay
        counted). Returns the session-step index the slot rewinds to, so
        the caller knows which frames to replay. Device work only.
        """
        if not (0 <= sid < self.n_streams) or not self._slot_busy[sid]:
            raise ValueError(f"stream {sid} is not open")
        mask = np.zeros((self.n_streams,), bool)
        mask[sid] = True
        self._whole, self._bufs = self._merge_rows(
            self._whole, self._bufs, self._shadow(), self._snap_bufs,
            jnp.asarray(mask))
        # the slot has logically executed only _snap_steps[sid] session
        # steps again; engine-global _n_steps keeps marching, so rebase
        # the slot's open marker to preserve steps = _n_steps - opened_at
        self._slot_opened_at[sid] = self._n_steps - self._snap_steps[sid]
        return self._snap_steps[sid]

    def set_theta_h(self, value: float):
        """Overwrite the live Θ_h (device write, no sync).

        The overload path for a supervisor: raise Θ_h to shed compute
        under pressure, decay it back on drain
        (``serve.resilience.ResilientStreamServer``). Mutually exclusive
        with per-layer thresholds for the same reason the in-jit dynamic
        controller is.
        """
        if self._per_layer:
            raise ValueError(
                "set_theta_h adjusts one scalar theta_h, which would "
                "silently override the per-layer threshold policy")
        self._carry = {**self._carry, "theta_h": jnp.float32(value)}

    def _shadow(self):
        """The whole state of the rollback shadow (None where the state is
        packed). An in-place engine mints it on first use: until a
        snapshot is taken it holds fresh state, and a shadow that aliased
        the donated live state would be freed by the next step."""
        if self._snap_whole is None and not self._packs_state:
            self._snap_whole = self.program.init_state(
                batch_shape=(self.n_streams,))
        return self._snap_whole

    def _ckpt_tree(self):
        """The engine's full restorable pytree (state + carry + shadows +
        host-side slot bookkeeping as numpy leaves), unpacked."""
        state, carry = self._view(self._whole, self._bufs)
        snap_state, snap_carry = self._view(self._shadow(), self._snap_bufs)
        return {
            "state": state,
            "carry": carry,
            "snap_state": snap_state,
            "snap_carry": snap_carry,
            "meta": {
                "n_steps": np.int64(self._n_steps),
                "slot_busy": np.asarray(self._slot_busy, bool),
                "slot_opened_at": np.asarray(self._slot_opened_at,
                                             np.int64),
                "snap_steps": np.asarray(self._snap_steps, np.int64),
            },
        }

    def checkpoint(self, ckpt_dir: str, step: int | None = None) -> str:
        """Publish a crash-consistent engine checkpoint (atomic rename via
        :mod:`repro.ft.checkpoint`). Captures recurrent state, the full
        accounting carry, the rollback shadows, and slot bookkeeping —
        :meth:`restore` resumes with byte-identical streams and EXACT
        :meth:`report` continuity. Syncs (the tree lands on host)."""
        step = self._n_steps if step is None else step
        return ft_checkpoint.save(ckpt_dir, step, self._ckpt_tree())

    @classmethod
    def restore(cls, ckpt_dir: str, program, task, step: int | None = None,
                **kwargs) -> "DeltaStreamEngine":
        """Rebuild an engine from :meth:`checkpoint` output.

        ``program``/``task``/``kwargs`` must match the checkpointing
        engine's construction (weights travel in the program, not the
        checkpoint); shape mismatches fail loudly in
        :func:`repro.ft.checkpoint.restore`.
        """
        eng = cls(program, task, **kwargs)
        tree = ft_checkpoint.restore(ckpt_dir, eng._ckpt_tree(), step=step)
        eng._whole, eng._bufs = eng._hold(tree["state"], tree["carry"])
        eng._snap_whole, eng._snap_bufs = eng._hold(tree["snap_state"],
                                                    tree["snap_carry"])
        meta = jax.device_get(tree["meta"])
        eng._n_steps = int(meta["n_steps"])
        eng._slot_busy = [bool(b) for b in meta["slot_busy"]]
        eng._slot_opened_at = [int(v) for v in meta["slot_opened_at"]]
        eng._snap_steps = [int(v) for v in meta["snap_steps"]]
        return eng

    # -- accounting -------------------------------------------------------

    @property
    def theta_h(self) -> float:
        """Current Θ_h (syncs once; moves only under the dynamic controller)."""
        return float(self.host_carry()["theta_h"])

    @property
    def stats(self) -> StreamStats:
        """Materialize the device carry ONCE; engine-lifetime aggregates.

        Reads the scalar lifetime accumulators (stream means, updated
        every step, never reset by session opens) — exact whatever mix of
        open/close traffic the slots have seen. The accounting terms are
        linear in the firing fractions, so the stream mean reproduces the
        single-stream accounting exactly.
        """
        host = self.host_carry()
        return StreamStats(
            steps=self._n_steps,
            fired_x=float(host["agg_fired_x"]),
            fired_h=float(host["agg_fired_h"]),
            est_latency_s=float(host["agg_lat_s"]),
            w_bytes=float(host["agg_w_bytes"]),
            ufired_x=float(host["agg_ufired_x"]),
            ufired_h=float(host["agg_ufired_h"]),
            tile_est_latency_s=float(host["agg_tile_lat_s"]),
            tile_w_bytes=float(host["agg_tile_w_bytes"]),
            poison_steps=float(host["agg_poison_steps"]),
            bad_state_steps=float(host["agg_bad_state"]),
        )

    def _fresh_carry(self) -> dict:
        zeros = jnp.zeros((self.n_streams,), jnp.float32)
        return {
            "fired_x": zeros,
            "fired_h": zeros,
            "lat_s": zeros,
            "w_bytes": zeros,
            "agg_fired_x": jnp.float32(0.0),
            "agg_fired_h": jnp.float32(0.0),
            "agg_lat_s": jnp.float32(0.0),
            "agg_w_bytes": jnp.float32(0.0),
            "agg_ufired_x": jnp.float32(0.0),
            "agg_ufired_h": jnp.float32(0.0),
            "agg_tile_lat_s": jnp.float32(0.0),
            "agg_tile_w_bytes": jnp.float32(0.0),
            "last_x": jnp.zeros((self.n_streams, self.dims.input_size),
                                self._frame_dtype),
            "poison_steps": zeros,
            "bad_state": zeros,
            "agg_poison_steps": jnp.float32(0.0),
            "agg_bad_state": jnp.float32(0.0),
            "theta_h": jnp.float32(self.thresholds.theta_h),
        }

    def reset(self):
        self._whole, self._bufs = self._hold(
            self.program.init_state(batch_shape=(self.n_streams,)),
            self._fresh_carry())
        self._n_steps = 0
        self._slot_busy = [False] * self.n_streams
        self._slot_opened_at = [0] * self.n_streams
        # snapshot shadows (device-resident): rollback targets per slot.
        # _snap_steps[sid] = session-steps already executed at snapshot
        # time, so a rollback can rewind the slot's step bookkeeping too.
        # An in-place engine's whole state is donated, so its shadow is
        # minted apart (_shadow); packed buffers are never donated.
        self._snap_whole = None
        self._snap_bufs = self._bufs
        self._snap_steps = [0] * self.n_streams

    def _step_buffers(self) -> int:
        """Argument and result buffers of one compiled tick: the frame and
        the held pair in (and the weights, where they are arguments), the
        output and the held pair out."""
        held = len(jax.tree_util.tree_leaves(self._whole)) + len(self._bufs)
        weights = (len(jax.tree_util.tree_leaves(self.program))
                   if self.program.in_place else 0)
        return weights + 2 * (1 + held)

    def report(self) -> dict:
        s = self.stats
        est = estimate_stack(self.dims, s.gamma_dx, s.gamma_dh, self.accel)
        rep = {
            "steps": s.steps,
            "gamma_dx": s.gamma_dx,
            "gamma_dh": s.gamma_dh,
            "mean_est_latency_us": 1e6 * s.est_latency_s / max(s.steps, 1),
            "mean_weight_bytes_per_step": s.w_bytes / max(s.steps, 1),
            "weight_bits": self.accel.w_weight_bits,
            "effective_throughput_gops": est.throughput_ops / 1e9,
            "theta_x": self.theta_x,
            "theta_h": self.theta_h,
            "backend": self.backend,
            "cell": self.cell,
            "n_streams": self.n_streams,
            "weight_fetch": "tile" if self._tile_fetch else "stream",
            "poison_steps": s.poison_steps,
            "bad_state_steps": s.bad_state_steps,
            "step_buffers": self._step_buffers(),
        }
        if self._tile_fetch:
            # the batched-tile economics: ONE weight pass per step serves
            # the whole stream tile, priced at the union firing; the
            # per-stream fields above keep their served-alone meaning
            steps = max(s.steps, 1)
            rep["union_gamma_dx"] = s.union_gamma_dx
            rep["union_gamma_dh"] = s.union_gamma_dh
            rep["tile_est_latency_us"] = 1e6 * s.tile_est_latency_s / steps
            rep["tile_weight_bytes_per_step"] = s.tile_w_bytes / steps
            rep["weight_bytes_per_stream_per_step"] = (
                s.tile_w_bytes / steps / self.n_streams)
        if self._max_context is not None:
            rep["kv_len"] = [self._tokens(s) for s in range(self.n_streams)]
            rep["max_context"] = self._max_context
        if self._per_layer:
            # the scalar fields would report the (unapplied) global policy
            # values — under a per-layer policy the tuples are the truth
            rep["theta_x"] = rep["theta_h"] = None
            rep["theta_x_per_layer"] = self._theta_x_layers
            rep["theta_h_per_layer"] = self._theta_h_layers
        return rep


# The class served only GRU programs when it was born; the name survives
# as an alias now that it streams any compiled delta-RNN cell.
GruStreamEngine = DeltaStreamEngine
