"""Mesh-sharded stream fleet: many engine tiles behind ONE jitted tick.

A :class:`ShardedStreamFleet` partitions ``n_streams`` stream slots across
the ``"data"`` axis of a ``("data", "model")`` mesh (from
:func:`repro.dist.elastic.best_mesh`) and drives every shard's batched
delta-kernel tile with a single ``shard_map``-wrapped engine step per
fabric tick — weights replicated per device, the stream tile sharded, no
host round-trip per shard. On CPU this develops and tests against
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

The fleet reuses :class:`repro.serve.engine.DeltaStreamEngine` wholesale
rather than re-deriving the step: a *template* engine of the per-shard
tile width ``B = n_streams / n_shards`` supplies the raw (un-jitted)
step/reset closures, and ``shard_map`` traces them per device at the
local block shapes. Two shape families make this exact:

* per-stream carry vectors (``fired_x`` .. ``bad_state``, ``last_x``) are
  ``[N]``/``[N, I]`` fleet-wide and arrive on each device as the ``[B]``
  slice the template closure already expects;
* the engine's scalar lifetime aggregates (``agg_*``, ``theta_h``) are
  promoted to **per-shard ``[S]`` vectors** sharded one element per
  device — inside the shard the closure sees a ``[1]`` slice and its
  scalar arithmetic broadcasts through unchanged. This is what makes the
  fleet's per-shard accounting exact: each shard carries its own
  engine-lifetime aggregate, and fleet totals are host-side sums of the
  materialized ``[S]`` vectors.

Because each device runs the *same computation at the same tile width* as
a standalone ``n_streams=B`` engine, every shard's outputs are **bitwise
identical** to a single-device engine fed that shard's rows (the PR 6/7
fixed-width rule: companion values and slot position are bitwise-neutral
at fixed tile width). That invariant is what the elastic-rebalance path
leans on: after a shard dies, survivors keep their exact bits (their
local block is untouched), and the dead shard's in-flight streams replay
from frame 0 on a survivor and still match a clean reference run.

Elastic scale-down consumes :func:`repro.dist.elastic.scale_event` for
the remesh plan, drain-checkpoints the dying shard through PR 7's
``engine.checkpoint`` (the shard's rows are exported into a standalone
template-width engine first), rebuilds the mesh from the surviving
devices and re-lands the surviving rows — same per-device tile width, so
survivors continue bitwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.elastic import best_mesh, scale_event
from repro.dist.sharding import AxisRules
from repro.serve.engine import DeltaStreamEngine, StreamStats

__all__ = ["ShardedStreamFleet"]


def _nearest_valid_widths(n_streams: int, s: int) -> tuple[int, int]:
    lo = (n_streams // s) * s
    return max(lo, s), lo + s


class ShardedStreamFleet:
    """``n_streams`` stream slots sharded over the mesh's data axis.

    Args:
      program: a compiled :class:`~repro.core.program.DeltaProgram` with a
        classifier head (``fused`` / ``fused_q8`` of either cell; with a
        per-shard width > 1 each shard's kernel compacts on the union of
        its tile, so one weight pass per tick serves the shard's whole
        tile).
      task: the :class:`~repro.models.gru_rnn.GruTaskConfig`.
      n_streams: fleet-wide slot count; must divide evenly over the data
        axis (each shard runs a fixed-width tile — the bitwise parity and
        rebalance story both require equal widths).
      mesh: a ``("data", "model")`` mesh; defaults to
        ``best_mesh(model_parallel=1)`` over all local devices.
      thresholds / accel: forwarded to the template engine.

    Slot ids are global: slot ``sid`` lives on shard ``sid // B`` where
    ``B = streams_per_shard``. Sessions mirror the engine API
    (:meth:`open_stream` takes the target shard, :meth:`close_stream`
    returns the same accounting dict plus the shard id).
    """

    def __init__(self, program, task, *, n_streams: int, mesh=None,
                 thresholds=None, accel=None):
        self.mesh = mesh if mesh is not None else best_mesh(model_parallel=1)
        if "data" not in self.mesh.axis_names:
            raise ValueError(
                f"fleet mesh needs a 'data' axis, got {self.mesh.axis_names}")
        s = int(self.mesh.shape["data"])
        if n_streams < s or n_streams % s:
            lo, hi = _nearest_valid_widths(n_streams, s)
            raise ValueError(
                f"n_streams={n_streams} does not divide over the data axis "
                f"(size {s}): every shard runs a fixed-width tile. Nearest "
                f"valid widths: {lo} ({lo // s}/shard) or {hi} "
                f"({hi // s}/shard)")
        self.n_shards = s
        self.n_streams = n_streams
        self.streams_per_shard = n_streams // s
        kw = {}
        if thresholds is not None:
            kw["thresholds"] = thresholds
        if accel is not None:
            kw["accel"] = accel
        self._engine_kwargs = kw
        # the template: a standalone engine at the per-shard tile width.
        # Its raw closures are what shard_map re-traces per device; it is
        # also the clean same-width reference for parity checks and the
        # export vehicle for drain-checkpoints.
        self.template = DeltaStreamEngine(program, task,
                                          n_streams=self.streams_per_shard,
                                          **kw)
        if self.template.dynamic_target is not None:  # pragma: no cover
            raise ValueError("dynamic-theta is per-engine state; the fleet "
                             "does not steer per-shard controllers")
        self.program = self.template.program
        self.task = task
        self.backend = self.template.backend
        self.cell = self.template.cell
        self.dims = self.template.dims
        self._rules = AxisRules()
        self._build_sharded_fns()
        self.reset()

    # -- mesh plumbing ----------------------------------------------------

    def _spec(self) -> P:
        """Stream-tile spec from the logical-axis rules: the slot axis is
        "batch", which resolves to the mesh's data axis."""
        return self._rules.resolve("batch", mesh=self.mesh)

    def _build_sharded_fns(self):
        spec = self._spec()
        self._sharding = NamedSharding(self.mesh, spec)
        # tree-prefix specs: one P per argument/result subtree. Leaves are
        # [N, ...] (stream axis 0) or [S] (one aggregate per shard) — both
        # shard on their leading axis.
        self._fleet_step = jax.jit(jax.shard_map(
            self.template._one_step_fn, mesh=self.mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec),
            check_vma=False))
        xs_spec = self._rules.resolve(None, "batch", mesh=self.mesh)
        self._fleet_steps = jax.jit(jax.shard_map(
            self.template._steps_fn, mesh=self.mesh,
            in_specs=(spec, spec, xs_spec),
            out_specs=(xs_spec, spec, spec),
            check_vma=False))
        self._fleet_reset = jax.jit(jax.shard_map(
            self.template._reset_streams_fn, mesh=self.mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec),
            check_vma=False))

    def _place(self, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._sharding), tree)

    def reset(self):
        n, s = self.n_streams, self.n_shards
        zeros = jnp.zeros((n,), jnp.float32)
        carry = {k: zeros for k in DeltaStreamEngine._PER_STREAM_KEYS}
        # the template's scalar lifetime aggregates, promoted to one slot
        # per shard; built from the template carry so a new engine
        # aggregate key fails loudly here instead of silently diverging
        for k, v in self.template._carry.items():
            if k.startswith("agg_"):
                assert np.ndim(v) == 0, f"aggregate {k} is not scalar"
                carry[k] = jnp.zeros((s,), jnp.float32)
        carry["last_x"] = jnp.zeros((n, self.dims.input_size), jnp.float32)
        carry["theta_h"] = jnp.full((s,), self.template.thresholds.theta_h,
                                    jnp.float32)
        self.state = self._place(self.program.init_state(batch_shape=(n,)))
        self._carry = self._place(carry)
        self._n_ticks = 0
        self._slot_busy = [False] * n
        self._slot_opened_at = [0] * n

    # -- hot path ---------------------------------------------------------

    def step(self, x) -> jax.Array:
        """One fabric tick: ``x [n_streams, I]`` -> ``[n_streams, O]``.

        ONE device dispatch drives all shards (the shard_map body is the
        engine's jitted step at the local tile width). Host numpy frames
        are snapshotted with a synchronous copy — same aliasing hazard as
        ``DeltaStreamEngine.step``.
        """
        if isinstance(x, np.ndarray):
            x = np.array(x, np.float32)
        x = jnp.asarray(x, jnp.float32)
        if x.shape != (self.n_streams, self.dims.input_size):
            raise ValueError(
                f"fleet has n_streams={self.n_streams}; step needs "
                f"[{self.n_streams}, {self.dims.input_size}], got "
                f"{tuple(x.shape)}")
        out, self.state, self._carry = self._fleet_step(
            self.state, self._carry, x)
        self._n_ticks += 1
        return out

    def step_many(self, xs) -> jax.Array:
        """``xs [T, n_streams, I]`` -> ``[T, n_streams, O]`` in one
        device call (``lax.scan`` inside every shard)."""
        if isinstance(xs, np.ndarray):
            xs = np.array(xs, np.float32)
        xs = jnp.asarray(xs, jnp.float32)
        if xs.ndim != 3 or xs.shape[1:] != (self.n_streams,
                                            self.dims.input_size):
            raise ValueError(
                f"fleet step_many needs [T, {self.n_streams}, "
                f"{self.dims.input_size}], got {tuple(xs.shape)}")
        outs, self.state, self._carry = self._fleet_steps(
            self.state, self._carry, xs)
        self._n_ticks += xs.shape[0]
        return outs

    # -- sessions ---------------------------------------------------------

    def shard_of(self, sid: int) -> int:
        return sid // self.streams_per_shard

    def shard_slots(self, shard: int) -> range:
        b = self.streams_per_shard
        return range(shard * b, (shard + 1) * b)

    def free_streams(self, shard: int | None = None) -> list:
        """Free slot ids (optionally restricted to one shard)."""
        sids = (range(self.n_streams) if shard is None
                else self.shard_slots(shard))
        return [i for i in sids if not self._slot_busy[i]]

    def active_slots(self, shard: int | None = None) -> int:
        sids = (range(self.n_streams) if shard is None
                else self.shard_slots(shard))
        return sum(1 for i in sids if self._slot_busy[i])

    def open_stream(self, shard: int) -> int:
        """Claim a free slot ON the given shard (placement is the
        router's job — the fleet never load-balances by itself)."""
        if not (0 <= shard < self.n_shards):
            raise ValueError(f"shard {shard} out of range "
                             f"(n_shards={self.n_shards})")
        free = self.free_streams(shard)
        if not free:
            raise RuntimeError(
                f"shard {shard}: all {self.streams_per_shard} slots busy; "
                "queue the request (see serve.router.StreamRouter)")
        sid = free[0]
        mask = np.zeros((self.n_streams,), bool)
        mask[sid] = True
        self.state, self._carry = self._fleet_reset(
            self.state, self._carry, jnp.asarray(mask))
        self._slot_busy[sid] = True
        self._slot_opened_at[sid] = self._n_ticks
        return sid

    def close_stream(self, sid: int, host_carry=None) -> dict:
        """Release a session slot; returns that stream's accounting (the
        engine dict plus ``"shard"``). ``host_carry`` shares one
        ``jax.device_get(fleet._carry)`` across a tick's harvests."""
        if not (0 <= sid < self.n_streams) or not self._slot_busy[sid]:
            raise ValueError(f"stream {sid} is not open")
        host = host_carry if host_carry is not None \
            else jax.device_get(self._carry)
        steps = self._n_ticks - self._slot_opened_at[sid]
        fired_x = float(host["fired_x"][sid])
        fired_h = float(host["fired_h"][sid])
        lat = float(host["lat_s"][sid])
        wb = float(host["w_bytes"][sid])
        self._slot_busy[sid] = False
        return {
            "stream": sid,
            "shard": self.shard_of(sid),
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

    # -- accounting -------------------------------------------------------

    def shard_stats(self, shard: int, host_carry=None) -> StreamStats:
        """One shard's engine-lifetime aggregates (its slice of the [S]
        carry vectors) as the engine's own StreamStats type."""
        host = host_carry if host_carry is not None \
            else jax.device_get(self._carry)
        s = shard
        return StreamStats(
            steps=self._n_ticks,
            fired_x=float(host["agg_fired_x"][s]),
            fired_h=float(host["agg_fired_h"][s]),
            est_latency_s=float(host["agg_lat_s"][s]),
            w_bytes=float(host["agg_w_bytes"][s]),
            ufired_x=float(host["agg_ufired_x"][s]),
            ufired_h=float(host["agg_ufired_h"][s]),
            tile_est_latency_s=float(host["agg_tile_lat_s"][s]),
            tile_w_bytes=float(host["agg_tile_w_bytes"][s]),
            poison_steps=float(host["agg_poison_steps"][s]),
            bad_state_steps=float(host["agg_bad_state"][s]),
        )

    def report(self) -> dict:
        """Fleet + per-shard accounting in one carry materialization.

        Rate aggregates (firing means, Eq. 7 terms) average over shards
        (equal tile widths, so the mean is exact); event counts (poison /
        bad-state totals) SUM over shards — they are exact counters."""
        host = jax.device_get(self._carry)
        per_shard = [self.shard_stats(s, host_carry=host)
                     for s in range(self.n_shards)]
        ticks = max(self._n_ticks, 1)
        rep = {
            "n_shards": self.n_shards,
            "streams_per_shard": self.streams_per_shard,
            "n_streams": self.n_streams,
            "ticks": self._n_ticks,
            "mesh": dict(self.mesh.shape),
            "backend": self.backend,
            "cell": self.cell,
            "active_slots": self.active_slots(),
            "gamma_dx": float(
                1.0 - np.mean([st.fired_x for st in per_shard]) / ticks),
            "gamma_dh": float(
                1.0 - np.mean([st.fired_h for st in per_shard]) / ticks),
            "mean_est_latency_us": float(
                1e6 * np.mean([st.est_latency_s for st in per_shard])
                / ticks),
            "mean_weight_bytes_per_step": float(
                np.mean([st.w_bytes for st in per_shard]) / ticks),
            "poison_steps": float(
                np.sum([st.poison_steps for st in per_shard])),
            "bad_state_steps": float(
                np.sum([st.bad_state_steps for st in per_shard])),
            "per_shard": [{
                "shard": s,
                "gamma_dx": st.gamma_dx,
                "gamma_dh": st.gamma_dh,
                "union_gamma_dx": st.union_gamma_dx,
                "union_gamma_dh": st.union_gamma_dh,
                "tile_weight_bytes_per_step": st.tile_w_bytes / ticks,
                "poison_steps": st.poison_steps,
                "bad_state_steps": st.bad_state_steps,
            } for s, st in enumerate(per_shard)],
        }
        return rep

    # -- elastic scale-down ----------------------------------------------

    def reference_engine(self) -> DeltaStreamEngine:
        """A fresh standalone engine at the per-shard tile width — the
        clean same-width reference every fleet stream must match bitwise."""
        return DeltaStreamEngine(self.program, self.task,
                                 n_streams=self.streams_per_shard,
                                 **self._engine_kwargs)

    def export_shard_engine(self, shard: int) -> DeltaStreamEngine:
        """Materialize ONE shard as a standalone template-width engine.

        The engine carries the shard's exact rows (state, per-stream
        accounting, guard memory), its lifetime aggregates, and its slot
        bookkeeping — so ``engine.checkpoint`` on the export IS the
        drain-checkpoint of the dying shard, restorable by PR 7's
        ``DeltaStreamEngine.restore`` on any single device.
        """
        if not (0 <= shard < self.n_shards):
            raise ValueError(f"shard {shard} out of range")
        b = self.streams_per_shard
        rows = slice(shard * b, (shard + 1) * b)
        eng = self.reference_engine()
        host_state = jax.device_get(self.state)
        host_carry = jax.device_get(self._carry)
        eng.state = jax.tree_util.tree_map(lambda a: jnp.asarray(a[rows]),
                                           host_state)
        carry = {}
        for k, v in host_carry.items():
            if k in DeltaStreamEngine._PER_STREAM_KEYS or k == "last_x":
                carry[k] = jnp.asarray(v[rows])
            else:  # [S] per-shard aggregate (or theta_h) -> this shard's
                carry[k] = jnp.asarray(v[shard])
        eng._carry = carry
        eng._n_steps = self._n_ticks
        eng._slot_busy = list(self._slot_busy[rows])
        eng._slot_opened_at = list(self._slot_opened_at[rows])
        # seed the rollback shadows at the exported state (a restore-side
        # rollback rewinds at worst to the drain point, never further)
        eng._snap_state = eng.state
        eng._snap_carry = dict(eng._carry)
        eng._snap_steps = [self._n_ticks - o for o in eng._slot_opened_at]
        return eng

    def checkpoint_shard(self, shard: int, ckpt_dir: str,
                         step: int | None = None) -> str:
        """Drain-checkpoint one shard via PR 7's ``engine.checkpoint``."""
        eng = self.export_shard_engine(shard)
        return eng.checkpoint(ckpt_dir, step=step)

    def remove_shard(self, dead: int, ckpt_dir: str | None = None) -> dict:
        """Simulated device loss: drop shard ``dead``, keep survivors
        bitwise.

        Consumes :func:`repro.dist.elastic.scale_event` for the remesh
        plan, drain-checkpoints the dying shard first when ``ckpt_dir``
        is given, rebuilds the mesh from the SURVIVING device rows (the
        plan's new shape alone would re-admit the dead device), re-lands
        the surviving slot rows, and re-wraps the sharded step for the
        smaller mesh. Per-device tile width is unchanged, so surviving
        streams continue with exactly the bits they had.

        Returns the plan plus ``sid_map`` (old surviving slot id -> new),
        the checkpoint path (if drained), and the displaced slot ids whose
        streams must be replayed from frame 0 by the caller (the router).
        """
        if not (0 <= dead < self.n_shards):
            raise ValueError(f"shard {dead} out of range "
                             f"(n_shards={self.n_shards})")
        mp = int(self.mesh.shape.get("model", 1))
        # raises ValueError before any mutation when scaling to zero
        plan = scale_event(self.mesh, (self.n_shards - 1) * mp,
                           model_parallel=mp)
        ckpt_path = None
        if ckpt_dir is not None:
            ckpt_path = self.checkpoint_shard(dead, ckpt_dir)
        b = self.streams_per_shard
        dead_rows = np.arange(dead * b, (dead + 1) * b)
        displaced = [int(i) for i in dead_rows if self._slot_busy[i]]

        host_state = jax.device_get(self.state)
        host_carry = jax.device_get(self._carry)

        def drop_rows(a):
            return np.delete(np.asarray(a), dead_rows, axis=0)

        new_state = jax.tree_util.tree_map(drop_rows, host_state)
        new_carry = {}
        for k, v in host_carry.items():
            if k in DeltaStreamEngine._PER_STREAM_KEYS or k == "last_x":
                new_carry[k] = drop_rows(v)
            else:
                new_carry[k] = np.delete(np.asarray(v), dead, axis=0)

        surviving = np.delete(self.mesh.devices, dead, axis=0)
        self.mesh = Mesh(surviving, self.mesh.axis_names)
        assert dict(self.mesh.shape) == plan["new_shape"], \
            (dict(self.mesh.shape), plan["new_shape"])
        self.n_shards -= 1
        self.n_streams -= b
        self._build_sharded_fns()
        self.state = self._place(new_state)
        self._carry = self._place(new_carry)
        keep = [i for i in range(len(self._slot_busy))
                if i not in set(int(r) for r in dead_rows)]
        self._slot_busy = [self._slot_busy[i] for i in keep]
        self._slot_opened_at = [self._slot_opened_at[i] for i in keep]
        sid_map = {old: new for new, old in enumerate(keep)}
        return {
            "plan": plan,
            "dead_shard": dead,
            "checkpoint": ckpt_path,
            "displaced": displaced,
            "sid_map": sid_map,
        }
