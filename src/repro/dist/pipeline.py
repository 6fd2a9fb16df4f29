"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Each stage holds its own weights (``ws`` split over the stage axis); the
microbatch stream enters at stage 0 and flows one hop per tick through a
ring ppermute. With M microbatches and S stages the schedule runs
``M + S - 1`` ticks; outputs are collected on the last stage. Warmup/drain
ticks compute on zero buffers whose results are never written back — the
usual bubble, made explicit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def split_microbatches(batch, n_micro: int):
    """Reshape ``[B, ...]`` leaves to ``[n_micro, B // n_micro, ...]``."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n_micro, a.shape[0] // n_micro, *a.shape[1:]),
        batch)


def pipeline_forward(stage_fn, mesh: Mesh, axis: str, n_micro: int):
    """Build ``fwd(ws, xs)``: ``ws: [S, ...]`` per-stage weights, ``xs:
    [M, mb, ...]`` microbatches -> ``[M, mb, ...]`` outputs of the last
    stage. ``stage_fn(w, x)`` must be shape-preserving (stage interfaces
    match by construction in a layered model)."""
    n_stages = mesh.shape[axis]
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axis), P()), out_specs=P(axis))
    def fwd(ws, xs):
        idx = jax.lax.axis_index(axis)
        w = ws[0]                      # this stage's weights
        m = xs.shape[0]
        n_ticks = m + n_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t; later stages consume the hop
            inp = jnp.where(idx == 0, xs[jnp.clip(t, 0, m - 1)], buf)
            out = stage_fn(w, inp)
            nxt = jax.lax.ppermute(out, axis, perm) if perm else out
            # the last stage finishes microbatch t - (S-1) at tick t
            mb = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (mb >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, out, jnp.clip(mb, 0, m - 1), 0)
            outs = jnp.where(write, updated, outs)
            return (nxt, outs), None

        # the carry varies per stage from the first tick on
        carry0 = jax.lax.pcast((jnp.zeros_like(xs[0]), jnp.zeros_like(xs)),
                               (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
        return outs[None]              # [1, M, mb, ...] per stage

    def run(ws, xs):
        return fwd(ws, xs)[-1]         # last stage's collected outputs

    return run
