"""Expert-parallel MoE dispatch under ``shard_map``.

Under GSPMD alone, the global sort/scatter dispatch partitions into giant
u32 index planes (the SPMD partitioner replicates scatter indices across
the feature dim — tens of GB per device at 1M tokens). This module instead
makes the parallelism explicit:

* tokens are data-parallel (replicated across the ``model`` axis),
* each model-rank owns ``E / ep`` experts,
* every rank routes its local tokens, scatters *only the assignments that
  target its own experts* into a local capacity buffer (purely local,
  efficient scatter lowering), runs its experts, combines locally,
* a single ``psum`` over the model axis sums the per-rank partial outputs —
  the same wire pattern as a TP all-reduce, and the only collective.

Differentiable end-to-end (shard_map + local gather/scatter); composes with
the remat'd scan-over-layers. Falls back to the single-device sorted path
when no mesh/EP axis is available (unit tests, CPU smokes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_mesh, current_rules
from repro.models.common import ACTIVATIONS

Array = jax.Array


def _axis_extent(mesh, axes) -> int:
    if axes is None:
        return 1
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in names:
        n *= sizes.get(a, 1)
    return n


def _route_local(router_w, xt, top_k, dp_axes=None):
    logits = (xt.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    e = router_w.shape[-1]
    assigned = jnp.zeros((xt.shape[0], e), jnp.float32)
    assigned = assigned.at[jnp.arange(xt.shape[0])[:, None], gate_idx].set(1.0)
    me = jnp.mean(probs, 0)
    ce = jnp.mean(assigned, 0)
    if dp_axes is not None:
        # global router statistics (Switch aux is nonlinear in the batch)
        me = jax.lax.pmean(me, dp_axes)
        ce = jax.lax.pmean(ce, dp_axes)
    aux = e * jnp.sum(me * ce) / top_k
    return gate_vals, gate_idx, aux


def moe_apply_ep(params, x: Array, *, top_k: int,
                 capacity_factor: float = 1.25, activation: str = "silu"):
    """Expert-parallel MoE. Requires an active mesh whose ``experts`` axis
    divides the expert count. Returns (y, aux)."""
    from repro.dist.sharding import shard
    mesh = current_mesh()
    rules = current_rules()
    e_total = params["router"].shape[-1]          # routable experts
    e_phys = params["experts_gate"].shape[0]      # padded physical experts
    ep_axes = rules.resolve("experts", mesh=mesh)[0]
    dp_axes = rules.resolve("batch", mesh=mesh)[0]
    ep = _axis_extent(mesh, ep_axes)
    assert ep > 1 and e_phys % ep == 0
    e_local = e_phys // ep
    ep_name = ep_axes if isinstance(ep_axes, str) else ep_axes[0]
    act = ACTIVATIONS[activation]

    # re-shard EP x FSDP storage to pure EP for the dispatch (ZeRO-style
    # per-layer all-gather over the data axis)
    wg_full = shard(params["experts_gate"], "experts", None, None)
    wu_full = shard(params["experts_up"], "experts", None, None)
    wd_full = shard(params["experts_down"], "experts", None, None)

    def body(router_w, wg, wu, wd, xl):
        b, s, d = xl.shape
        t = b * s
        xt = xl.reshape(t, d)
        gate_vals, gate_idx, aux = _route_local(router_w, xt, top_k, dp_axes)

        tk = t * top_k
        cap = int(max(top_k, round(t * top_k * capacity_factor / e_total)))
        flat_e = gate_idx.reshape(tk)
        flat_t = jnp.repeat(jnp.arange(t), top_k)
        flat_g = gate_vals.reshape(tk)

        order = jnp.argsort(flat_e)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(flat_e, length=e_phys)
        offs = jnp.cumsum(counts) - counts
        rank = jnp.arange(tk) - offs[se]
        keep = rank < cap

        e0 = jax.lax.axis_index(ep_name) * e_local
        mine = keep & (se >= e0) & (se < e0 + e_local)
        dest = (se - e0) * cap + jnp.clip(rank, 0, cap - 1)

        buf = jnp.zeros((e_local * cap, d), xl.dtype)
        src = jnp.where(mine[:, None], xt[st], 0.0).astype(xl.dtype)
        buf = buf.at[jnp.where(mine, dest, e_local * cap)].set(src,
                                                               mode="drop")
        xe = buf.reshape(e_local, cap, d)
        h = act(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_local * cap, d)

        rows = jnp.where(mine[:, None], ye[dest], 0.0)
        contrib = rows * sg[:, None].astype(rows.dtype)
        y = jnp.zeros((t, d), xl.dtype)
        y = y.at[st].add(contrib.astype(xl.dtype))
        y = jax.lax.psum(y, ep_name)          # sum expert partials (TP-style)
        return y.reshape(b, s, d), aux[None]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(ep_name, None, None), P(ep_name, None, None),
                  P(ep_name, None, None), P(dp_axes, None, None)),
        out_specs=(P(dp_axes, None, None), P(dp_axes)),
        check_vma=False)
    y, aux = fn(params["router"], wg_full, wu_full, wd_full, x)
    return y, jnp.mean(aux)
