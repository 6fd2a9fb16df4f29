"""Logical-axis sharding rules (GSPMD layer).

Model code annotates activations with *logical* axis names
(``shard(x, "batch", "seq", "embed")``); an :class:`AxisRules` table maps
each logical name to zero or more *mesh* axis names. Mesh axes that the
active mesh does not have are silently dropped, so the same model code runs
on a single host mesh ``("data",)``, the debug mesh ``("data", "model")``,
and the production pods ``("pod", "data", "model")`` unchanged.

The active (mesh, rules) pair is installed with :func:`use_mesh`; with no
context installed every helper is a no-op, which is what keeps the
single-device DeltaGRU paths free of sharding machinery.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

# Logical axis -> mesh axes. "batch" spreads over both pod and data axes
# (pure DP across pods); tensor-ish axes go to the model axis.
_DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": (),
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "kv_lora": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
}


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``, the only kind this repo builds.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which indexing a
    sharded result (``y[sid]``) or a gather inside ``shard_map`` must state
    its sharding; with ``Auto`` axes the partitioner places them.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def _mesh_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _axis_extent(mesh: Mesh, entry) -> int:
    """Total device extent of one PartitionSpec entry (str | tuple | None)."""
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else tuple(entry)
    sizes = _mesh_sizes(mesh)
    ext = 1
    for n in names:
        ext *= sizes.get(n, 1)
    return ext


def _collapse(names: tuple):
    """() -> None, (a,) -> a, (a, b) -> (a, b) — PartitionSpec entry form."""
    if not names:
        return None
    return names[0] if len(names) == 1 else names


@dataclass(frozen=True)
class AxisRules:
    """Logical-axis -> mesh-axis mapping plus the parameter-FSDP knobs.

    ``embed_fsdp`` is the data-ish axis group used to FSDP-shard the
    *non-model* dimension of 2-D parameters (ZeRO-3 style); ``None`` keeps
    parameters data-replicated (ZeRO-1). ``experts_fsdp`` is the same knob
    for the per-expert weight stacks.
    """

    rules: dict = field(default_factory=lambda: dict(_DEFAULT_RULES))
    embed_fsdp: tuple | None = ("data",)
    experts_fsdp: tuple | None = ("data",)

    def with_overrides(self, **kw) -> "AxisRules":
        """Return a copy with attribute or per-logical-axis overrides."""
        attrs = {}
        new_rules = dict(self.rules)
        for k, v in kw.items():
            if k in ("embed_fsdp", "experts_fsdp"):
                attrs[k] = v
            else:
                new_rules[k] = tuple(v) if v else ()
        return replace(self, rules=new_rules, **attrs)

    def resolve(self, *axes, mesh: Mesh) -> P:
        """Map logical axis names (or ``None``) to a PartitionSpec, keeping
        only mesh axes that exist on ``mesh``."""
        present = set(mesh.axis_names)
        entries = []
        for a in axes:
            if a is None:
                entries.append(None)
                continue
            names = tuple(n for n in self.rules.get(a, ()) if n in present)
            entries.append(_collapse(names))
        return P(*entries)

    def _present(self, names, mesh: Mesh) -> tuple:
        return tuple(n for n in (names or ()) if n in set(mesh.axis_names))


def enforce_divisibility(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec entries whose mesh extent does not divide the dim size.

    GSPMD would otherwise pad-and-halo; for parameter/batch layouts we want
    the clean fallback to replication instead.
    """
    out = []
    for d, e in enumerate(spec):
        if e is not None and (d >= len(shape)
                              or shape[d] % _axis_extent(mesh, e) != 0):
            e = None
        out.append(e)
    return P(*out)


# ---------------------------------------------------------------------------
# Active-mesh context
# ---------------------------------------------------------------------------

_CONTEXT: list = []  # stack of (mesh, rules)


def current_mesh() -> Mesh | None:
    return _CONTEXT[-1][0] if _CONTEXT else None


def current_rules() -> AxisRules:
    return _CONTEXT[-1][1] if _CONTEXT else AxisRules()


class use_mesh:
    """``with use_mesh(mesh, rules):`` installs the sharding context so that
    :func:`shard` constraints are live while model code traces."""

    def __init__(self, mesh: Mesh, rules: AxisRules | None = None):
        self._pair = (mesh, rules or AxisRules())

    def __enter__(self):
        _CONTEXT.append(self._pair)
        return self._pair[0]

    def __exit__(self, *exc):
        _CONTEXT.pop()
        return False


def shard(x: Array, *axes) -> Array:
    """Constrain ``x`` to the resolved logical sharding (no-op without a
    mesh; entries that don't divide fall back to replicated)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = current_rules().resolve(*axes, mesh=mesh)
    spec = enforce_divisibility(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter spec inference
# ---------------------------------------------------------------------------

def infer_param_specs(params, *, rules: AxisRules | None = None,
                      mesh: Mesh) -> object:
    """Path+shape rule for parameter layouts.

    2-D weights put their larger dimension on the model axes and the other
    on the FSDP (data) axes — the standard megatron-x-ZeRO layout; 1-D
    params replicate; 3-D per-expert stacks shard experts on the expert
    axes and their embed dim on ``experts_fsdp``. Every proposed spec then
    passes the divisibility filter, so odd shapes degrade to replication
    instead of erroring.
    """
    rules = rules or AxisRules()
    model_ax = _collapse(rules._present(rules.rules.get("heads"), mesh))
    data_ax = _collapse(rules._present(rules.embed_fsdp, mesh))
    exp_ax = _collapse(rules._present(rules.rules.get("experts"), mesh))
    exp_fsdp = _collapse(rules._present(rules.experts_fsdp, mesh))

    def spec_for(path, x):
        shape = x.shape
        if x.ndim <= 1:
            return P(*([None] * x.ndim))
        name = ""
        if path:
            last = path[-1]
            name = str(getattr(last, "key", getattr(last, "name", last)))
        if x.ndim == 3 and "expert" in name:
            s = P(exp_ax, exp_fsdp, None)
        elif x.ndim >= 3:
            s = P(*([None] * (x.ndim - 2) + [data_ax, model_ax]))
        elif shape[-1] >= shape[-2]:
            s = P(data_ax, model_ax)
        else:
            s = P(model_ax, data_ax)
        return enforce_divisibility(s, shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params)
