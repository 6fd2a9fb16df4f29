"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return make_mesh((data, model), ("data", "model"))
