"""Elastic mesh construction: pick the best (data, model) factorization for
however many devices are currently healthy, and plan remesh events when the
fleet grows or shrinks mid-run."""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def _factorize(n_devices: int, model_parallel: int) -> tuple[int, int]:
    """Largest model-parallel degree <= requested that divides the fleet.

    Callers must validate ``n_devices >= 1`` first: a zero/negative count
    would "factorize" into a degenerate ``(n, 1)`` mesh shape here.
    """
    mp = max(1, min(model_parallel, n_devices))
    while n_devices % mp:
        mp -= 1
    return n_devices // mp, mp


def best_mesh(n_devices: int | None = None, model_parallel: int = 1):
    """A ``("data", "model")`` mesh over ``n_devices`` (default: all local).

    The requested model-parallel degree is clamped to a divisor of the
    device count, so an elastic scale-down never produces a ragged mesh.
    Scaling to zero devices is a fleet death, not a mesh: ``ValueError``.
    """
    avail = len(jax.devices())
    n = avail if n_devices is None else min(n_devices, avail)
    if n < 1:
        raise ValueError(
            f"best_mesh needs at least one device, got n_devices={n_devices} "
            f"({avail} available); a zero-device mesh is a fleet death, not "
            f"a resize")
    data, mp = _factorize(n, model_parallel)
    return make_mesh((data, mp), ("data", "model"))


def scale_event(old_mesh, new_n_devices: int, model_parallel: int = 1) -> dict:
    """Plan a remesh after an elastic resize; consumed by the restart policy
    (checkpoint -> rebuild mesh -> reshard-restore).

    Raises ``ValueError`` when asked to scale to fewer than one device —
    there is no ``(0, mp)`` mesh to reshard onto; that case must be handled
    as a full-fleet failure (checkpoint + halt), not a resize.
    """
    if new_n_devices < 1:
        raise ValueError(
            f"scale_event needs at least one surviving device, got "
            f"new_n_devices={new_n_devices}; scaling to zero is a full-fleet "
            f"failure (checkpoint + halt), not a resize")
    data, mp = _factorize(new_n_devices, model_parallel)
    old_shape = dict(old_mesh.shape)
    new_shape = {"data": data, "model": mp}
    return {
        "old_shape": old_shape,
        "new_shape": new_shape,
        "requires_resharding": old_shape != new_shape,
    }
