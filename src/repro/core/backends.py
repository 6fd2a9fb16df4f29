"""Execution-backend registry for delta-RNN cells.

EdgeDRNN serves every operating point (INT8 vs wide activations, static vs
dynamic thresholds, 1-2 layer stacks) from ONE weight memory + PE array
behind one command interface; this module is the software analogue. A
:class:`BackendSpec` captures everything a caller previously had to thread
by hand through ``backend=`` / ``layouts=`` / ``packs=`` /
``m_init=stack_m_init(...)`` knobs:

* how a layer's weights are packed for the kernel (``pack``),
* how one timestep executes (``step``),
* which delta-memory init convention its state uses (``m_init`` — the
  ``fused_q8`` code-domain accumulator starts at zero, everything else
  folds the biases in),
* the weight width it streams from HBM (``weight_bits`` — this is what the
  Eq. 6/7 performance model prices via
  :func:`repro.core.perf_model.spec_for_backend`),
* whether it can run user-supplied activation functions
  (``supports_custom_acts`` — the fused kernels hard-code the Fig. 7
  pipeline),
* whether its kernel compacts fired blocks on the union across its
  leading stream axis (``union_compaction``), so one weight pass serves a
  whole tile of streams.

The registry is keyed on ``(cell, name)`` so it is cell-agnostic: the
DeltaGRU backends register themselves when :mod:`repro.core.deltagru`
imports, and :mod:`repro.core.deltalstm` registers the same names under
``cell="lstm"``. Lookups lazily import the builtin cell modules, so
``get_backend("fused")`` works without the caller having touched
``deltagru`` first. A backend is registered once per cell: whether a call
runs one stream or a tile of them is the shape of its input, and the
serving engine prices a tile (one weight pass at the union firing) from
its own ``n_streams`` and the spec's ``union_compaction``.

:func:`repro.core.program.compile_delta_program` builds on this: it
resolves a spec once for any cell family, packs once, and returns a
program object whose states can only be constructed with the right
convention.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

# (cell, name) -> BackendSpec
_REGISTRY: dict = {}


@dataclass(frozen=True)
class BackendSpec:
    """One execution path for a delta-RNN cell.

    Attributes:
      name: registry key (``"dense" | "fused" | "fused_q8" | ...``).
      cell: which recurrent cell family the spec executes (``"gru"``,
        ``"lstm"``, ...). Specs of different cells never collide.
      pack: ``pack(layer_params, block) -> (layers, layouts, packs)`` —
        pre-packs a whole stack's weights once, outside any scan. May
        rewrite the parameter stack itself (the int8 exporter returns the
        dequantized fake-quant view so oracles and state init see the same
        grids); returns ``layouts`` (per-layer kernel layouts) and/or
        ``packs`` (per-layer packed matvec operand pairs), each possibly
        ``None``.
      step: one timestep. Signature (cell-specific, GRU shown)::

          step(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
               matvec, layout, packed, interpret) -> DeltaGruStepOut

      m_init: delta-memory init convention of the states this backend
        consumes (``"bias"`` folds biases into M up front; ``"zero"`` is
        the unscaled code-domain accumulator whose bias lives in the
        packed layout). Feeding a state built under the other convention
        silently corrupts results — the program API makes that
        unrepresentable.
      weight_bits: width of one streamed weight in bits; the Eq. 6/7
        model derives K (PE count) and DRAM traffic from it.
      supports_custom_acts: whether user ``sigmoid=`` / ``tanh=``
        overrides are honoured (kernel backends hard-code Fig. 7).
      union_compaction: whether the kernel compacts fired blocks on the
        **union** of fired columns across its leading stream axis, with
        each stream's own delta as the multiplicand. One weight pass then
        serves a whole ``[B, ...]`` tile of streams and bytes/stream falls
        sublinearly with B (see
        :func:`repro.core.perf_model.estimate_batched_tile`); without it
        each stream pays its own fetch.
    """

    name: str
    pack: Callable
    step: Callable
    cell: str = "gru"
    m_init: str = "bias"
    weight_bits: int = 32
    supports_custom_acts: bool = True
    union_compaction: bool = False


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Register a backend spec; duplicate ``(cell, name)`` keys are an error."""
    key = (spec.cell, spec.name)
    if key in _REGISTRY:
        raise ValueError(
            f"backend {spec.name!r} is already registered for cell "
            f"{spec.cell!r}; pick a new name or unregister the old spec")
    _REGISTRY[key] = spec
    return spec


def unregister_backend(name: str, cell: str = "gru") -> None:
    """Remove a spec (tests / experimental backends)."""
    _REGISTRY.pop((cell, name), None)


def _ensure_builtins() -> None:
    """Import the builtin cell modules so their specs self-register."""
    import repro.core.deltagru    # noqa: F401  (registers gru backends)
    import repro.core.deltalstm   # noqa: F401  (registers lstm backends)
    import repro.core.deltarwkv   # noqa: F401  (registers rwkv6 backends)
    import repro.core.deltarglru  # noqa: F401  (registers rglru backends)
    import repro.core.deltamamba2h  # noqa: F401  (registers mamba2h backends)


def get_backend(name: str, cell: str = "gru") -> BackendSpec:
    """Look up a registered spec; unknown names raise with the known set."""
    _ensure_builtins()
    spec = _REGISTRY.get((cell, name))
    if spec is None:
        known = list_backends(cell)
        raise ValueError(
            f"unknown {cell} backend {name!r}; registered backends: {known}")
    return spec


def list_backends(cell: str = "gru") -> tuple:
    """Registered backend names for a cell, in registration order: the
    one query every "which backends exist" list derives from."""
    _ensure_builtins()
    return tuple(n for (c, n) in _REGISTRY if c == cell)


def registered_backends(cell: str = "gru") -> tuple:
    """All registered specs for a cell, in registration order."""
    _ensure_builtins()
    return tuple(s for (c, _), s in _REGISTRY.items() if c == cell)
