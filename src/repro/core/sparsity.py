"""Temporal-sparsity metrics (EdgeDRNN Eq. 4) and op counting (Eq. 7 numerator).

``Gamma`` (Γ) is the fraction of zeros in delta vectors. The *effective*
sparsity weights Γ_Δx and Γ_Δh by the number of parameters each one gates:
a zero in Δx skips a column of the (3H × I)-ish input weight block, a zero in
Δh skips a column of the (3H × H) recurrent block.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def fraction_zeros(x: Array) -> Array:
    """Fraction of exactly-zero elements (a delta that fired is a.s. nonzero)."""
    return jnp.mean((x == 0).astype(jnp.float32))


def gamma_from_fired(fired: Array) -> Array:
    """Sparsity from a boolean 'fired' mask: Γ = mean(!fired)."""
    return 1.0 - jnp.mean(fired.astype(jnp.float32))


@dataclass(frozen=True)
class GruDims:
    """Dimensions of an L-layer delta-RNN stack (uniform hidden size).

    ``gates`` is the number of stacked gate rows per weight column: 3 for
    GRU (r, u, c — the default, so every existing positional construction
    keeps its meaning) and 4 for LSTM (i, f, g, o). The Eq. 4/7/8 machinery
    is linear in the gate count, so the same dims object prices both cell
    families; :func:`lstm_dims` is the 4-gate spelling.

    Cell families whose delta-gated projections are *not* a stack of gate
    rows over ``[I+H]`` columns (RWKV6 time-mix, RG-LRU) instead pass the
    gated weight volumes explicitly via ``x_weights`` / ``h_weights``: the
    total parameter count gated by the Δx-group and Δh-group delta streams
    across the whole stack. All Eq. 4/7/8 pricing is linear in those two
    volumes, so :func:`effective_sparsity`,
    :func:`repro.core.perf_model.stack_effective_macs` and
    :func:`~repro.core.perf_model.dram_traffic_bytes_per_timestep`
    generalize unchanged. When they are ``None`` the classic gate-row
    formulas apply.
    """

    input_size: int   # I
    hidden_size: int  # H
    num_layers: int   # L
    gates: int = 3    # gate rows per column: GRU 3, LSTM 4
    x_weights: int | None = None  # explicit Δx-gated weight volume (stack total)
    h_weights: int | None = None  # explicit Δh-gated weight volume (stack total)

    @property
    def x_weight_volume(self) -> int:
        """Parameters gated by the Δx delta streams (Eq. 7 input block).

        Defaults to the gate-row formula ``gHI + gH^2(L-1)``: input weights
        of layer 1 are (gH x I), input weights of layers 2..L are (gH x H).
        """
        if self.x_weights is not None:
            return self.x_weights
        i, h, l, g = (self.input_size, self.hidden_size, self.num_layers,
                      self.gates)
        return g * h * i + g * h * h * (l - 1)

    @property
    def h_weight_volume(self) -> int:
        """Parameters gated by the Δh delta streams (Eq. 7 recurrent block).

        Defaults to the gate-row formula ``gH^2 L``.
        """
        if self.h_weights is not None:
            return self.h_weights
        h, l, g = self.hidden_size, self.num_layers, self.gates
        return g * h * h * l

    @property
    def params_per_timestep_ops(self) -> int:
        """Total MAC*2 (multiply + add) op count per timestep (Eq. 7 'Op').

        Op = 2 * (x_weight_volume + h_weight_volume); for the classic
        gate-row cells that is 2 * (gHI + gH^2(L-1) + gH^2 L) with
        g = gates — the extra 1x (W_hc) fold the paper counts inside
        3H^2L for GRU.
        """
        return 2 * (self.x_weight_volume + self.h_weight_volume)

    @property
    def n_params(self) -> int:
        """Delta-gated weight parameter count (biases negligible, per the
        paper; for the LM cells, the dense non-delta side weights — LoRA
        mixers, output projections, scan state updates — are excluded:
        only the priced, skippable projection volume counts here)."""
        return self.x_weight_volume + self.h_weight_volume


# Gate rows per weight column, per cell family — the single source of
# truth the serving engine and dims helpers derive Eq. 4/7/8 pricing from.
# A new gate-row cell family must add its entry here (unknown cells raise
# loudly rather than silently pricing as a 3-gate GRU).
CELL_GATES = {"gru": 3, "lstm": 4}


def _rwkv6_volumes(i: int, h: int, l: int, layers=None) -> tuple[int, int]:
    """RWKV6 time-mix delta-gated projection volumes per stack.

    Δx-group: the mixed r/k/v streams each gate a [D, D] projection
    (W_r/W_k/W_v) → 3·D² per layer. Δh-group: the decay stream x_w gates
    the [D, DECAY_LORA] decay LoRA down-projection. Everything else
    (token-shift LoRA, gate/output projections, WKV scan) stays dense.
    """
    from repro.core.deltarwkv import DECAY_LORA
    return 3 * h * h * l, h * DECAY_LORA * l


def _rglru_volumes(i: int, h: int, l: int, layers=None) -> tuple[int, int]:
    """RG-LRU delta-gated projection volumes per stack.

    Δx-group: the block input gates w_in + w_in_gate, each [D, W]
    → 2·D·W per layer. Δh-group: the post-conv stream u gates the
    recurrence/input gate projections w_rg + w_ig, each [W, W] → 2·W²
    per layer. Causal conv, λ, and w_out stay dense.
    """
    return 2 * i * h * l, 2 * h * h * l


def _mamba2h_volumes(i: int, h: int, l: int, layers) -> tuple[int, int]:
    """Mamba-2 / attention hybrid delta-gated projection volumes (bf16).

    Δx-group: the mixer input gates ``in_proj`` (Mamba layers) or
    ``q, k, v`` (attention layers), and the MLP input gates ``W_in``.
    Δh-group: the mixer's inner stream gates ``out_proj`` / ``o_proj``,
    and the SwiGLU output gates ``W_out``. The shapes differ by layer
    kind, so they are read from the compiled stack (``layers``); the
    conv, the SSM update, the attention over the cache and the tied head
    stay dense.
    """
    from repro.core.deltamamba2h import proj_volumes
    if layers is None:
        raise ValueError("mamba2h volumes are read from the compiled "
                         "stack; pass layers=program.layers")
    return proj_volumes(layers)


# Cell families priced by explicit projection volumes rather than gate
# rows: maps cell -> fn(input_size, hidden_size, num_layers, layers) ->
# (x_weights, h_weights); ``layers`` is the compiled stack, for a family
# whose volumes its three sizes do not fix.
CELL_PROJ_VOLUMES = {"rwkv6": _rwkv6_volumes, "rglru": _rglru_volumes,
                     "mamba2h": _mamba2h_volumes}


def cell_dims(cell: str, input_size: int, hidden_size: int,
              num_layers: int, layers=None) -> GruDims:
    """Dims of an L-layer delta-RNN stack of the given cell family."""
    if cell in CELL_GATES:
        return GruDims(input_size, hidden_size, num_layers,
                       gates=CELL_GATES[cell])
    if cell in CELL_PROJ_VOLUMES:
        xw, hw = CELL_PROJ_VOLUMES[cell](input_size, hidden_size, num_layers,
                                         layers)
        return GruDims(input_size, hidden_size, num_layers, gates=1,
                       x_weights=xw, h_weights=hw)
    raise ValueError(f"unknown cell family {cell!r}; known gate "
                     f"counts: {CELL_GATES}, known projection-volume "
                     f"cells: {sorted(CELL_PROJ_VOLUMES)}")


def lstm_dims(input_size: int, hidden_size: int, num_layers: int) -> GruDims:
    """Dims of an L-layer (Delta)LSTM stack: the 4-gate weight volume."""
    return cell_dims("lstm", input_size, hidden_size, num_layers)


def effective_sparsity(dims: GruDims, gamma_dx: float, gamma_dh: float) -> float:
    """Eq. 4 Γ_eff: parameter-weighted average of input/hidden sparsity."""
    if dims.x_weights is None and dims.h_weights is None:
        # Classic gate-row path: column counts (the gate factor cancels).
        i, h, l = dims.input_size, dims.hidden_size, dims.num_layers
        num = (i + h * (l - 1)) * gamma_dx + h * l * gamma_dh
        den = i + h * (l - 1) + h * l
        return num / den
    xw, hw = dims.x_weight_volume, dims.h_weight_volume
    return (xw * gamma_dx + hw * gamma_dh) / (xw + hw)


def measure_layer_sparsity(delta_x: Array, delta_h: Array) -> tuple[Array, Array]:
    """Measured (Γ_Δx, Γ_Δh) for one layer over a [T, ...] delta sequence."""
    return fraction_zeros(delta_x), fraction_zeros(delta_h)


def stack_sparsity(per_layer_dx: Sequence[Array], per_layer_dh: Sequence[Array]) -> tuple[Array, Array]:
    """Aggregate per-layer Γ into stack-level Γ_Δx / Γ_Δh (Eq. 4 averages)."""
    gdx = jnp.mean(jnp.stack([jnp.asarray(g) for g in per_layer_dx]))
    gdh = jnp.mean(jnp.stack([jnp.asarray(g) for g in per_layer_dh]))
    return gdx, gdh
