"""EdgeDRNN analytical performance model (paper Eqs. 5-8).

This module reproduces, exactly, the paper's estimation machinery:

* Eq. 5  — Delta Unit latency ``tau_DU``.
* Eq. 6  — bandwidth-matched PE count ``K = W_DRAM / W_weight`` and peak
           throughput ``nu_peak = 2 * f_pl * K``.
* Eq. 7  — mean effective throughput of a DeltaGRU stack given measured
           temporal sparsity (validated against Table II "Est." columns).
* Eq. 8  — memory-bounded peak throughput and sparsity-normalized batch-1
           throughput (validated against Table VI).

It also prices a batched stream tile (one weight pass at the union firing
of its streams). The chip's own peaks, which the benchmark's roofline
metrics read, are in ``bench/peaks.json``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace

from repro.core.sparsity import GruDims, effective_sparsity


@dataclass(frozen=True)
class AcceleratorSpec:
    """An EdgeDRNN-style bandwidth-matched accelerator."""

    f_pl_hz: float = 125e6       # programmable-logic clock
    dram_bits: int = 64          # DRAM interface width for weight fetch
    w_weight_bits: int = 8       # weight precision
    w_index_bits: int = 0        # nonzero-index overhead (0 for delta nets)
    n_delta_units: int = 1       # N in Eq. 5
    lookahead: int = 1           # d in Eq. 5

    @property
    def k_pes(self) -> int:
        """Eq. 6: number of PEs that exactly saturates the DRAM interface."""
        return self.dram_bits // self.w_weight_bits

    @property
    def peak_ops(self) -> float:
        """Eq. 6: theoretical peak throughput in Op/s (1 MAC = 2 Op)."""
        return 2.0 * self.f_pl_hz * self.k_pes

    @property
    def mem_bounded_peak_ops(self) -> float:
        """Eq. 8: memory-bounded peak throughput including index overhead."""
        eff_lanes = self.dram_bits / (self.w_weight_bits + self.w_index_bits)
        return 2.0 * self.f_pl_hz * eff_lanes


EDGEDRNN = AcceleratorSpec()

def backend_weight_bits(cell: str = "gru") -> dict:
    """Bytes-per-op term of the Eq. 6/7 model, per registered backend.

    A bandwidth-matched accelerator retires ``K = W_DRAM / W_weight`` MACs
    per cycle, so the *streamed weight width* of the executing backend sets
    both the latency and the DRAM traffic. The single source of truth is
    the backend registry (:mod:`repro.core.backends`): the fp32 backends
    stream 4 bytes per fetched weight (the training-time fiction);
    ``fused_q8`` streams the paper's INT8.
    """
    from repro.core.backends import registered_backends
    return {s.name: s.weight_bits for s in registered_backends(cell)}


def spec_for_backend(spec: AcceleratorSpec, backend: str,
                     cell: str = "gru") -> AcceleratorSpec:
    """Derive the spec whose weight-stream width matches a DeltaGRU backend.

    Dispatches through the backend registry (unknown names raise, the same
    rejection every other registry consumer gets). With the default
    EDGEDRNN spec, ``fused_q8`` keeps the paper's operating point (8-bit
    weights -> K=8 PEs on the 64-bit bus) while the fp32 backends drop to
    K=2 — the 4x bytes-per-op penalty of streaming unquantized weights
    over the same interface.
    """
    from repro.core.backends import get_backend
    return replace(spec, w_weight_bits=get_backend(backend, cell).weight_bits)


def delta_unit_latency_cycles(vec_len: int, gamma: float,
                              spec: AcceleratorSpec = EDGEDRNN) -> int:
    """Eq. 5: cycles for the Delta Unit(s) to encode a vector of ``vec_len``."""
    n, d = spec.n_delta_units, spec.lookahead
    return max(math.ceil(vec_len / (n * d)), math.ceil(vec_len * (1.0 - gamma)))


@dataclass(frozen=True)
class StackEstimate:
    ops_per_timestep: int
    effective_macs: float
    latency_s: float
    throughput_ops: float
    gamma_eff: float


def stack_effective_macs(dims: GruDims, gamma_dx, gamma_dh):
    """Eq. 7 numerator: MACs that survive delta skipping.

    Pure arithmetic (no branching), so it is traced-safe — the streaming
    engine accumulates it on-device inside its jitted step. The weight
    volume each delta group gates comes from the dims object: the gate-row
    formula (3 for GRU, 4 for LSTM) or the explicit projection volumes the
    LM cells (rwkv6, rglru) declare — the same law either way.
    """
    in_block = dims.x_weight_volume    # gated by delta-x
    rec_block = dims.h_weight_volume   # gated by delta-h
    return in_block * (1.0 - gamma_dx) + rec_block * (1.0 - gamma_dh)


def stack_latency_s(dims: GruDims, gamma_dx, gamma_dh,
                    spec: AcceleratorSpec = EDGEDRNN):
    """Eq. 7 latency: surviving MACs at ``K`` MACs/cycle. Traced-safe."""
    return stack_effective_macs(dims, gamma_dx, gamma_dh) / (
        spec.k_pes * spec.f_pl_hz)


def estimate_stack(dims: GruDims, gamma_dx: float, gamma_dh: float,
                   spec: AcceleratorSpec = EDGEDRNN) -> StackEstimate:
    """Eq. 7: estimated latency / mean effective throughput of a DeltaGRU stack.

    The MxV work that survives delta skipping is
    ``(3HI + 3H^2(L-1)) * (1-Gamma_dx) + 3H^2*L * (1-Gamma_dh)`` MACs; with
    ``K`` MACs retired per cycle the latency is ``macs / (K * f_pl)``.
    ``tau_a`` (activation pipeline) is amortized/overlapped and dropped, as in
    the paper's approximation. A fully-silent stack (both Γ = 1) has zero
    latency and is reported as infinite throughput rather than crashing.
    """
    macs = stack_effective_macs(dims, gamma_dx, gamma_dh)
    latency = stack_latency_s(dims, gamma_dx, gamma_dh, spec)
    ops = dims.params_per_timestep_ops
    return StackEstimate(
        ops_per_timestep=ops,
        effective_macs=macs,
        latency_s=latency,
        throughput_ops=ops / latency if latency > 0 else float("inf"),
        gamma_eff=effective_sparsity(dims, gamma_dx, gamma_dh),
    )


def normalized_batch1_throughput(gamma_eff: float,
                                 w_index_bits: int,
                                 spec: AcceleratorSpec = EDGEDRNN) -> float:
    """Eq. 8 upper bound used in Table VI.

    All accelerators are normalized to EdgeDRNN's operating point
    (f=125 MHz, 64-bit DRAM weight bus, INT8 weights) but keep their native
    index overhead; temporal/weight sparsity multiplies the memory-bounded
    peak by ``1/(1-Gamma_eff)``.
    """
    norm = AcceleratorSpec(f_pl_hz=spec.f_pl_hz, dram_bits=spec.dram_bits,
                           w_weight_bits=spec.w_weight_bits,
                           w_index_bits=w_index_bits)
    return norm.mem_bounded_peak_ops / (1.0 - gamma_eff)


def dram_traffic_bytes_per_timestep(dims: GruDims, gamma_dx: float,
                                    gamma_dh: float,
                                    w_weight_bits: int = 8) -> float:
    """Weight bytes fetched per timestep after delta column skipping
    (``dims.gates`` rows per fetched column for the gate-row cells;
    explicit ``x_weights``/``h_weights`` projection volumes for the LM
    cells)."""
    surviving = (dims.x_weight_volume * (1.0 - gamma_dx)
                 + dims.h_weight_volume * (1.0 - gamma_dh))
    return surviving * w_weight_bits / 8.0


# ---------------------------------------------------------------------------
# Batched stream tiles: one weight pass serves B streams (union firing).
# ---------------------------------------------------------------------------

def union_sparsity(gamma, batch: int):
    """Temporal sparsity surviving a union over ``batch`` independent
    streams.

    A weight column is skipped by a batched tile kernel only when EVERY
    stream in the tile kept it silent; with independent streams each
    silent with probability ``gamma``, that is ``gamma ** batch`` — the
    union firing fraction ``1 - gamma**B`` grows with B, which is exactly
    why bytes/stream falls *sublinearly* rather than as ``1/B``. Pure
    arithmetic (traced-safe); feed MEASURED union gammas instead when you
    have them (streams are rarely perfectly independent).
    """
    return gamma ** batch


def estimate_batched_tile(dims: GruDims, gamma_dx: float, gamma_dh: float,
                          batch: int,
                          spec: AcceleratorSpec = EDGEDRNN) -> dict:
    """Analytic batched bytes/op pricing from per-stream gammas.

    Independent-streams model: per-stream sparsity ``gamma`` unions down
    to ``gamma**B`` across the tile (:func:`union_sparsity`); one weight
    pass at the union firing then serves every stream, so

    * tile latency  = Eq. 7 latency at the union gammas (the weight
      stream is the bottleneck and is shared),
    * tile bytes    = Eq. 7 traffic at the union gammas: the fused
      kernels (``BackendSpec.union_compaction``) fetch the union of fired
      columns across the tile once,
    * bytes/stream  = tile bytes / B  (sublinear in B: the numerator
      grows with the union firing),
    * throughput    = B steps retired per tile pass.
    """
    gx_u = union_sparsity(gamma_dx, batch)
    gh_u = union_sparsity(gamma_dh, batch)
    lat = stack_latency_s(dims, gx_u, gh_u, spec)
    tile_bytes = dram_traffic_bytes_per_timestep(
        dims, gx_u, gh_u, w_weight_bits=spec.w_weight_bits)
    ops = dims.params_per_timestep_ops * batch
    return {
        "batch": batch,
        "gamma_dx_union": gx_u,
        "gamma_dh_union": gh_u,
        "tile_latency_s": lat,
        "tile_weight_bytes": tile_bytes,
        "weight_bytes_per_stream": tile_bytes / batch,
        "throughput_ops": ops / lat if lat > 0 else float("inf"),
    }
