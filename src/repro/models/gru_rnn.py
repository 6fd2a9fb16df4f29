"""The paper's own networks: multi-layer (Delta)GRU stacks with a CTC
classifier head (TIDIGITS) or a regression head (SensorsGas), with the QAT
policy wired through (paper Sec. IV-A).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.deltagru import (deltagru_sequence, gru_sequence,
                                 init_gru_stack)
from repro.models.common import dense_init
from repro.quant.qat import FP32, QatPolicy

Array = jax.Array


@dataclass(frozen=True)
class GruTaskConfig:
    input_size: int
    hidden_size: int
    num_layers: int
    output_size: int          # CTC classes (incl. blank) or regression dims
    task: str = "ctc"         # ctc | regression
    theta_x: float = 0.0
    theta_h: float = 0.0


# Paper network sizes (Table II) on TIDIGITS features (40-d log filter bank).
PAPER_NETWORKS = {
    "1L-256H": GruTaskConfig(40, 256, 1, 12),
    "2L-256H": GruTaskConfig(40, 256, 2, 12),
    "1L-512H": GruTaskConfig(40, 512, 1, 12),
    "2L-512H": GruTaskConfig(40, 512, 2, 12),
    "1L-768H": GruTaskConfig(40, 768, 1, 12),
    "2L-768H": GruTaskConfig(40, 768, 2, 12),
    # SensorsGas regression (14 sensors -> 1 concentration)
    "2L-256H-GAS": GruTaskConfig(14, 256, 2, 1, task="regression"),
    # AMPRO prosthetic control network (Fig. 15)
    "2L-128H-AMPRO": GruTaskConfig(8, 128, 2, 4, task="regression"),
}


def init_gru_model(key: Array, cfg: GruTaskConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "gru": init_gru_stack(k1, cfg.input_size, cfg.hidden_size,
                              cfg.num_layers, dtype),
        "head": dense_init(k2, cfg.hidden_size, cfg.output_size, dtype),
        "head_b": jnp.zeros((cfg.output_size,), dtype),
    }


def init_lstm_model(key: Array, cfg: GruTaskConfig, dtype=jnp.float32):
    """The LSTM twin of :func:`init_gru_model` (the Table VII workload
    family): a DeltaLSTM stack under the same task config + head shapes.
    Compile with ``compile_delta_program(model, cell="lstm", ...)`` and
    serve through ``DeltaStreamEngine`` exactly like the GRU models."""
    from repro.core.deltalstm import init_lstm_stack
    k1, k2 = jax.random.split(key)
    return {
        "lstm": init_lstm_stack(k1, cfg.input_size, cfg.hidden_size,
                                cfg.num_layers, dtype),
        "head": dense_init(k2, cfg.hidden_size, cfg.output_size, dtype),
        "head_b": jnp.zeros((cfg.output_size,), dtype),
    }


def gru_model_forward(params, cfg: GruTaskConfig, xs: Array, *,
                      use_delta: bool = True, qat: QatPolicy = FP32,
                      collect_sparsity: bool = False,
                      backend: str | None = None,
                      layouts=None,
                      program=None):
    """``xs: [T, B, I]`` -> (outputs ``[T, B, O]``, sparsity stats dict).

    ``use_delta=False`` runs the plain-GRU oracle (the paper's pretrain /
    cuDNN-equivalent baseline).

    ``program=`` (a :func:`repro.core.program.compile_deltagru` result) is
    the compiled inference spelling: the program's pre-packed weights and
    backend run the delta path, and its head (or ``params``'s, when the
    program was compiled from a bare stack) produces the outputs. The
    legacy ``backend=`` / ``layouts=`` kwargs remain for ad-hoc /
    training-time calls (``dense | fused | fused_q8 | fused_q4``, see
    :mod:`repro.core.deltagru`); the fused kernels hard-code the Fig. 7
    activation pipeline, so QAT activation policies require ``dense``.

    QAT (training-time fake quant) and ``fused_q8`` (inference-time real
    int8) are two sides of the same recipe: train with ``qat=EDGEDRNN_QAT``
    on ``dense``, then export with
    :func:`repro.quant.export.quantize_delta_model` (cell-agnostic;
    ``quantize_gru_model`` is the GRU spelling) and run the returned
    program."""
    if program is not None:
        if backend is not None or layouts is not None:
            raise ValueError(
                "backend=/layouts= conflict with program= — the compiled "
                f"program already fixes both (its backend: "
                f"{program.backend!r}); drop the legacy kwargs")
        if qat.enabled:
            raise ValueError(
                "program= holds weights packed at compile time; QAT fake "
                "quant would be silently ignored — quantize at compile "
                "(backend='fused_q8') or run the legacy dense path")
        if not use_delta:
            raise ValueError("program= compiles the DeltaGRU path; use the "
                             "legacy kwargs for the plain-GRU oracle")
        ys, _, stats = program.sequence(xs, cfg.theta_x, cfg.theta_h,
                                        collect_sparsity=collect_sparsity)
        if program.head is not None:
            return program.apply_head(ys), stats
        return ys @ params["head"] + params["head_b"], stats
    if qat.enabled:
        gru_params = [p._replace(w_x=qat.quantize_params(p.w_x),
                                 w_h=qat.quantize_params(p.w_h),
                                 b=qat.quantize_params(p.b))
                      for p in params["gru"]]
    else:
        gru_params = params["gru"]
    sigmoid, tanh = qat.act_fns()
    stats = {}
    if use_delta:
        ys, _, stats = deltagru_sequence(
            gru_params, xs, cfg.theta_x, cfg.theta_h,
            collect_sparsity=collect_sparsity, backend=backend or "dense",
            layouts=layouts, sigmoid=sigmoid, tanh=tanh)
    else:
        ys = gru_sequence(gru_params, xs, sigmoid=sigmoid, tanh=tanh)
    out = ys @ params["head"] + params["head_b"]
    return out, stats
