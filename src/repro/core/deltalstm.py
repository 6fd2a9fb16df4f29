"""DeltaLSTM — the delta-network algorithm applied to LSTM cells.

The paper benchmarks an LSTM on NCS2 (Table VII) and the delta method
originates from Neil et al. 2017 where it was applied to LSTM-family cells;
we provide it so the framework covers both gated-RNN families. Gate order:
``i`` (input), ``f`` (forget), ``g`` (candidate), ``o`` (output);
``W_x: [4H, I]``, ``W_h: [4H, H]``.

Delta memories: ``M = W_x dx + W_h dh + M_prev`` per gate pre-activation —
the same bookkeeping as DeltaGRU but with four gates and a cell state ``c``.

Execution backends go through the same registry as DeltaGRU
(:mod:`repro.core.backends`, ``cell="lstm"``) and carry full GRU parity:

* ``"dense"`` — plain XLA matmuls; the oracle (custom/QAT activations OK).
* ``"fused"`` — :mod:`repro.kernels.deltalstm_seq`: ONE pallas_call per
  layer step over the concatenated ``[4H, I+H]`` Fig. 6-style layout with
  a single fired-block compaction and the in-kernel i/f/g/o + cell-state
  pipeline; sequences run under ``lax.scan`` with zero per-step Python
  dispatch.
* ``"fused_q8"`` — the same fused pipeline with the paper's fixed-point
  semantics, via the cell-agnostic int8 core
  (:mod:`repro.kernels.delta_q8`, G=4): int8 packed ``[4, Hp, Ip+Hk]``
  weights streamed from HBM (4x fewer bytes per fired column), Q8.8
  activations, unscaled code-domain delta memories (``m_init="zero"`` —
  biases are applied at the activation stage), Q8.8 -> Q1.4 LUT
  i/f/g/o gates, and the cell state ``c`` on the saturating Q8.8
  accumulator grid (clips at the rails, never wraps). Quantize a trained
  stack with :func:`repro.quant.export.quantize_delta_stack`
  (``cell="lstm"``) or just compile:
  ``compile_delta_program(params, cell="lstm", backend="fused_q8")``.
* ``"fused_q4"`` — the int4 twin of ``fused_q8`` (nibble-packed weights).

As for DeltaGRU, every fused backend takes a ``[B, ...]`` tile of streams
as it takes one: one weight pass per layer step, compacted on the union
of fired columns across the tile (``BackendSpec.union_compaction``).

Both compile into :func:`repro.core.program.compile_delta_program`
programs (``cell="lstm"``) and stream through
:class:`repro.serve.engine.DeltaStreamEngine` sessions exactly like their
GRU counterparts.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.backends import BackendSpec, get_backend, register_backend
from repro.core.delta import DeltaState, delta_encode, init_delta_state
from repro.core.thresholds import layer_theta

Array = jax.Array


def _default_acts(sigmoid: Callable, tanh: Callable) -> bool:
    return sigmoid is jax.nn.sigmoid and tanh is jnp.tanh


class LstmLayerParams(NamedTuple):
    w_x: Array  # [4H, I]
    w_h: Array  # [4H, H]
    b: Array    # [4H]

    @property
    def hidden_size(self) -> int:
        return self.w_h.shape[-1]

    @property
    def input_size(self) -> int:
        return self.w_x.shape[-1]


def init_lstm_layer(key: Array, input_size: int, hidden_size: int,
                    dtype=jnp.float32, forget_bias: float = 1.0) -> LstmLayerParams:
    kx, kh = jax.random.split(key)
    sx = (6.0 / (input_size + 4 * hidden_size)) ** 0.5
    sh = (6.0 / (hidden_size + 4 * hidden_size)) ** 0.5
    b = jnp.zeros((4 * hidden_size,), dtype)
    b = b.at[hidden_size:2 * hidden_size].set(forget_bias)
    return LstmLayerParams(
        w_x=jax.random.uniform(kx, (4 * hidden_size, input_size), dtype, -sx, sx),
        w_h=jax.random.uniform(kh, (4 * hidden_size, hidden_size), dtype, -sh, sh),
        b=b,
    )


def init_lstm_stack(key: Array, input_size: int, hidden_size: int,
                    num_layers: int, dtype=jnp.float32) -> list[LstmLayerParams]:
    keys = jax.random.split(key, num_layers)
    return [init_lstm_layer(k, input_size if l == 0 else hidden_size,
                            hidden_size, dtype)
            for l, k in enumerate(keys)]


def lstm_step(params: LstmLayerParams, carry, x: Array,
              sigmoid: Callable = jax.nn.sigmoid, tanh: Callable = jnp.tanh):
    """Reference LSTM cell. ``carry = (h, c)``."""
    h_prev, c_prev = carry
    z = x @ params.w_x.T + h_prev @ params.w_h.T + params.b
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    i, f, o = sigmoid(zi), sigmoid(zf), sigmoid(zo)
    g = tanh(zg)
    c = f * c_prev + i * g
    h = o * tanh(c)
    return (h, c)


class DeltaLstmLayerState(NamedTuple):
    h: Array
    c: Array
    x_mem: DeltaState
    h_mem: DeltaState
    m: Array  # [..., 4H]


def init_deltalstm_state(params: LstmLayerParams, batch_shape=(),
                         dtype=None, m_init: str = "bias") -> DeltaLstmLayerState:
    """``m_init="bias"`` folds the biases into the delta memories up front
    (the paper's "bias as first weight column" trick, same as DeltaGRU);
    ``"zero"`` is the ``fused_q8`` convention — ``M`` is the unscaled
    code-domain accumulator and the quantized bias lives in the packed
    layout, consumed at the activation stage instead."""
    dtype = dtype or params.w_x.dtype
    h_dim, i_dim = params.hidden_size, params.input_size
    if m_init == "zero":
        m0 = jnp.zeros((*batch_shape, 4 * h_dim), dtype)
    else:
        m0 = jnp.broadcast_to(params.b.astype(dtype),
                              (*batch_shape, 4 * h_dim))
    z = jnp.zeros((*batch_shape, h_dim), dtype)
    return DeltaLstmLayerState(
        h=z, c=z, x_mem=init_delta_state((*batch_shape, i_dim), dtype),
        h_mem=init_delta_state((*batch_shape, h_dim), dtype), m=m0)


class DeltaLstmStepOut(NamedTuple):
    h: Array
    state: DeltaLstmLayerState
    delta_x: Array   # the (sparse) encoded input delta actually used
    delta_h: Array   # the (sparse) encoded hidden delta actually used


# -- per-backend step implementations (registered BackendSpec.step fns) -----

def _step_dense(params: LstmLayerParams, state: DeltaLstmLayerState,
                x: Array, theta_x, theta_h, *,
                sigmoid: Callable = jax.nn.sigmoid,
                tanh: Callable = jnp.tanh,
                matvec: Callable | None = None,
                layout=None, packed=None, interpret=None) -> DeltaLstmStepOut:
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    mv = matvec if matvec is not None else (lambda w, v: v @ w.T)
    m = state.m + mv(params.w_x, dx_out.delta) + mv(params.w_h, dh_out.delta)
    zi, zf, zg, zo = jnp.split(m, 4, axis=-1)
    i, f, o = sigmoid(zi), sigmoid(zf), sigmoid(zo)
    g = tanh(zg)
    c = f * state.c + i * g
    h = o * tanh(c)
    new_state = DeltaLstmLayerState(h=h, c=c, x_mem=dx_out.state,
                                    h_mem=dh_out.state, m=m)
    return DeltaLstmStepOut(h=h, state=new_state, delta_x=dx_out.delta,
                            delta_h=dh_out.delta)


def _step_fused(params: LstmLayerParams, state: DeltaLstmLayerState,
                x: Array, theta_x, theta_h, *, sigmoid, tanh, matvec,
                layout=None, packed=None,
                interpret=None) -> DeltaLstmStepOut:
    """i/f/g/o + cell update via the single-pallas_call fused kernel.

    Mode resolution follows :mod:`repro.kernels.ops`: compiled Pallas on
    TPU; on other backends the pure-jnp oracle of the same fused math
    (interpret-mode emulation is a correctness tool, not a perf path —
    request it explicitly with ``interpret=True``).
    """
    from repro.kernels import deltalstm_seq as _seq
    from repro.kernels import ops as _ops
    if matvec is not None:
        return _step_dense(params, state, x, theta_x, theta_h,
                           sigmoid=sigmoid, tanh=tanh, matvec=matvec)
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused backend hard-codes the i/f/g/o activation "
                         "pipeline; pass backend='dense' (or matvec=) "
                         "for custom/QAT activations")
    if layout is None:
        layout = _seq.pack_lstm_layer(params.w_x, params.w_h)
    use_ref = _ops._FORCE_REF or (interpret is None
                                  and _ops._interpret_default())
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    h_dim, i_dim = params.hidden_size, params.input_size
    lead = state.h.shape[:-1]
    args = (layout, state.m.reshape(-1, 4 * h_dim),
            state.h.reshape(-1, h_dim), state.c.reshape(-1, h_dim),
            dx_out.delta.reshape(-1, i_dim), dh_out.delta.reshape(-1, h_dim))
    if use_ref:
        m_new, h_new, c_new = _seq.deltalstm_seq_step_ref(*args)
    else:
        m_new, h_new, c_new = _seq.deltalstm_seq_step(
            *args, interpret=bool(interpret))
    h_new = h_new.reshape(*lead, h_dim)
    new_state = DeltaLstmLayerState(
        h=h_new, c=c_new.reshape(*lead, h_dim), x_mem=dx_out.state,
        h_mem=dh_out.state, m=m_new.reshape(*lead, 4 * h_dim))
    return DeltaLstmStepOut(h=h_new, state=new_state, delta_x=dx_out.delta,
                            delta_h=dh_out.delta)


def _step_fused_q8(params: LstmLayerParams, state: DeltaLstmLayerState,
                   x: Array, theta_x, theta_h, *, sigmoid, tanh, matvec,
                   layout=None, packed=None,
                   interpret=None) -> DeltaLstmStepOut:
    """Fixed-point i/f/g/o + cell update via the int8 single-pallas_call
    kernel (:mod:`repro.kernels.delta_q8`, the G=4 instantiation).

    Same mode resolution as :func:`_step_fused`: compiled Pallas on TPU
    (int8 HBM operand), the bit-identical pure-jnp oracle elsewhere (with
    the code->f32 conversion hoisted to pack time). State convention:
    ``m_init="zero"`` — ``M`` is the unscaled code-domain accumulator and
    the quantized bias lives in the packed layout; ``c`` lives on the
    saturating Q8.8 accumulator grid.
    """
    from repro.kernels import delta_q8 as _q8
    from repro.kernels import ops as _ops
    if matvec is not None:
        raise ValueError("fused_q8 carries code-domain delta memories; "
                         "a matvec= override cannot preserve its state "
                         "semantics (use backend='dense' instead)")
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused_q8 hard-codes the Q8.8/Q1.n LUT "
                         "activation pipeline; pass backend='dense' "
                         "with QAT act fns for training-time emulation")
    if layout is None:
        layout = _q8.pack_delta_weights_q8(params.w_x, params.w_h,
                                           b=params.b, gates=4)
    # The Delta Unit sees the Q8.8-quantized input stream (layer >= 2
    # inputs are already on-grid hidden states; re-rounding is exact).
    x = layout.quantize_act(x)
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    use_ref = _ops._FORCE_REF or (interpret is None
                                  and _ops._interpret_default())
    h_dim, i_dim = params.hidden_size, params.input_size
    lead = state.h.shape[:-1]
    args = (layout, state.m.reshape(-1, 4 * h_dim),
            state.h.reshape(-1, h_dim), state.c.reshape(-1, h_dim),
            dx_out.delta.reshape(-1, i_dim), dh_out.delta.reshape(-1, h_dim))
    if use_ref:
        m_new, h_new, c_new = _q8.deltalstm_q8_step_ref(*args)
    else:
        m_new, h_new, c_new = _q8.deltalstm_q8_step(
            *args, interpret=bool(interpret))
    h_new = h_new.reshape(*lead, h_dim)
    new_state = DeltaLstmLayerState(
        h=h_new, c=c_new.reshape(*lead, h_dim), x_mem=dx_out.state,
        h_mem=dh_out.state, m=m_new.reshape(*lead, 4 * h_dim))
    return DeltaLstmStepOut(h=h_new, state=new_state, delta_x=dx_out.delta,
                            delta_h=dh_out.delta)


def _step_fused_q4(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
                   matvec, layout=None, packed=None, interpret=None):
    """The int4 twin of :func:`_step_fused_q8`: nibble-packed weight
    volume (half the q8 bytes per fired column), identical Q8.8/LUT
    pipeline and code-domain state — the kernels dispatch on
    ``layout.weight_bits``, so past the packer this IS the q8 step."""
    from repro.kernels import delta_q8 as _q8
    if matvec is not None:
        raise ValueError("fused_q4 carries code-domain delta memories; "
                         "a matvec= override cannot preserve its state "
                         "semantics (use backend='dense' instead)")
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused_q4 hard-codes the Q8.8/Q1.n LUT "
                         "activation pipeline; pass backend='dense' "
                         "with QAT act fns for training-time emulation")
    if layout is None:
        layout = _q8.pack_delta_weights_q4(params.w_x, params.w_h,
                                           b=params.b, gates=4)
    return _step_fused_q8(params, state, x, theta_x, theta_h,
                          sigmoid=sigmoid, tanh=tanh, matvec=matvec,
                          layout=layout, packed=packed, interpret=interpret)


# -- per-backend stack packers (registered BackendSpec.pack fns) ------------

def _pack_none(params, block):
    return params, None, None


def _pack_fused(params, block):
    from repro.kernels.deltalstm_seq import pack_lstm_layer
    return params, [pack_lstm_layer(p.w_x, p.w_h, block_h=block,
                                    block_k=block)
                    for p in params], None


def _pack_fused_q8(params, block):
    # quantize-and-pack: the returned stack is the dequantized fake-quant
    # view, so oracles / state init see the same grids the kernel streams.
    from repro.quant.export import quantize_delta_stack
    qparams, layouts = quantize_delta_stack(params, cell="lstm", block=block)
    return qparams, layouts, None


def _pack_fused_q4(params, block):
    # int4 quantize-and-pack: nibble-packed volume + absmax/7 scales.
    from repro.quant.export import quantize_delta_stack
    qparams, layouts = quantize_delta_stack(params, cell="lstm", block=block,
                                            bits=4)
    return qparams, layouts, None


register_backend(BackendSpec(
    name="dense", cell="lstm", pack=_pack_none, step=_step_dense,
    m_init="bias", weight_bits=32, supports_custom_acts=True))
register_backend(BackendSpec(
    name="fused", cell="lstm", pack=_pack_fused, step=_step_fused,
    m_init="bias", weight_bits=32, supports_custom_acts=False,
    union_compaction=True))
register_backend(BackendSpec(
    name="fused_q8", cell="lstm", pack=_pack_fused_q8, step=_step_fused_q8,
    m_init="zero", weight_bits=8, supports_custom_acts=False,
    union_compaction=True))
register_backend(BackendSpec(
    name="fused_q4", cell="lstm", pack=_pack_fused_q4, step=_step_fused_q4,
    m_init="zero", weight_bits=4, supports_custom_acts=False,
    union_compaction=True))


def lstm_stack_m_init(backend: str) -> str:
    """M-memory init convention for an LSTM backend."""
    return get_backend(backend, cell="lstm").m_init


def deltalstm_step(params: LstmLayerParams, state: DeltaLstmLayerState,
                   x: Array, theta_x, theta_h,
                   sigmoid: Callable = jax.nn.sigmoid,
                   tanh: Callable = jnp.tanh,
                   matvec: Callable | None = None,
                   backend: str = "dense",
                   layout=None, packed=None,
                   interpret: bool | None = None) -> DeltaLstmStepOut:
    """One DeltaLSTM timestep, dispatched through the ``cell="lstm"``
    registry (builtin: ``"dense" | "fused" | "fused_q8" | "fused_q4"``).
    ``layout`` / ``packed`` / ``interpret`` follow
    the GRU-style step contract."""
    spec = get_backend(backend, cell="lstm")
    return spec.step(params, state, x, theta_x, theta_h, sigmoid=sigmoid,
                     tanh=tanh, matvec=matvec, layout=layout, packed=packed,
                     interpret=interpret)


# ---------------------------------------------------------------------------
# Multi-layer stacks over sequences (GRU-parity drivers)
# ---------------------------------------------------------------------------

class DeltaLstmStackState(NamedTuple):
    layers: tuple  # tuple[DeltaLstmLayerState, ...]


def init_deltalstm_stack_state(params: Sequence[LstmLayerParams],
                               batch_shape=(), dtype=None,
                               m_init: str = "bias") -> DeltaLstmStackState:
    return DeltaLstmStackState(
        layers=tuple(init_deltalstm_state(p, batch_shape, dtype,
                                          m_init=m_init)
                     for p in params))


def deltalstm_stack_step(params: Sequence[LstmLayerParams],
                         state: DeltaLstmStackState, x: Array,
                         theta_x, theta_h, layouts=None, packs=None, **kw):
    """One timestep through all layers; the input threshold of layers >= 2
    applies to the previous layer's output stream, as in the GRU stack.

    ``theta_x`` / ``theta_h`` accept a scalar or a static per-layer
    tuple/list (see :func:`repro.core.thresholds.layer_theta`);
    ``layouts`` / ``packs`` are optional per-layer pre-packed weights for
    kernel backends (see :func:`pack_lstm_stack`).
    """
    new_layers = []
    deltas = []
    inp = x
    for li, (p, st) in enumerate(zip(params, state.layers)):
        out = deltalstm_step(
            p, st, inp, layer_theta(theta_x, li), layer_theta(theta_h, li),
            layout=layouts[li] if layouts is not None else None,
            packed=packs[li] if packs is not None else None, **kw)
        new_layers.append(out.state)
        deltas.append((out.delta_x, out.delta_h))
        inp = out.h
    return inp, DeltaLstmStackState(tuple(new_layers)), deltas


def pack_lstm_stack(params: Sequence[LstmLayerParams], backend: str,
                    block: int = 128):
    """Pre-pack every layer's weights for a kernel backend, once
    (the LSTM spelling of :func:`repro.core.deltagru.pack_stack`)."""
    _, layouts, packs = get_backend(backend, cell="lstm").pack(params, block)
    return layouts, packs


def deltalstm_sequence(params: Sequence[LstmLayerParams], xs: Array,
                       theta_x, theta_h,
                       init_state: DeltaLstmStackState | None = None,
                       collect_sparsity: bool = True,
                       backend: str = "dense",
                       layouts=None, packs=None, **kw):
    """Run a DeltaLSTM stack over ``xs: [T, B, I]`` with ``lax.scan``.

    Full GRU-sequence parity: ``backend=`` selects the registered execution
    path, kernel layouts are packed ONCE here outside the scan (or passed
    pre-packed), per-layer thresholds are accepted, and the returned stats
    dict carries the measured Eq. 4 firing fractions.

    Returns ``(ys [T, B, H], final_state, stats)``.
    """
    if init_state is None:
        init_state = init_deltalstm_stack_state(
            params, xs.shape[1:-1], xs.dtype,
            m_init=lstm_stack_m_init(backend))
    if layouts is None and packs is None:
        layouts, packs = pack_lstm_stack(params, backend)

    def step(state, x):
        y, new_state, deltas = deltalstm_stack_step(params, state, x,
                                                    theta_x, theta_h,
                                                    backend=backend,
                                                    layouts=layouts,
                                                    packs=packs, **kw)
        if collect_sparsity:
            stats = tuple((jnp.mean((dx == 0).astype(jnp.float32)),
                           jnp.mean((dh == 0).astype(jnp.float32)))
                          for dx, dh in deltas)
        else:
            stats = ()
        return new_state, (y, stats)

    final_state, (ys, stats) = jax.lax.scan(step, init_state, xs)
    if collect_sparsity:
        gamma_dx = jnp.mean(jnp.stack([jnp.mean(s[0]) for s in stats]))
        gamma_dh = jnp.mean(jnp.stack([jnp.mean(s[1]) for s in stats]))
        return ys, final_state, {"gamma_dx": gamma_dx, "gamma_dh": gamma_dh,
                                 "per_layer": stats}
    return ys, final_state, {}


def lstm_sequence(params: Sequence[LstmLayerParams], xs: Array, **kw):
    batch_shape = xs.shape[1:-1]
    init = tuple((jnp.zeros((*batch_shape, p.hidden_size), xs.dtype),) * 2
                 for p in params)

    def step(carries, x):
        inp = x
        new = []
        for p, hc in zip(params, carries):
            hc = lstm_step(p, hc, inp, **kw)
            new.append(hc)
            inp = hc[0]
        return tuple(new), inp

    _, ys = jax.lax.scan(step, init, xs)
    return ys
