"""DeltaGRU — the paper's core contribution (EdgeDRNN Eq. 1-3), pure JAX.

A DeltaGRU layer keeps, per stream (batch element):

* state memories ``x_hat`` / ``h_hat`` (Eq. 2, :mod:`repro.core.delta`),
* four *delta memories* ``M_r, M_u, M_xc, M_hc`` holding running partial
  sums (Eq. 3), initialized to the biases (``M_hc`` to 0),
* the ordinary hidden state ``h``.

At ``theta_x == theta_h == 0`` a DeltaGRU is bit-for-bit a standard GRU
(up to float addition reassociation) — the property tests pin this down.

Gate ordering throughout: ``r`` (reset), ``u`` (update), ``c`` (candidate);
concatenated weights are ``W_x: [3H, I]`` and ``W_h: [3H, H]`` in that order,
matching the paper's concatenated-column DRAM layout (Fig. 6).

**Primary entry point**: compile once, then stream —
:func:`repro.core.program.compile_deltagru` resolves a backend spec from
the registry (:mod:`repro.core.backends`), packs every layer's weights
once, and returns an immutable :class:`~repro.core.program.DeltaGruProgram`
whose ``init_state()`` / ``step()`` / ``sequence()`` methods carry the
backend's state convention with them — a mismatched state is
unrepresentable instead of silently corrupting. The loose
``backend=`` / ``layouts=`` / ``packs=`` kwargs on the functions below
remain as the legacy spelling (and the training-time path, where packing
per call is the point).

Execution backends (``backend=`` on every step/sequence entry point; each
is a registered :class:`repro.core.backends.BackendSpec`):

* ``"dense"`` — plain XLA matmuls; the oracle. Zeros in the deltas are
  multiplied, not skipped.
* ``"fused"`` — :mod:`repro.kernels.deltagru_seq`: ONE pallas_call per
  layer step over the concatenated ``[3H, I+H]`` Fig. 6 layout with a
  single compaction, activation pipeline included; sequences run under
  ``lax.scan`` with zero per-step Python dispatch.
* ``"fused_q8"`` — the same fused pipeline with the paper's fixed-point
  semantics (Sec. IV-A): **int8 packed weights** streamed from HBM
  (4x fewer bytes per fired column), Q8.8 activations, unscaled
  code-domain delta memories (the PE's integer accumulator; biases are
  applied at the activation stage, not folded into ``M``), and the Q8.8
  -> Q1.4 LUT sigmoid/tanh grid in-kernel. Quantize a trained stack with
  :func:`repro.quant.export.quantize_stack` and pass its layouts.
* ``"fused_q4"`` — the int4 twin of ``fused_q8``: nibble-packed weights
  (half the q8 bytes per fired column), unpacked in-register.

Every fused backend takes a ``[B, ...]`` tile of streams as it takes one
stream: one weight pass per layer step, compacting fired blocks on the
**union** of fired columns across the tile
(``BackendSpec.union_compaction``). A stream whose delta slice in a
union-fired block is all-zero contributes exactly ±0.0 partial products,
so a stream's outputs do not depend on its companions at a fixed tile
width, bitwise (fp32) and code-exact (q8/q4).

``dense`` and the fused fp32 paths are numerically equivalent to the
Eq. 3 recurrence (the equivalence suite pins fused == dense == the Eq. 1
oracle at ``theta == 0``). ``fused_q8`` instead bit-matches the
fake-quant fixed-point reference on the declared Qm.n grids
(``tests/test_quant_backends.py``) and reduces to a quantized plain GRU
at ``theta == 0``.
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


class GruLayerParams(NamedTuple):
    w_x: Array  # [3H, I]   gates (r,u,c) stacked on axis 0
    w_h: Array  # [3H, H]
    b: Array    # [3H]

    @property
    def hidden_size(self) -> int:
        return self.w_h.shape[-1]

    @property
    def input_size(self) -> int:
        return self.w_x.shape[-1]


def init_gru_layer(key: Array, input_size: int, hidden_size: int,
                   dtype=jnp.float32) -> GruLayerParams:
    """Glorot-uniform weights, zero biases."""
    kx, kh = jax.random.split(key)
    sx = (6.0 / (input_size + 3 * hidden_size)) ** 0.5
    sh = (6.0 / (hidden_size + 3 * hidden_size)) ** 0.5
    return GruLayerParams(
        w_x=jax.random.uniform(kx, (3 * hidden_size, input_size), dtype, -sx, sx),
        w_h=jax.random.uniform(kh, (3 * hidden_size, hidden_size), dtype, -sh, sh),
        b=jnp.zeros((3 * hidden_size,), dtype),
    )


def init_gru_stack(key: Array, input_size: int, hidden_size: int,
                   num_layers: int, dtype=jnp.float32) -> list[GruLayerParams]:
    keys = jax.random.split(key, num_layers)
    layers = []
    for l, k in enumerate(keys):
        i = input_size if l == 0 else hidden_size
        layers.append(init_gru_layer(k, i, hidden_size, dtype))
    return layers


# ---------------------------------------------------------------------------
# Reference GRU (Eq. 1)
# ---------------------------------------------------------------------------

def gru_step(params: GruLayerParams, h_prev: Array, x: Array,
             sigmoid: Callable = jax.nn.sigmoid,
             tanh: Callable = jnp.tanh) -> Array:
    """Standard GRU cell update (Eq. 1). ``x: [..., I]``, ``h: [..., H]``."""
    h_dim = params.hidden_size
    zx = x @ params.w_x.T + params.b            # [..., 3H]
    zh = h_prev @ params.w_h.T                  # [..., 3H]
    rx, ux, cx = jnp.split(zx, 3, axis=-1)
    rh, uh, ch = jnp.split(zh, 3, axis=-1)
    r = sigmoid(rx + rh)
    u = sigmoid(ux + uh)
    c = tanh(cx + r * ch)
    del h_dim
    return (1.0 - u) * c + u * h_prev


# ---------------------------------------------------------------------------
# DeltaGRU (Eq. 2 + 3)
# ---------------------------------------------------------------------------

class DeltaGruLayerState(NamedTuple):
    h: Array             # [..., H] hidden state
    x_mem: DeltaState    # x_hat  [..., I]
    h_mem: DeltaState    # h_hat  [..., H]
    m: Array             # [..., 4H] delta memories (M_r, M_u, M_xc, M_hc)


def init_deltagru_state(params: GruLayerParams, batch_shape=(),
                        dtype=None, m_init: str = "bias") -> DeltaGruLayerState:
    """Paper init: ``M_r = b_r, M_u = b_u, M_xc = b_c, M_hc = 0``; states 0.

    Biases are folded into the delta memories up front, which is exactly the
    paper's "bias as first weight column, consumed once at t=1" trick.

    ``m_init="zero"`` (the ``fused_q8`` convention) leaves ``M`` all-zero:
    that backend's delta memories are the PE's *unscaled integer
    accumulator* and the quantized bias lives in the packed layout,
    consumed at the activation stage instead.
    """
    dtype = dtype or params.w_x.dtype
    h_dim, i_dim = params.hidden_size, params.input_size
    if m_init == "zero":
        m0 = jnp.zeros((4 * h_dim,), dtype)
    else:
        b_r, b_u, b_c = jnp.split(params.b.astype(dtype), 3)
        m0 = jnp.concatenate([b_r, b_u, b_c, jnp.zeros((h_dim,), dtype)])
    m0 = jnp.broadcast_to(m0, (*batch_shape, 4 * h_dim))
    return DeltaGruLayerState(
        h=jnp.zeros((*batch_shape, h_dim), dtype),
        x_mem=init_delta_state((*batch_shape, i_dim), dtype),
        h_mem=init_delta_state((*batch_shape, h_dim), dtype),
        m=m0,
    )


class DeltaGruStepOut(NamedTuple):
    h: Array
    state: DeltaGruLayerState
    delta_x: Array   # the (sparse) encoded input delta actually used
    delta_h: Array   # the (sparse) encoded hidden delta actually used


def _fused_layer_step(params: GruLayerParams, state: DeltaGruLayerState,
                      dx_out, dh_out, layout=None,
                      interpret: bool | None = None):
    """Eq. 3 via the single-pallas_call fused kernel (flattens batch dims).

    Mode resolution follows :mod:`repro.kernels.ops`: compiled Pallas on
    TPU; on other backends the pure-jnp oracle of the same fused math
    (interpret-mode emulation is a correctness tool, not a perf path —
    request it explicitly with ``interpret=True``).
    """
    from repro.kernels import deltagru_seq as _seq
    from repro.kernels import ops as _ops
    if layout is None:
        layout = _seq.pack_gru_layer(params.w_x, params.w_h)
    use_ref = _ops._FORCE_REF or (interpret is None
                                  and _ops._interpret_default())
    h_dim, i_dim = params.hidden_size, params.input_size
    lead = state.h.shape[:-1]
    args = (layout, state.m.reshape(-1, 4 * h_dim),
            state.h.reshape(-1, h_dim), dx_out.delta.reshape(-1, i_dim),
            dh_out.delta.reshape(-1, h_dim))
    if use_ref:
        m_new, h_new = _seq.deltagru_seq_step_ref(*args)
    else:
        m_new, h_new = _seq.deltagru_seq_step(*args,
                                              interpret=bool(interpret))
    h_new = h_new.reshape(*lead, h_dim)
    new_state = DeltaGruLayerState(
        h=h_new, x_mem=dx_out.state, h_mem=dh_out.state,
        m=m_new.reshape(*lead, 4 * h_dim))
    return DeltaGruStepOut(h=h_new, state=new_state,
                           delta_x=dx_out.delta, delta_h=dh_out.delta)


def _fused_q8_layer_step(params: GruLayerParams, state: DeltaGruLayerState,
                         dx_out, dh_out, layout=None,
                         interpret: bool | None = None):
    """Fixed-point Eq. 3 via the int8 single-pallas_call kernel.

    Same mode resolution as :func:`_fused_layer_step`: compiled Pallas on
    TPU (int8 HBM operand), the bit-identical pure-jnp oracle elsewhere
    (with the code->f32 conversion hoisted to pack time).
    """
    from repro.kernels import deltagru_seq as _seq
    from repro.kernels import ops as _ops
    if layout is None:
        layout = _seq.pack_spmv_weights_q8(params.w_x, params.w_h,
                                           b=params.b)
    use_ref = _ops._FORCE_REF or (interpret is None
                                  and _ops._interpret_default())
    h_dim, i_dim = params.hidden_size, params.input_size
    lead = state.h.shape[:-1]
    args = (layout, state.m.reshape(-1, 4 * h_dim),
            state.h.reshape(-1, h_dim), dx_out.delta.reshape(-1, i_dim),
            dh_out.delta.reshape(-1, h_dim))
    if use_ref:
        m_new, h_new = _seq.deltagru_q8_step_ref(*args)
    else:
        m_new, h_new = _seq.deltagru_q8_step(*args,
                                             interpret=bool(interpret))
    h_new = h_new.reshape(*lead, h_dim)
    new_state = DeltaGruLayerState(
        h=h_new, x_mem=dx_out.state, h_mem=dh_out.state,
        m=m_new.reshape(*lead, 4 * h_dim))
    return DeltaGruStepOut(h=h_new, state=new_state,
                           delta_x=dx_out.delta, delta_h=dh_out.delta)


def _accumulate_step(state: DeltaGruLayerState, dx_out, dh_out,
                     mv_x: Callable, mv_h: Callable,
                     sigmoid: Callable, tanh: Callable) -> DeltaGruStepOut:
    """Shared Eq. 3 accumulate + activation path over two matvec thunks."""
    dx, dh = dx_out.delta, dh_out.delta
    zx = mv_x(dx)                               # [..., 3H] = W_x @ dx
    zh = mv_h(dh)                               # [..., 3H] = W_h @ dh

    m_r, m_u, m_xc, m_hc = jnp.split(state.m, 4, axis=-1)
    zxr, zxu, zxc = jnp.split(zx, 3, axis=-1)
    zhr, zhu, zhc = jnp.split(zh, 3, axis=-1)

    m_r = m_r + zxr + zhr
    m_u = m_u + zxu + zhu
    m_xc = m_xc + zxc
    m_hc = m_hc + zhc

    r = sigmoid(m_r)
    u = sigmoid(m_u)
    c = tanh(m_xc + r * m_hc)
    h = (1.0 - u) * c + u * state.h

    new_state = DeltaGruLayerState(
        h=h, x_mem=dx_out.state, h_mem=dh_out.state,
        m=jnp.concatenate([m_r, m_u, m_xc, m_hc], axis=-1),
    )
    return DeltaGruStepOut(h=h, state=new_state, delta_x=dx, delta_h=dh)


# -- per-backend step implementations (registered BackendSpec.step fns) -----

def _step_dense(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
                matvec, layout, packed, interpret):
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    mv = matvec if matvec is not None else (lambda w, v: v @ w.T)
    return _accumulate_step(state, dx_out, dh_out,
                            lambda v: mv(params.w_x, v),
                            lambda v: mv(params.w_h, v), sigmoid, tanh)


def _step_fused(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
                matvec, layout, packed, interpret):
    if matvec is not None:
        # a matvec= override takes precedence over the fused kernel: run
        # the generic accumulate path with the caller's matvec.
        dx_out = delta_encode(x, state.x_mem, theta_x)
        dh_out = delta_encode(state.h, state.h_mem, theta_h)
        return _accumulate_step(state, dx_out, dh_out,
                                lambda v: matvec(params.w_x, v),
                                lambda v: matvec(params.w_h, v),
                                sigmoid, tanh)
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused backend hard-codes the Fig. 7 activation "
                         "pipeline; pass backend='dense' (or matvec=) "
                         "for custom/QAT activations")
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    return _fused_layer_step(params, state, dx_out, dh_out,
                             layout=layout, interpret=interpret)


def _step_fused_q8(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
                   matvec, layout, packed, interpret):
    if matvec is not None:
        raise ValueError("fused_q8 carries code-domain delta memories; "
                         "a matvec= override cannot preserve its state "
                         "semantics (use backend='dense' instead)")
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused_q8 hard-codes the Q8.8/Q1.n LUT "
                         "activation pipeline; pass backend='dense' "
                         "with QAT act fns for training-time emulation")
    if layout is None:
        from repro.kernels.deltagru_seq import pack_spmv_weights_q8
        layout = pack_spmv_weights_q8(params.w_x, params.w_h, b=params.b)
    # The Delta Unit sees the Q8.8-quantized input stream (layer >= 2
    # inputs are already on-grid hidden states; re-rounding is exact).
    x = layout.quantize_act(x)
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    return _fused_q8_layer_step(params, state, dx_out, dh_out,
                                layout=layout, interpret=interpret)


def _step_fused_q4(params, state, x, theta_x, theta_h, *, sigmoid, tanh,
                   matvec, layout, packed, interpret):
    """The int4 twin of :func:`_step_fused_q8`: same Q8.8/LUT pipeline and
    code-domain delta memories, but the streamed volume is the
    nibble-packed int4 pack (half the q8 bytes per fired column) and the
    kernels unpack in-register — the dispatch is ``layout.weight_bits``,
    so the layer step below is shared with q8 verbatim."""
    if matvec is not None:
        raise ValueError("fused_q4 carries code-domain delta memories; "
                         "a matvec= override cannot preserve its state "
                         "semantics (use backend='dense' instead)")
    if not _default_acts(sigmoid, tanh):
        raise ValueError("fused_q4 hard-codes the Q8.8/Q1.n LUT "
                         "activation pipeline; pass backend='dense' "
                         "with QAT act fns for training-time emulation")
    if layout is None:
        from repro.kernels.delta_q8 import pack_delta_weights_q4
        layout = pack_delta_weights_q4(params.w_x, params.w_h, b=params.b)
    x = layout.quantize_act(x)
    dx_out = delta_encode(x, state.x_mem, theta_x)
    dh_out = delta_encode(state.h, state.h_mem, theta_h)
    return _fused_q8_layer_step(params, state, dx_out, dh_out,
                                layout=layout, interpret=interpret)


# -- per-backend stack packers (registered BackendSpec.pack fns) ------------

def _pack_none(params, block):
    return params, None, None


def _pack_fused(params, block):
    from repro.kernels.deltagru_seq import pack_gru_layer
    return params, [pack_gru_layer(p.w_x, p.w_h, block_h=block,
                                   block_k=block)
                    for p in params], None


def _pack_fused_q8(params, block):
    # quantize-and-pack: the returned stack is the dequantized fake-quant
    # view, so oracles / state init see the same grids the kernel streams.
    from repro.quant.export import quantize_stack
    qparams, layouts = quantize_stack(params, block=block)
    return qparams, layouts, None


def _pack_fused_q4(params, block):
    # int4 quantize-and-pack: nibble-packed volume + absmax/7 scales.
    from repro.quant.export import quantize_stack
    qparams, layouts = quantize_stack(params, block=block, bits=4)
    return qparams, layouts, None


register_backend(BackendSpec(
    name="dense", cell="gru", pack=_pack_none, step=_step_dense,
    m_init="bias", weight_bits=32, supports_custom_acts=True))
register_backend(BackendSpec(
    name="fused", cell="gru", pack=_pack_fused, step=_step_fused,
    m_init="bias", weight_bits=32, supports_custom_acts=False,
    union_compaction=True))
register_backend(BackendSpec(
    name="fused_q8", cell="gru", pack=_pack_fused_q8, step=_step_fused_q8,
    m_init="zero", weight_bits=8, supports_custom_acts=False,
    union_compaction=True))
register_backend(BackendSpec(
    name="fused_q4", cell="gru", pack=_pack_fused_q4, step=_step_fused_q4,
    m_init="zero", weight_bits=4, supports_custom_acts=False,
    union_compaction=True))


def deltagru_step(params: GruLayerParams, state: DeltaGruLayerState, x: Array,
                  theta_x, theta_h,
                  sigmoid: Callable = jax.nn.sigmoid,
                  tanh: Callable = jnp.tanh,
                  matvec: Callable | None = None,
                  backend: str = "dense",
                  layout=None,
                  packed=None,
                  interpret: bool | None = None) -> DeltaGruStepOut:
    """One DeltaGRU timestep (Eq. 3), dispatched through the backend
    registry (:mod:`repro.core.backends`).

    Args:
      matvec: optional override ``matvec(w, delta) -> product``; takes
        precedence over ``backend`` (rejected by ``fused_q8``, whose state
        lives in the code domain).
      backend: any registered GRU backend name (builtin:
        ``"dense" | "fused" | "fused_q8" | "fused_q4"``, see module
        docstring). Unknown names raise, listing the registered set.
      layout: optional pre-packed :class:`FusedGruLayout` (fused) or
        :class:`QuantGruLayout` (fused_q8) for the kernel backends
        (packed/quantized on the fly otherwise — sequence entry points
        pack once and thread it here).

    State convention: ``state`` must have been created with
    ``init_deltagru_state(..., m_init=stack_m_init(backend))``. For
    ``fused_q8`` that means ``m_init="zero"`` — its ``M`` is the unscaled
    code-domain accumulator and the bias lives in the packed layout; a
    default (``m_init="bias"``) state would silently double-count the
    bias through the dequant scale. The sequence/stack/engine entry
    points handle this automatically when they build the initial state,
    and the :class:`~repro.core.program.DeltaGruProgram` API makes the
    mismatch unrepresentable.
      packed: pre-padded spmv operand pairs; unused by the builtin
        backends, passed through to registered third-party specs.
      interpret: Pallas mode for the kernel backends. ``None`` (default)
        auto-selects: compiled kernels on TPU, the pure-jnp references
        elsewhere. ``True`` forces interpret-mode emulation — the
        kernel-correctness path.
    """
    spec = get_backend(backend, cell="gru")
    return spec.step(params, state, x, theta_x, theta_h, sigmoid=sigmoid,
                     tanh=tanh, matvec=matvec, layout=layout, packed=packed,
                     interpret=interpret)


# ---------------------------------------------------------------------------
# Multi-layer stacks over sequences
# ---------------------------------------------------------------------------

class DeltaGruStackState(NamedTuple):
    layers: tuple  # tuple[DeltaGruLayerState, ...]


def init_deltagru_stack_state(params: Sequence[GruLayerParams], batch_shape=(),
                              dtype=None,
                              m_init: str = "bias") -> DeltaGruStackState:
    return DeltaGruStackState(
        layers=tuple(init_deltagru_state(p, batch_shape, dtype, m_init=m_init)
                     for p in params))


def stack_m_init(backend: str) -> str:
    """M-memory init convention for a backend (see init_deltagru_state)."""
    return get_backend(backend, cell="gru").m_init


def deltagru_stack_step(params: Sequence[GruLayerParams],
                        state: DeltaGruStackState, x: Array,
                        theta_x, theta_h, layouts=None, packs=None, **kw):
    """One timestep through all layers. Per paper Sec. II-C the *input*
    threshold of layers >= 2 is ``theta_x`` applied to the previous layer's
    output stream (those deltas count toward Gamma_dx in Eq. 4).

    ``theta_x`` / ``theta_h`` accept a scalar or a static per-layer
    tuple/list (one entry per layer — the
    :meth:`~repro.core.thresholds.ThresholdPolicy.layer_thetas` spelling);
    ``layouts`` / ``packs`` are optional per-layer pre-packed weights for
    the kernel backends (see :func:`pack_stack`).
    """
    new_layers = []
    deltas = []
    inp = x
    for li, (p, st) in enumerate(zip(params, state.layers)):
        out = deltagru_step(
            p, st, inp, layer_theta(theta_x, li), layer_theta(theta_h, li),
            layout=layouts[li] if layouts is not None else None,
            packed=packs[li] if packs is not None else None, **kw)
        new_layers.append(out.state)
        deltas.append((out.delta_x, out.delta_h))
        inp = out.h
    return inp, DeltaGruStackState(tuple(new_layers)), deltas


def pack_stack(params: Sequence[GruLayerParams], backend: str,
               block: int = 128):
    """Pre-pack every layer's weights for a kernel backend, once.

    Legacy entry point: dispatches to the registered spec's ``pack`` and
    drops its (possibly rewritten) parameter stack, returning only
    ``(layouts, packs)`` — per-layer fused layouts for the fused backends,
    ``(None, None)`` for ``"dense"``. This hoists the per-call ``jnp.pad``
    out of the scan body: inside a sequence the pads would otherwise
    re-run every timestep. Prefer
    :func:`repro.core.program.compile_deltagru`, which also keeps the
    rewritten stack (the int8 dequant view) and the state convention.
    """
    _, layouts, packs = get_backend(backend, cell="gru").pack(params, block)
    return layouts, packs


def deltagru_sequence(params: Sequence[GruLayerParams], xs: Array,
                      theta_x, theta_h,
                      init_state: DeltaGruStackState | None = None,
                      collect_sparsity: bool = True,
                      backend: str = "dense",
                      layouts=None, packs=None, **kw):
    """Run a DeltaGRU stack over ``xs: [T, B, I]`` with ``lax.scan``.

    ``backend`` selects the per-step execution path (see module docstring);
    kernel backends get their weights packed ONCE here, outside the scan —
    or pass pre-packed ``layouts``/``packs`` (e.g. the exact
    :func:`repro.quant.export.quantize_stack` layouts for ``fused_q8``) to
    skip even that.

    Returns (ys ``[T, B, H]``, final_state, stats) where stats holds measured
    per-layer firing fractions for Eq. 4 if ``collect_sparsity``.
    """
    if init_state is None:
        init_state = init_deltagru_stack_state(params, xs.shape[1:-1],
                                               xs.dtype,
                                               m_init=stack_m_init(backend))
    if layouts is None and packs is None:
        layouts, packs = pack_stack(params, backend)

    def step(state, x):
        y, new_state, deltas = deltagru_stack_step(params, state, x,
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


def gru_sequence(params: Sequence[GruLayerParams], xs: Array, **kw):
    """Reference multi-layer GRU over ``xs: [T, B, I]`` (Eq. 1 oracle)."""
    batch_shape = xs.shape[1:-1]
    h0 = tuple(jnp.zeros((*batch_shape, p.hidden_size), xs.dtype) for p in params)

    def step(hs, x):
        new_hs = []
        inp = x
        for p, h in zip(params, hs):
            h = gru_step(p, h, inp, **kw)
            new_hs.append(h)
            inp = h
        return tuple(new_hs), inp

    _, ys = jax.lax.scan(step, h0, xs)
    return ys
