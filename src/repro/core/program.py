"""Compiled DeltaRNN programs: compile once, stream forever.

EdgeDRNN's deployment model is a compile-then-stream split: weights are
packed into the DRAM layout once, and the streaming side only ever issues
steps against that fixed program. :func:`compile_delta_program` is the
software analogue — it resolves a :class:`~repro.core.backends.BackendSpec`
from the registry for any registered **cell family** (``"gru"``,
``"lstm"``, ``"rwkv6"``, ``"rglru"`` and ``"mamba2h"`` builtin — the LM
cells delta-threshold their projection banks, see
:mod:`repro.core.deltarwkv` / :mod:`repro.core.deltarglru` /
:mod:`repro.core.deltamamba2h`), packs every layer's weights once
(quantizing them for
``fused_q8`` — for either cell family, ``compile`` of a trained fp32/QAT
stack IS the int8 export), and returns an immutable :class:`DeltaProgram`:

* the program is a **pytree** (layers / layouts / packs / head are leaves,
  the backend and cell names are static), so it passes through ``jit``,
  ``vmap`` and ``lax.scan`` like any parameter structure;
* states come only from :meth:`DeltaProgram.init_state`, which bakes in
  the backend's delta-memory convention (``m_init``) — a ``fused_q8``
  program cannot be fed a bias-folded state, the historical silent-
  corruption trap of the loose ``backend=`` / ``layouts=`` / ``m_init=``
  knob soup;
* :meth:`DeltaProgram.step` / :meth:`DeltaProgram.sequence` verify the
  state they are given was minted by a same-cell, same-backend program and
  raise otherwise.

Typical use::

    prog = compile_deltagru(params, backend="fused_q8")     # quantizes+packs
    lprog = compile_delta_program(lstm_params, cell="lstm",
                                  backend="fused")          # same API, LSTM
    state = prog.init_state(batch_shape=(n_streams,))
    y, state, deltas = prog.step(state, x, theta_x, theta_h)
    logits = prog.apply_head(y)

or hand the program straight to the serving layer:
``DeltaStreamEngine(prog, task)``. ``theta_x`` / ``theta_h`` accept a
scalar or a static per-layer tuple (e.g. from
:meth:`repro.core.thresholds.ThresholdPolicy.layer_thetas`).

``compile_deltagru`` remains as the GRU-pinned thin alias, and
``DeltaGruProgram`` / ``DeltaGruProgramState`` name the same classes they
always did.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import BackendSpec, get_backend

Array = jax.Array


def _cell_ops(cell: str) -> dict:
    """Per-cell stack drivers (init / step / sequence), resolved lazily so
    the program module does not import every cell family up front."""
    if cell == "gru":
        from repro.core import deltagru as m
        return {"init": m.init_deltagru_stack_state,
                "step": m.deltagru_stack_step,
                "sequence": m.deltagru_sequence,
                "params_key": "gru"}
    if cell == "lstm":
        from repro.core import deltalstm as m
        return {"init": m.init_deltalstm_stack_state,
                "step": m.deltalstm_stack_step,
                "sequence": m.deltalstm_sequence,
                "params_key": "lstm"}
    if cell == "rwkv6":
        from repro.core import deltarwkv as m
        return {"init": m.init_deltarwkv_stack_state,
                "step": m.deltarwkv_stack_step,
                "sequence": m.deltarwkv_sequence,
                "params_key": "rwkv6"}
    if cell == "rglru":
        from repro.core import deltarglru as m
        return {"init": m.init_deltarglru_stack_state,
                "step": m.deltarglru_stack_step,
                "sequence": m.deltarglru_sequence,
                "params_key": "rglru"}
    if cell == "mamba2h":
        from repro.core import deltamamba2h as m
        return {"init": m.init_mamba2h_stack_state,
                "step": m.deltamamba2h_stack_step,
                "sequence": m.deltamamba2h_sequence,
                "params_key": "mamba2h",
                # token frames in, logits out through the tied embedding;
                # the state (KV cache and SSM state) is updated in place
                "head": m.mamba2h_head,
                "frame_dtype": np.int32,
                "frame_ok": m.frame_ok,
                "watched": m.watched,
                "max_context": m.max_context,
                "in_place": True}
    raise ValueError(f"unknown cell family {cell!r}; known: "
                     f"('gru', 'lstm', 'rwkv6', 'rglru', 'mamba2h')")


@dataclass(frozen=True)
class DeltaProgramState:
    """A delta-RNN stack state minted by (and bound to) a compiled program.

    Wraps the raw stack state with the backend and cell names as *static*
    pytree metadata: programs check both before every step, so a state
    whose delta-memory convention (or cell family) doesn't match the
    executing backend raises instead of silently corrupting. Construct via
    :meth:`DeltaProgram.init_state`, never directly.
    """

    stack: object
    backend: str
    cell: str = "gru"

    @property
    def layers(self) -> tuple:
        return self.stack.layers


jax.tree_util.register_pytree_node(
    DeltaProgramState,
    lambda s: ((s.stack,), (s.backend, s.cell)),
    lambda aux, ch: DeltaProgramState(stack=ch[0], backend=aux[0],
                                      cell=aux[1]))

# Historical GRU-era names; same classes, cell defaults to "gru".
DeltaGruProgramState = DeltaProgramState


@dataclass(frozen=True)
class DeltaProgram:
    """An immutable, ready-to-run delta-RNN stack for one (cell, backend).

    Holds the per-layer parameters (for ``fused_q8`` these are the
    dequantized fake-quant view, so oracle comparisons and state shapes
    see the same grids the kernel streams), the pre-packed kernel layouts
    / matvec packs, an optional classifier head, and the backend spec
    resolved once at compile time. Registered as a pytree: arrays are
    leaves, ``backend`` / ``cell`` / ``interpret`` are static — programs
    can be passed as ``jit`` arguments, scanned over, or held by engines.

    Build with :func:`compile_delta_program` (or the GRU-pinned
    :func:`compile_deltagru`); do not construct directly.
    """

    layers: tuple          # tuple[GruLayerParams | LstmLayerParams, ...]
    layouts: tuple | None  # per-layer kernel layouts
    packs: tuple | None    # per-layer (w_x_packed, w_h_packed)
    head: Array | None
    head_b: Array | None
    backend: str
    interpret: bool | None = None
    cell: str = "gru"

    # -- derived ----------------------------------------------------------

    @property
    def spec(self) -> BackendSpec:
        return get_backend(self.backend, cell=self.cell)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    @property
    def input_size(self) -> int:
        return self.layers[0].input_size

    @property
    def hidden_size(self) -> int:
        return self.layers[-1].hidden_size

    @property
    def has_head(self) -> bool:
        """Whether :meth:`apply_head` can make outputs: a compiled head,
        or a family that brings its own (a tied embedding)."""
        return self.head is not None or "head" in _cell_ops(self.cell)

    @property
    def frame_dtype(self):
        """One stream-frame's dtype: float32 features, or int32 token ids
        for a family with a token input stage."""
        return _cell_ops(self.cell).get("frame_dtype", np.float32)

    @property
    def in_place(self) -> bool:
        """Whether a server should donate the state to each step, so it is
        updated in place (a family whose state holds a KV cache)."""
        return _cell_ops(self.cell).get("in_place", False)

    @property
    def max_context(self) -> int | None:
        """Tokens a stream's cache holds, for a family that keeps one."""
        f = _cell_ops(self.cell).get("max_context")
        return None if f is None else f(self.layers)

    def frame_ok(self, x: Array) -> Array:
        """Per component of a frame, whether it may enter the stack:
        finite features, or token ids inside the vocabulary."""
        f = _cell_ops(self.cell).get("frame_ok")
        return jnp.isfinite(x) if f is None else f(self.layers, x)

    def watched(self, state: DeltaProgramState):
        """The part of a state a server scans for non-finite values after
        each step (all of it, unless the family names a smaller part that
        any corruption reaches in the same step)."""
        f = _cell_ops(self.cell).get("watched")
        return state.stack if f is None else f(state.stack)

    # -- states -----------------------------------------------------------

    def init_state(self, batch_shape=(), dtype=None) -> DeltaProgramState:
        """A fresh stack state under THIS backend's ``m_init`` convention.

        This is the only way to mint a program state — the convention
        (bias-folded M for the fp32 backends, all-zero code-domain
        accumulator for ``fused_q8``) is not a caller knob anymore.
        """
        stack = _cell_ops(self.cell)["init"](self.layers, batch_shape, dtype,
                                             m_init=self.spec.m_init)
        return DeltaProgramState(stack=stack, backend=self.backend,
                                 cell=self.cell)

    def check_state(self, state) -> None:
        """Raise unless ``state`` was minted by a same-cell, same-backend
        program."""
        if not isinstance(state, DeltaProgramState):
            raise TypeError(
                "expected a DeltaProgramState from program.init_state(); "
                f"got {type(state).__name__} — raw stack states carry no "
                "m_init convention tag and cannot be safely executed")
        if state.cell != self.cell:
            raise ValueError(
                f"state was built for cell {state.cell!r} but this program "
                f"runs {self.cell!r}; the stack state structures are not "
                "interchangeable — rebuild with program.init_state()")
        if state.backend != self.backend:
            raise ValueError(
                f"state was built for backend {state.backend!r} "
                f"(m_init={get_backend(state.backend, self.cell).m_init!r}) "
                f"but this program runs {self.backend!r} "
                f"(m_init={self.spec.m_init!r}); feeding it through would "
                "silently corrupt the delta memories — rebuild with "
                "program.init_state()")

    # -- execution --------------------------------------------------------

    def step(self, state: DeltaProgramState, x: Array,
             theta_x=0.0, theta_h=0.0):
        """One timestep through all layers.

        ``x: [..., I]`` with the same batch shape the state was built
        with; ``theta_x`` / ``theta_h`` are scalars or static per-layer
        tuples. Returns ``(y, new_state, deltas)`` where ``y`` is the top
        layer's hidden output and ``deltas`` the per-layer sparse
        ``(delta_x, delta_h)`` pairs (for firing accounting).
        """
        self.check_state(state)
        y, stack, deltas = _cell_ops(self.cell)["step"](
            self.layers, state.stack, x, theta_x, theta_h,
            backend=self.backend, layouts=self.layouts, packs=self.packs,
            interpret=self.interpret)
        return y, DeltaProgramState(stack=stack, backend=self.backend,
                                    cell=self.cell), deltas

    def sequence(self, xs: Array, theta_x=0.0, theta_h=0.0,
                 init_state: DeltaProgramState | None = None,
                 collect_sparsity: bool = True):
        """Run the program over ``xs: [T, B, I]`` with ``lax.scan``.

        Returns ``(ys, final_state, stats)`` exactly like the cell's
        ``*_sequence`` driver, but with the packed weights reused from
        compile time and the state convention enforced.
        """
        if init_state is None:
            init_state = self.init_state(xs.shape[1:-1], xs.dtype)
        self.check_state(init_state)
        ys, final, stats = _cell_ops(self.cell)["sequence"](
            self.layers, xs, theta_x, theta_h,
            init_state=init_state.stack, collect_sparsity=collect_sparsity,
            backend=self.backend, layouts=self.layouts, packs=self.packs,
            interpret=self.interpret)
        return ys, DeltaProgramState(stack=final, backend=self.backend,
                                     cell=self.cell), stats

    def apply_head(self, ys: Array) -> Array:
        """Apply the compiled classifier/regression head (if any)."""
        head = _cell_ops(self.cell).get("head")
        if head is not None:
            return head(self.layers, ys)
        if self.head is None:
            raise ValueError("program was compiled from a bare layer stack; "
                             "compile from a model params dict to carry "
                             "the head")
        return ys @ self.head + self.head_b

    def with_interpret(self, interpret: bool | None) -> "DeltaProgram":
        """Same program, different Pallas mode (kernel-correctness runs)."""
        return replace(self, interpret=interpret)


jax.tree_util.register_pytree_node(
    DeltaProgram,
    lambda p: ((p.layers, p.layouts, p.packs, p.head, p.head_b),
               (p.backend, p.interpret, p.cell)),
    lambda aux, ch: DeltaProgram(layers=ch[0], layouts=ch[1], packs=ch[2],
                                 head=ch[3], head_b=ch[4], backend=aux[0],
                                 interpret=aux[1], cell=aux[2]))

DeltaGruProgram = DeltaProgram


def infer_cell(params) -> str:
    """Cell family of a model params dict (stack-key spelling)."""
    if isinstance(params, dict):
        for cell in ("lstm", "rwkv6", "rglru"):
            if cell in params:
                return cell
        if "gru" in params:
            return "gru"
    return "gru"


def compile_delta_program(params, backend: str = "fused", *,
                          cell: str = "gru", layouts=None, packs=None,
                          block: int = 128,
                          interpret: bool | None = None) -> DeltaProgram:
    """Compile a delta-RNN stack (or model dict) into a program.

    Args:
      params: either a sequence of per-layer params
        (:class:`~repro.core.deltagru.GruLayerParams` /
        :class:`~repro.core.deltalstm.LstmLayerParams`) or a model params
        dict (``{"gru" | "lstm", "head", "head_b"}`` — the head is carried
        into the program for serving).
      backend: any backend name registered for ``cell``; resolved once,
        here.
      cell: the cell family (``"gru"``, ``"lstm"``, ``"rwkv6"``,
        ``"rglru"`` or ``"mamba2h"`` builtin; ``"mamba2h"`` compiles from
        ``{"mamba2h": [Mamba2hParams]}``, the whole stack as one entry).
      layouts / packs: optional pre-packed per-layer kernel operands
        (e.g. the exact :func:`repro.quant.export.quantize_stack` layouts);
        packed from ``params`` otherwise. For ``backend="fused_q8"`` with
        no ``layouts``, the stack is quantized here — ``compile`` of a
        trained fp32/QAT stack is the whole int8 export.
      block: kernel block size used when packing.
      interpret: Pallas mode baked into the program (None = auto).

    Returns:
      An immutable :class:`DeltaProgram`.
    """
    ops = _cell_ops(cell)
    spec = get_backend(backend, cell=cell)
    head = head_b = None
    if isinstance(params, dict):
        head, head_b = params.get("head"), params.get("head_b")
        key = ops["params_key"]
        if key not in params:
            raise ValueError(
                f"cell={cell!r} programs compile from a {key!r} stack; the "
                f"params dict has keys {sorted(params)} — pass cell="
                f"{infer_cell(params)!r} or the matching stack")
        stack = list(params[key])
    else:
        stack = list(params)
    if not stack or not (isinstance(stack[0], tuple)
                         or hasattr(stack[0], "cfg")):
        raise TypeError(f"compile_delta_program needs a non-empty {cell} "
                        f"layer-params stack; got {type(params).__name__}")
    if layouts is None and packs is None:
        stack, layouts, packs = spec.pack(stack, block)
    return DeltaProgram(
        layers=tuple(stack),
        layouts=tuple(layouts) if layouts is not None else None,
        packs=tuple(packs) if packs is not None else None,
        head=head, head_b=head_b, backend=backend, interpret=interpret,
        cell=cell)


def compile_deltagru(params, backend: str = "fused", *,
                     layouts=None, packs=None, block: int = 128,
                     interpret: bool | None = None) -> DeltaProgram:
    """GRU-pinned alias of :func:`compile_delta_program` (the historical
    spelling; identical semantics with ``cell="gru"``)."""
    return compile_delta_program(params, backend, cell="gru",
                                 layouts=layouts, packs=packs, block=block,
                                 interpret=interpret)
