"""Every kernel of the served path compiles for a TPU v5e chip.

Nothing runs here: each case lowers one kernel entry point with
``interpret=False`` at the real serving shapes, compiles it for one chip
of a described (not attached) v5e topology, and requires a Pallas custom
call in the result. Interpret mode cannot show what this shows: block
shapes the TPU tiling refuses, unaligned DMA slices, VMEM overruns.

Shapes: the paper's 2L-768H GRU and its LSTM twin (layer 1: I=40, layer
2: I=768; H=768; one stream, plus one 8-stream tile step), and the
delta-ized LM cells at their configs' widths (RWKV6 d_model 2048 = 32
heads of 64, RG-LRU width 4096) at decode (T=1).

The topology is described in a fixture, never at import: only one
process may load the TPU library, and under several test workers each
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_q8 import (deltagru_q8_step, deltalstm_q8_step,
                                    pack_delta_weights_q8)
from repro.kernels.delta_spmv import delta_spmv
from repro.kernels.deltagru_seq import deltagru_seq_step, pack_gru_layer
from repro.kernels.deltalstm_seq import deltalstm_seq_step, pack_lstm_layer
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

H = 768
LAYER_INPUTS = {"l1": 40, "l2": 768}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles are written to a persistent cache but cannot be read
    # back without a chip; keep it off for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _cell_case(cell, kind, buffered, i_dim, b):
    """(fn, arg shapes) of one GRU/LSTM layer step."""
    g = 3 if cell == "gru" else 4
    w = (_f32(g * H, i_dim), _f32(g * H, H))
    if kind == "fp32":
        pack = pack_gru_layer if cell == "gru" else pack_lstm_layer
        lay = jax.eval_shape(pack, *w)
    else:
        lay = jax.eval_shape(
            lambda wx, wh, bias: pack_delta_weights_q8(
                wx, wh, bias, gates=g, weight_bits=kind,
                with_ref_codes=False), *w, _f32(g * H))
    state = (_f32(b, 4 * H), _f32(b, H), _f32(b, H), _f32(b, i_dim),
             _f32(b, H))

    def fn(lay, m, h, c, dx, dh):
        if cell == "gru" and kind == "fp32":
            return deltagru_seq_step(lay, m, h, dx, dh, interpret=False)
        if cell == "gru":
            return deltagru_q8_step(lay, m, h, dx, dh, interpret=False,
                                    buffered=buffered)
        if kind == "fp32":
            return deltalstm_seq_step(lay, m, h, c, dx, dh, interpret=False)
        return deltalstm_q8_step(lay, m, h, c, dx, dh, interpret=False,
                                 buffered=buffered)

    return fn, (lay, *state)


CELL_CASES = {
    f"{cell}-{name}-{layer}": (cell, kind, buffered, i_dim, 1)
    for cell in ("gru", "lstm")
    for name, kind, buffered in (("fused", "fp32", False),
                                 ("q8", 8, False), ("q8-buffered", 8, True),
                                 ("q4", 4, False), ("q4-buffered", 4, True))
    for layer, i_dim in LAYER_INPUTS.items()
}
CELL_CASES.update({f"gru-q8-tile8-{layer}": ("gru", 8, False, i_dim, 8)
                   for layer, i_dim in LAYER_INPUTS.items()})

LM_CASES = {
    # RWKV6 d_model 2048: a 2048x2048 projection bank and the 32x64 WKV scan
    "delta_spmv-2048": (
        lambda w, dx: delta_spmv(w, dx, interpret=False),
        (_f32(2048, 2048), _f32(1, 2048))),
    "rwkv6_scan-2048": (
        lambda r, k, v, w, u: rwkv6_scan(r, k, v, w, u, interpret=False),
        (_f32(1, 32, 1, 64),) * 4 + (_f32(32, 64),)),
    "rglru_scan-4096": (
        lambda x, a: rglru_scan(x, a, interpret=False),
        (_f32(1, 1, 4096), _f32(1, 1, 4096))),
}


def _compile_on(one_chip, fn, args):
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return jax.jit(fn).lower(*placed).compile().as_text()


@pytest.mark.parametrize("case", sorted(CELL_CASES))
def test_cell_step_compiles_for_v5e(one_chip, case):
    fn, args = _cell_case(*CELL_CASES[case])
    assert "tpu_custom_call" in _compile_on(one_chip, fn, args)


@pytest.mark.parametrize("case", sorted(LM_CASES))
def test_lm_kernel_compiles_for_v5e(one_chip, case):
    fn, args = LM_CASES[case]
    assert "tpu_custom_call" in _compile_on(one_chip, fn, args)


def test_granite_decode_step_compiles_in_place_for_v5e(one_chip):
    """The whole granite-4.0-h-micro decode step (40 layers at hidden
    2048, the bf16 KV cache at 131,072 positions) with the state donated:
    the cache and the SSM state alias their outputs, no operation copies
    either, and the step needs only a few MB beside its arguments."""
    import re

    from repro.core.deltamamba2h import (Mamba2hConfig,
                                         deltamamba2h_stack_step,
                                         init_mamba2h_params,
                                         init_mamba2h_stack_state,
                                         mamba2h_head)
    types = ["mamba"] * 40
    for i in (5, 15, 25, 35):
        types[i] = "attention"
    cfg = Mamba2hConfig(tuple(types), d_model=2048, vocab=100352,
                        mamba_heads=64, mamba_head_dim=64, d_state=128,
                        n_groups=1, d_conv=4, attn_heads=32, kv_heads=8,
                        attn_head_dim=64, intermediate=8192,
                        max_context=131072)
    place = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = place(jax.eval_shape(
        lambda: init_mamba2h_params(jax.random.PRNGKey(0), cfg)))
    state = place(jax.eval_shape(
        lambda: init_mamba2h_stack_state(params, (1,))))

    def step(params, state, tok):
        y, state, _ = deltamamba2h_stack_step((params,), state, tok, 0.0,
                                              0.0)
        return mamba2h_head((params,), y), state

    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    c = jax.jit(step, donate_argnums=(1,)).lower(params, state, tok).compile()
    text = c.as_text()
    big = (r"bf16\[1,4,131072,512\]", r"f32\[1,36,64,64,128\]")
    for shape in big:
        assert not re.search(rf"= {shape}\S* copy(-start)?\(", text), shape
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 4 * 131072 * 512 * 2 \
        + 36 * 64 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20
