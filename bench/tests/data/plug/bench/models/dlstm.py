"""The delta-LSTM family (``core/deltalstm.py``, four gates i/f/g/o and a
cell state) served in float32 on the ``dense`` backend: weights from the
seed, and the system under test built from them.

The weights are made on the device in one jitted call, in float32, and
handed both to the program and, as they are, to the plain reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# The control: the plain reference, put in the program's place, with its
# products on operands rounded to bfloat16, the precision below float32.
controls = {"default": {"precision": "default"}}


@functools.partial(jax.jit, static_argnames=("i", "h", "layers", "o"))
def _weights(key, *, i: int, h: int, layers: int, o: int) -> dict:
    """Glorot-uniform gate weights, a forget-gate bias of 1, a normal
    head."""
    keys = jax.random.split(key, layers + 1)
    out = []
    for l in range(layers):
        il = i if l == 0 else h
        kx, kh = jax.random.split(keys[l])
        sx = math.sqrt(6.0 / (il + 4 * h))
        sh = math.sqrt(6.0 / (5 * h))
        b = jnp.zeros((4 * h,), jnp.float32).at[h:2 * h].set(1.0)
        out.append((jax.random.uniform(kx, (4 * h, il), jnp.float32, -sx, sx),
                    jax.random.uniform(kh, (4 * h, h), jnp.float32, -sh, sh),
                    b))
    kw, kb = jax.random.split(keys[-1])
    return {"layers": out,
            "head": jax.random.normal(kw, (h, o), jnp.float32) / math.sqrt(h),
            "head_b": 0.1 * jax.random.normal(kb, (o,), jnp.float32)}


def weights(config: dict, seed: int) -> dict:
    return jax.block_until_ready(_weights(
        jax.random.PRNGKey(seed), i=config["input_size"],
        h=config["hidden_size"], layers=config["num_layers"],
        o=config["output_size"]))


def dense_weights(config: dict) -> int:
    """``4H (I_l + H)`` per layer plus ``H O``."""
    i, h = config["input_size"], config["hidden_size"]
    stack = sum(4 * h * ((i if l == 0 else h) + h)
                for l in range(config["num_layers"]))
    return stack + h * config["output_size"]


def program(config: dict, w: dict):
    from repro.core.deltalstm import LstmLayerParams
    from repro.core.program import compile_delta_program
    params = {"lstm": [LstmLayerParams(*t) for t in w["layers"]],
              "head": w["head"], "head_b": w["head_b"]}
    return compile_delta_program(params, backend=config["backend"],
                                 cell="lstm")


def task(config: dict):
    from repro.core.thresholds import ThresholdPolicy
    from repro.models.gru_rnn import GruTaskConfig
    t = GruTaskConfig(config["input_size"], config["hidden_size"],
                      config["num_layers"], config["output_size"],
                      task=config["task"], theta_x=config["theta_x"],
                      theta_h=config["theta_h"])
    return t, ThresholdPolicy(config["theta_x"], config["theta_h"])


def frame_spec(config: dict):
    return (config["input_size"],), np.float32


def reference_kwargs(config: dict) -> dict:
    return {"theta_x": config["theta_x"], "theta_h": config["theta_h"]}


def tiny(config: dict, hidden: int = 16) -> dict:
    return {**config, "hidden_size": hidden}
