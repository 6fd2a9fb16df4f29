"""The delta-GRU family: weights from the seed, and the system under test
built from them.

A configuration that names no ``"model"`` is of this family. The weights
are made on the device in one jitted call, in float32 (the type the
program quantizes from), and handed both to the program and, as they are,
to the plain reference: nothing the program derives from them reaches the
reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GATES = {"gru": 3, "lstm": 4}       # weight rows per hidden unit of a cell

# The controls: the plain reference, put in the program's place, at the
# weight width or the matmul precision below the configuration's.
controls = {"int4": {"bits": 4}, "high": {"precision": "high"},
            "default": {"precision": "default"}}

# The committed limits hold the program to the reference as the chip runs
# both: bit for bit there (0.0 on every run). On the CPU, XLA sums the
# float32 head of a batch-1 step in another order than the reference's
# scan, a few units in the last place (up to 5.5e-7 of the outputs' RMS
# at width 16). The rehearsal holds the program to this instead; every
# planted fault reads orders of magnitude above it.
CPU_OUT_GAP = 1e-5


@functools.partial(jax.jit, static_argnames=("i", "h", "layers", "o"))
def _weights(key, *, i: int, h: int, layers: int, o: int) -> dict:
    """Glorot-uniform gate weights and biases, a normal head."""
    keys = jax.random.split(key, layers + 1)
    out = []
    for l in range(layers):
        il = i if l == 0 else h
        kx, kh, kb = jax.random.split(keys[l], 3)
        sx = math.sqrt(6.0 / (il + 3 * h))
        sh = math.sqrt(6.0 / (4 * h))
        out.append((jax.random.uniform(kx, (3 * h, il), jnp.float32, -sx, sx),
                    jax.random.uniform(kh, (3 * h, h), jnp.float32, -sh, sh),
                    jax.random.uniform(kb, (3 * h,), jnp.float32, -sh, sh)))
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
    """Weights a dense step of the stack and head multiplies, unpadded:
    ``3H (I_l + H)`` per layer plus ``H O``."""
    i, h = config["input_size"], config["hidden_size"]
    gates = GATES[config["cell"]]
    stack = sum(gates * h * ((i if l == 0 else h) + h)
                for l in range(config["num_layers"]))
    return stack + h * config["output_size"]


def program(config: dict, w: dict):
    """The compiled program of the system under test."""
    from repro.core.deltagru import GruLayerParams
    from repro.core.program import compile_delta_program
    params = {"gru": [GruLayerParams(*t) for t in w["layers"]],
              "head": w["head"], "head_b": w["head_b"]}
    return compile_delta_program(params, backend=config["backend"],
                                 cell=config["cell"],
                                 block=config["block"])


def task(config: dict):
    from repro.core.thresholds import ThresholdPolicy
    from repro.models.gru_rnn import GruTaskConfig
    t = GruTaskConfig(config["input_size"], config["hidden_size"],
                      config["num_layers"], config["output_size"],
                      task=config["task"], theta_x=config["theta_x"],
                      theta_h=config["theta_h"])
    return t, ThresholdPolicy(config["theta_x"], config["theta_h"])


def frame_spec(config: dict):
    """One stream-frame: ``input_size`` float32 features."""
    return (config["input_size"],), np.float32


def reference_kwargs(config: dict) -> dict:
    """What the reference's ``replay`` takes from the configuration."""
    return {"theta_x": config["theta_x"], "theta_h": config["theta_h"],
            "bits": config["weight_bits"]}


def tiny(config: dict, hidden: int = 16) -> dict:
    """The configuration at the CPU rehearsal's width and limit."""
    return {**config, "hidden_size": hidden,
            "limits": {"out_gap": CPU_OUT_GAP}}
