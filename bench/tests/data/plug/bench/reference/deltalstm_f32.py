"""Plain reference of a delta-LSTM stack with a float32 head.

Written from Neil et al., "Delta Networks for Optimized Recurrent Network
Computation" (arXiv:1612.05571), in straightforward ``jax.numpy``, and
importing nothing of the system under test. Per stream and frame, per
layer: Eq. 2 on the layer's input and on its previous hidden state (a
component whose change since it last fired is at least theta fires; the
delta is that change, and the memory takes the new value); the delta
memory ``M`` (the biases at the start) adds ``W_x dx + W_h dh``; gates
``i, f, g, o`` of ``M``; ``c = f c + i g``, ``h = o tanh(c)``. The head
is ``y = h @ W_o + b_o``. Products at ``Precision.HIGHEST``, or with
``precision="default"`` on operands rounded to bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, precision):
    if precision == "default":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.dot(a, b, precision=HIGHEST)


def _fire(v, mem, theta):
    raw = v - mem
    fired = jnp.abs(raw) >= theta
    return jnp.where(fired, raw, 0.0), jnp.where(fired, v, mem)


@functools.partial(jax.jit, static_argnames=("theta_x", "theta_h",
                                             "precision"))
def _stream(params, xs, *, theta_x, theta_h, precision):
    def body(state, x):
        new, v = [], x
        for (w_x, w_h, _), (x_hat, h_hat, h, c, m) in zip(params["layers"],
                                                           state):
            dx, x_hat = _fire(v, x_hat, theta_x)
            dh, h_hat = _fire(h, h_hat, theta_h)
            m = m + _dot(dx, w_x.T, precision) + _dot(dh, w_h.T, precision)
            zi, zf, zg, zo = jnp.split(m, 4)
            c = jax.nn.sigmoid(zf) * c + jax.nn.sigmoid(zi) * jnp.tanh(zg)
            h = jax.nn.sigmoid(zo) * jnp.tanh(c)
            new.append((x_hat, h_hat, h, c, m))
            v = h
        return new, _dot(v, params["head"], precision) + params["head_b"]

    state = []
    for w_x, w_h, b in params["layers"]:
        z = jnp.zeros(w_h.shape[1], jnp.float32)
        state.append((jnp.zeros(w_x.shape[1], jnp.float32), z, z, z, b))
    return jax.lax.scan(body, state, xs)[1]


def replay(params: dict, streams: list, theta_x: float, theta_h: float, *,
           precision: str = "highest", serve_ticks=None, n_ticks: int = 0):
    """The head outputs of every stream, each from a fresh state; with
    ``serve_ticks`` also the fired-column counts, which this reference
    does not keep (empty lists)."""
    outs = [np.asarray(_stream(params, jnp.asarray(f, jnp.float32),
                               theta_x=float(theta_x),
                               theta_h=float(theta_h), precision=precision))
            for f in streams]
    return outs if serve_ticks is None else (outs, [], [])
