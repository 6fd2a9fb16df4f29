"""Plain reference of a quantized delta-GRU stack with a float32 head.

Written from the EdgeDRNN paper (arXiv:2012.13600, Eqs. 1-3 and Sec. IV-A)
in straightforward ``jax.numpy``, and importing nothing of the system under
test. What it computes, per stream and frame:

* Weights: each gate row of the concatenated ``[W_x | W_h]`` matrix of a
  layer is quantized symmetrically, ``scale = absmax(row) / qmax`` with
  ``qmax = 2**(bits-1) - 1`` (127 for int8, 7 for int4) and codes
  ``clip(round(w / scale), -qmax, qmax)``. Biases are rounded onto the
  Q8.8 activation grid.
* Eq. 2 on the Q8.8 input of the first layer and on every hidden state: a
  component whose change since it last fired is at least Θ fires; the
  delta is that change, and the state memory takes the new value.
* Eq. 3 in the integer domain: four delta memories ``M_r, M_u, M_xc,
  M_hc`` accumulate ``delta x code`` products (unscaled); the activation
  stage dequantizes ``b + scale * M`` and rounds through the Q8.8 input /
  Q1.4 output grid of the sigmoid and tanh, and the new hidden state is
  rounded onto Q8.8. All sums run in float32 at ``Precision.HIGHEST``.
* The head ``y = h @ W_o + b_o`` in float32 at ``Precision.HIGHEST``.

``precision`` lowers the precision of both products: ``"default"``
multiplies the operands rounded to bfloat16 (one pass, as a TPU's default
float32 matmul does), ``"high"`` adds the products with each operand's
bfloat16 remainder (three passes, as ``Precision.HIGH`` does), with float32
sums. Written out, so a control reads alike on any platform. These, and
``bits=4``, are the controls that the check has to fail.

:func:`replay` runs the stack over many streams in fixed-shape chunks (one
compiled program for any mix of stream lengths) and, where asked, gathers
for each tick of the served schedule the union over the streams served in
it of the columns that Eq. 2 fired, per layer, and the number of
stream-columns fired: the counts behind the kernel's needed bytes and ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ACT_SCALE, ACT_MIN, ACT_MAX = 256.0, -256.0, 256.0 - 1.0 / 256.0  # Q8.8
LUT_SCALE, LUT_MIN, LUT_MAX = 16.0, -2.0, 2.0 - 1.0 / 16.0        # Q1.4


def _grid(v, scale, lo, hi):
    return jnp.clip(jnp.round(v * scale) / scale, lo, hi)


def q88(v):
    return _grid(v, ACT_SCALE, ACT_MIN, ACT_MAX)


def lut(v):
    return _grid(v, LUT_SCALE, LUT_MIN, LUT_MAX)


def quantize(params: dict, bits: int) -> dict:
    """Quantize float32 ``{"layers": [(w_x [3H, I], w_h [3H, H], b [3H])],
    "head": [H, O], "head_b": [O]}`` weights to ``bits``-bit codes."""
    qmax = float(2 ** (bits - 1) - 1)
    layers = []
    for w_x, w_h, b in params["layers"]:
        h = w_h.shape[-1]
        i = w_x.shape[-1]
        w = jnp.concatenate([w_x, w_h], axis=1).astype(jnp.float32)
        absmax = jnp.max(jnp.abs(w), axis=1)
        scale = jnp.where(absmax > 0, absmax, 1.0) / qmax
        codes = jnp.clip(jnp.round(w / scale[:, None]), -qmax, qmax)
        layers.append({
            "cx": codes[:, :i].reshape(3, h, i),
            "ch": codes[:, i:].reshape(3, h, h),
            "s": scale.reshape(3, h),
            "b": q88(b.astype(jnp.float32)).reshape(3, h),
        })
    return {"layers": layers, "head": params["head"].astype(jnp.float32),
            "head_b": params["head_b"].astype(jnp.float32)}


def _bf16(v):
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def _dot(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 at ``precision`` (see above)."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    ah, bh = _bf16(a), _bf16(b)
    out = jnp.einsum(spec, ah, bh, precision=HIGHEST)
    if precision == "high":
        out = (out + jnp.einsum(spec, ah, _bf16(b - bh), precision=HIGHEST)
               + jnp.einsum(spec, _bf16(a - ah), bh, precision=HIGHEST))
    elif precision != "default":
        raise ValueError(f"unknown precision {precision!r}")
    return out


def init_state(q: dict, batch: int) -> list:
    """Fresh per-layer state ``(x_hat, h_hat, h, M)``: all zeros."""
    out = []
    for lay in q["layers"]:
        h, i = lay["cx"].shape[1], lay["cx"].shape[2]
        z = functools.partial(jnp.zeros, dtype=jnp.float32)
        out.append((z((batch, i)), z((batch, h)), z((batch, h)),
                    z((batch, 4, h))))
    return out


def _layer(lay, st, x, theta_x, theta_h, prec):
    x_hat, h_hat, h, m = st
    raw_x = x - x_hat
    fx = jnp.abs(raw_x) >= theta_x
    dx = jnp.where(fx, raw_x, 0.0)
    x_hat = jnp.where(fx, x, x_hat)
    raw_h = h - h_hat
    fh = jnp.abs(raw_h) >= theta_h
    dh = jnp.where(fh, raw_h, 0.0)
    h_hat = jnp.where(fh, h, h_hat)
    px = _dot("bi,ghi->bgh", dx, lay["cx"], prec)
    ph = _dot("bi,ghi->bgh", dh, lay["ch"], prec)
    m_r = m[:, 0] + (px[:, 0] + ph[:, 0])
    m_u = m[:, 1] + (px[:, 1] + ph[:, 1])
    m_xc = m[:, 2] + px[:, 2]
    m_hc = m[:, 3] + ph[:, 2]
    s, b = lay["s"], lay["b"]
    r = lut(jax.nn.sigmoid(q88(b[0] + m_r * s[0])))
    u = lut(jax.nn.sigmoid(q88(b[1] + m_u * s[1])))
    c = lut(jnp.tanh(q88((b[2] + m_xc * s[2]) + r * (m_hc * s[2]))))
    h_new = q88((1.0 - u) * c + u * h)
    m_new = jnp.stack([m_r, m_u, m_xc, m_hc], axis=1)
    return (x_hat, h_hat, h_new, m_new), jnp.concatenate([fx, fh], axis=-1)


@functools.partial(jax.jit, static_argnames=("theta_x", "theta_h", "track",
                                             "precision"))
def _chunk(q, state, xs, ticks, union, counts, *, theta_x, theta_h, track,
           precision):
    """Run ``xs [T, B, I]`` from ``state``. ``ticks [T, B]`` names the
    served tick of each stream-frame in ``union``/``counts`` (an index past
    their end drops it)."""
    def body(carry, inp):
        st, un, ct = carry
        x, tk = inp
        v = q88(x)
        new_st, new_un, new_ct = [], list(un), list(ct)
        for li, lay in enumerate(q["layers"]):
            s, fired = _layer(lay, st[li], v, theta_x, theta_h, precision)
            new_st.append(s)
            v = s[2]
            if track:
                f = fired.astype(jnp.int32)
                new_un[li] = un[li].at[tk].max(f, mode="drop")
                new_ct[li] = ct[li].at[tk].add(jnp.sum(f, axis=-1),
                                               mode="drop")
        y = _dot("bh,ho->bo", v, q["head"], precision) + q["head_b"]
        return (new_st, new_un, new_ct), y

    (state, union, counts), ys = jax.lax.scan(body, (state, union, counts),
                                              (xs, ticks))
    return state, union, counts, ys


def replay(params: dict, streams: list, theta_x: float, theta_h: float, *,
           bits: int = 8, precision: str = "highest",
           serve_ticks: list | None = None, n_ticks: int = 0,
           batch: int = 128, chunk: int = 128):
    """Run every stream of ``streams`` (``[T_s, I]`` numpy frames) from a
    fresh state; return the head outputs (``[T_s, O]`` numpy per stream).

    With ``serve_ticks`` (one int array per stream, the tick in which each
    frame was served, or -1) and ``n_ticks``, also return per layer the
    ``[n_ticks, I_l + H]`` union of fired columns (0/1) and the ``[n_ticks]``
    count of fired stream-columns, over the streams served in each tick.
    """
    q = quantize(params, bits)
    n_layers = len(q["layers"])
    track = serve_ticks is not None and n_ticks > 0
    widths = [int(l["cx"].shape[2] + l["ch"].shape[2]) for l in q["layers"]]
    union = [jnp.zeros((max(n_ticks, 1), w), jnp.int32) for w in widths]
    counts = [jnp.zeros((max(n_ticks, 1),), jnp.int32) for _ in widths]
    i_dim = int(q["layers"][0]["cx"].shape[2])
    batch = max(1, min(batch, len(streams)))
    order = sorted(range(len(streams)), key=lambda s: -len(streams[s]))
    outs: list = [None] * len(streams)
    for g in range(0, len(order), batch):
        group = order[g:g + batch]
        t_max = len(streams[group[0]])
        t_pad = -(-t_max // chunk) * chunk
        xs = np.zeros((t_pad, batch, i_dim), np.float32)
        tk = np.full((t_pad, batch), max(n_ticks, 1), np.int32)
        for j, s in enumerate(group):
            f = streams[s]
            xs[:len(f), j] = f
            xs[len(f):, j] = f[-1]
            if track:
                st = np.asarray(serve_ticks[s])
                tk[:len(f), j] = np.where(st >= 0, st, n_ticks)
        state = init_state(q, batch)
        ys = []
        for t0 in range(0, t_pad, chunk):
            state, union, counts, y = _chunk(
                q, state, jnp.asarray(xs[t0:t0 + chunk]),
                jnp.asarray(tk[t0:t0 + chunk]), union, counts,
                theta_x=float(theta_x), theta_h=float(theta_h), track=track,
                precision=precision)
            ys.append(y)
        y = np.concatenate([np.asarray(c) for c in ys])
        for j, s in enumerate(group):
            outs[s] = y[:len(streams[s]), j]
    if not track:
        return outs
    return (outs, [np.asarray(u) for u in union][:n_layers],
            [np.asarray(c) for c in counts][:n_layers])
