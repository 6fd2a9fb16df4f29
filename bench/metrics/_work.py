"""The work a delta step needs, counted from the columns Eq. 2 fired.

Shared by the kernel-roofline reader. Counts are of the algorithm, not of
an implementation: a fired input or hidden column of a layer needs its
``gates * H`` weights once per tick, however many streams of the tick fired
it (the union), and ``2 * gates * H`` operations per stream that fired it.
Only real columns count: the padding of the input to the kernel's block is
not work the algorithm needs.
"""
from __future__ import annotations

import numpy as np


def needed(union: list, counts: list, gates: int, hidden: int,
           weight_bits: int):
    """Per tick and layer: ``(weights, bytes, ops)`` arrays of shape
    ``[ticks, layers]``.

    ``union[l]`` is ``[ticks, I_l + H]`` (1 where a column fired in any
    stream served in the tick), ``counts[l]`` is ``[ticks]`` (stream-columns
    fired). ``weights`` counts the unpadded weights of the fired columns.
    """
    rows = gates * hidden
    w = np.stack([np.asarray(u).sum(axis=1) for u in union], axis=1) * rows
    ops = 2.0 * rows * np.stack([np.asarray(c) for c in counts], axis=1)
    return w, w * (weight_bits / 8.0), ops


def least_seconds(union, counts, gates, hidden, weight_bits,
                  peak_ops: float, peak_bytes: float) -> float:
    """The least time the chip could take for the traced ticks: per tick
    and layer the larger of operations over peak and bytes over peak."""
    _, b, ops = needed(union, counts, gates, hidden, weight_bits)
    return float(np.sum(np.maximum(ops / peak_ops, b / peak_bytes)))
