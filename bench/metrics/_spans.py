"""Host time per tick in one of the program's own spans.

Shared by the readers of the engine's and the batcher's inner spans. The
program writes them as ``jax.profiler.TraceAnnotation``s, so they sit in
the profiler's trace on the device ops' clock (``r.trace["host"]``:
``[name, start_ns, duration_ns]``), beside the harness's ``bench.tick``.
"""
from __future__ import annotations


def ms_per_tick(r, name: str, zero_beside: str | None = None):
    """Milliseconds of the events named ``name`` that start in the traced
    window ``[lo, hi)``, over the ``bench.tick`` events that start there.

    ``None`` without a trace or a tick in it, and where no event of that
    name was found, so a renamed span (or a program without it) reads as
    missing rather than as 0. Where ``zero_beside`` names a span that was
    found, the program writes this layer's spans and the missing one took
    no time: 0.0.
    """
    t = r.trace
    if t is None:
        return None
    lo, hi = t["lo"], t["hi"]
    inside = [(n, d) for n, s, d in t["host"] if lo <= s < hi]
    ticks = sum(n == "bench.tick" for n, _ in inside)
    if not ticks:
        return None
    durs = [d for n, d in inside if n == name]
    if not durs and not (zero_beside
                         and any(n == zero_beside for n, _ in inside)):
        return None
    return 1e-6 * float(sum(durs)) / ticks
