"""Rehearsal of the benchmark on the CPU: tiny widths, short windows.

The harness modules live under ``bench/`` and the program under ``src/``;
the fleet path needs four devices, so eight virtual CPU devices are
forced (the same number the repository's own tests use).
"""
import copy
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


# The committed limits hold the program to the reference as the chip runs
# both: bit for bit there (0.0 on every run). On the CPU, XLA sums the
# float32 head of a batch-1 step in another order than the reference's
# scan, a few units in the last place (up to 5.5e-7 of the outputs' RMS
# at width 16). The rehearsal holds the program to this instead; every
# planted fault reads orders of magnitude above it.
CPU_OUT_GAP = 1e-5


def tiny(cell, hidden=16, slots=8, rate=20.0, max_len=300):
    """A cell's files at a tiny width and load, for the CPU."""
    cell = copy.copy(cell)
    cell.config = {**cell.config, "hidden_size": hidden,
                   "limits": {"out_gap": CPU_OUT_GAP}}
    t = copy.deepcopy(cell.traffic)
    if t["entry"] != "engine":
        t["slots"] = slots
        t["arrivals"]["rate_per_s"] = rate
        t["arrivals"]["length"]["max"] = max_len
    if t["loop"] == "closed":
        t["max_frames_per_s"] = 4000
    cell.traffic = t
    return cell


@pytest.fixture
def tiny_cell():
    from harness import spec
    return lambda name, **kw: tiny(spec.load_cell(name), **kw)


@pytest.fixture
def shrink():
    return tiny
