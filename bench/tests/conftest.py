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


def tiny(cell, slots=8, rate=20.0, max_len=300, **kw):
    """A cell's files at a tiny size and load, for the CPU: the
    configuration as its model family's ``tiny`` gives it (``kw`` goes
    there), the traffic at a few slots and short streams."""
    from harness import spec
    cell = copy.copy(cell)
    cell.config = spec.load_family(cell.config).tiny(cell.config, **kw)
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
