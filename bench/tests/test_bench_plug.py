"""A configuration of a new model family, with frames of a new kind, is
brought as new files and entries of ``BENCHMARK.json`` alone.

The fixture under ``data/plug/`` holds the files such a change adds: a
family module (the delta-LSTM of ``core/deltalstm.py`` in float32), its
plain reference, a configuration at a tiny width, a frame kind with a
generator of its own (one-hot symbols) and a mix that uses it. The test
lays them over a copy of ``bench/`` beside a ``BENCHMARK.json`` that adds
the configuration and one cell, checks that no file the copy already had
changed, and runs the copy's own tests of the new cell, which find it in
``BENCHMARK.json``: the whole run on the CPU, untraced and traced, with
``correct`` true; the family's control and the engine cell's faults with
``correct`` false."""
import filecmp
import json
import os
import re
import shutil
import subprocess
import sys

from harness import spec

PLUG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "plug")


def _checkout(root: str) -> str:
    """The copy with the fixture laid over it; returns the new cell."""
    shutil.copytree(spec.BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = os.path.join(PLUG, "bench")
    for d, _, files in os.walk(src):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), src)
            dest = os.path.join(root, "bench", rel)
            assert not os.path.exists(dest), rel       # new files only
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            shutil.copy(os.path.join(d, f), dest)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(PLUG, "entries.json")) as f:
        add = json.load(f)
    b["configs"] += add["configs"]
    b["workloads"] += add["workloads"]
    (cell,) = [w["name"] for w in add["workloads"]]
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in add["reported_by"]:
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f, indent=1)
    return cell


def _unchanged(root: str) -> None:
    """Every file of ``bench/`` is in the copy as it was."""
    for d, dirs, files in os.walk(spec.BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), spec.BENCH)
            assert filecmp.cmp(os.path.join(d, f),
                               os.path.join(root, "bench", rel),
                               shallow=False), rel


def test_new_family_and_frame_kind_need_only_new_files(tmp_path):
    root = str(tmp_path / "checkout")
    cell = _checkout(root)
    _unchanged(root)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(spec.ROOT, "src")}
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", cell, "bench/tests/test_bench_cells.py",
         "bench/tests/test_bench_traced.py",
         "bench/tests/test_bench_control.py"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    out = p.stdout[-4000:] + p.stderr[-4000:]
    assert p.returncode == 0, out
    # one run, one traced run, the control, two faults
    assert re.search(r"\b5 passed\b", p.stdout), out
    assert "failed" not in p.stdout, out
