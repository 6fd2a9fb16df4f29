"""The command refuses a machine without a TPU, and the benchmark's own
files alone, without the program beside them, do not run."""
import json
import os
import shutil
import subprocess
import sys

from harness import spec


def _run(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gru768-q8-b1-speech", "--seed", str(2**31 + 3), "--seconds", "1",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_tpu():
    p = _run(spec.ROOT, "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        cell.reference()
        cell.family()
        for name, mod in cell.readers().items():
            entry = next(m for m in b["per_layer"] if m["name"] == name)
            assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
                entry["layer"], entry["unit"], entry["moves"])


def test_benchmark_json_keeps_to_the_contract_limits():
    import re
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = b["end_to_end"] + b["per_layer"]
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in b["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
    for c in b["configs"]:
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    assert all(m["bound"] <= 0.25 for m in b["end_to_end"])
    assert 1 <= b["run_seconds"] <= 51
    # a full check of the largest benchmark fits its time
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_peak_table_is_keyed_by_device_kind():
    import pytest
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 1.97e14
    assert p["int8_ops_per_s"] == 3.93e14
    assert p["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks("cpu")
