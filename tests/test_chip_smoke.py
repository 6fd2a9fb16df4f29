"""``chip_smoke.py`` rehearsed on CPU at a tiny width.

The script's phases are plain functions of the model size: here they run
the same serving path (program -> engine -> batcher / router + fleet) on
a 2-layer GRU of width 16, with the Pallas kernels in interpret mode, and
must agree with their references exactly as on the chip. The script's
``main`` must refuse a machine without a TPU, and the script alone,
without the repository next to it, must fail.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.models.gru_rnn import GruTaskConfig, init_gru_model  # noqa: E402
from repro.serve.loadgen import poisson_arrivals  # noqa: E402

TASK = GruTaskConfig(8, 16, 2, 3)


@pytest.fixture(scope="module")
def params():
    return init_gru_model(jax.random.PRNGKey(0), TASK)


@pytest.mark.parametrize("interpret", [None, True])
@pytest.mark.parametrize("backend", ["fused", "fused_q8", "fused_q4"])
def test_stream_phase(params, backend, interpret):
    frames = chip_smoke.speech_like_frames(np.random.default_rng(1), 14, 8)
    r = chip_smoke.phase_stream(params, TASK, backend, frames,
                                interpret=interpret)
    assert r["ok"], r
    assert r["frames"] == 14 and r["finite"]
    if backend != "fused":
        assert r["max_abs_diff"] == 0.0
    json.dumps(r)   # the script prints each phase as one JSON line


def test_batcher_phase_routes_to_tile_and_matches_oracle(params):
    rng = np.random.default_rng(2)
    streams = [chip_smoke.speech_like_frames(rng, int(n), 8)
               for n in rng.integers(3, 9, size=5)]
    r = chip_smoke.phase_batcher(params, TASK, streams, n_streams=4,
                                 interpret=True)
    assert r["ok"], r
    assert r["backend"] == "fused_q8" and r["weight_fetch"] == "tile"
    assert r["max_abs_diff"] == 0.0


def test_fleet_phase_on_four_devices(params):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (tests/conftest.py forces 8)")
    arrivals = poisson_arrivals(12, rate_per_tick=3.0, min_len=3,
                                max_len=8, input_size=8, seed=3)
    r = chip_smoke.phase_fleet(params, TASK, arrivals, n_devices=4,
                               streams_per_shard=2)
    assert r["ok"], r
    assert r["mesh"] == {"data": 4, "model": 1}
    assert r["parity_ok"] == r["completed"] == 12


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--fleet"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
