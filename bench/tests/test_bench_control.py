"""The check has to fail what it is there to catch. At a tiny width on
the CPU: the controls (the reference in the program's place with int4
weights, the width below the configuration's int8, or with its products
at the matmul precisions below the configuration's ``highest``) and the
faults a serving cell can have, planted in the timed path underneath a
whole run: a step that returns its state unchanged, half of the batch
left out, and an answer altered where it is produced."""
import time

import jax.numpy as jnp
import pytest

from harness import main, spec

PEAKS = spec.peaks("TPU v5 lite")
CELLS = {n: spec.load_cell(n) for n in spec.workloads()}


def _run(cell, **kw):
    return main.run_cell(cell, 2**31 + 29, 1.0, False, time.perf_counter(),
                         peaks=PEAKS, log=lambda *a, **k: None, **kw)


@pytest.mark.parametrize("name,control", [
    (n, c) for n, cell in CELLS.items() for c in sorted(cell.family().controls)])
def test_control_is_not_correct(tiny_cell, name, control, tmp_path):
    """Each control of the cell's model family, put in the program's
    place, reads above the limit committed for its configuration."""
    limit = CELLS[name].config["limits"]["out_gap"]
    res = _run(tiny_cell(name), workdir=str(tmp_path), controls=[control])
    assert res["correct"]
    assert res["control"][control]["out_gap"] > limit


def _state_unchanged(monkeypatch):
    from repro.core.program import DeltaProgram
    step = DeltaProgram.step

    def bad(self, state, x, tx=0.0, th=0.0):
        y, _, deltas = step(self, state, x, tx, th)
        return y, state, deltas
    monkeypatch.setattr(DeltaProgram, "step", bad)


def _half_batch(monkeypatch):
    from repro.core.program import DeltaProgram
    step = DeltaProgram.step

    def bad(self, state, x, tx=0.0, th=0.0):
        y, new, deltas = step(self, state, x, tx, th)
        return y.at[y.shape[0] // 2:].set(0.0), new, deltas
    monkeypatch.setattr(DeltaProgram, "step", bad)


def _answer_altered(monkeypatch):
    from repro.serve.engine import DeltaStreamEngine
    step = DeltaStreamEngine.step
    calls = {"n": 0}

    def bad(self, x):
        out = step(self, x)
        calls["n"] += 1
        if calls["n"] == 150:       # one frame of the run, as it is produced
            out = out.at[..., 0].add(jnp.asarray(1.0, out.dtype))
        return out
    monkeypatch.setattr(DeltaStreamEngine, "step", bad)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
# half of the batch can be left out only where a tick serves many streams
CASES = [(n, f) for n, cell in CELLS.items() for f in FAULTS
         if f != "half_batch" or cell.traffic["entry"] != "engine"]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(tiny_cell, name, fault, monkeypatch, tmp_path):
    cell = tiny_cell(name)
    FAULTS[fault](monkeypatch)
    res = _run(cell, workdir=str(tmp_path))
    assert not res["correct"], (fault, res["compared"])
