"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); a per-layer metric is the
reader ``bench/metrics/<name>.py``; a configuration names its plain
reference, ``bench/reference/<reference>.py``, and its model family,
``bench/models/<model>.py`` (``gru`` where it names none); a mix's frames
of a kind the generator does not build in come from
``bench/traffic/kinds/<kind>.py``. Adding any of them is adding a file:
nothing here changes.

A family module provides ``weights(config, seed)``, ``program(config,
w)``, ``task(config) -> (task, ThresholdPolicy)``,
``dense_weights(config)``, ``frame_spec(config) -> (shape, dtype)`` of one
stream-frame, ``reference_kwargs(config)`` (what the reference's
``replay`` takes from the configuration besides the weights and frames),
``controls`` (name -> the reference's keyword arguments of each control)
and ``tiny(config, **kw)``, the configuration at the CPU rehearsal's
size. A reference provides ``replay(w, streams, **kwargs)``: the outputs
of every stream of frames, and with ``serve_ticks`` and ``n_ticks``
``(outputs, union, counts)``, the fired columns of each served tick (see
``bench/reference/deltagru_q8.py``). A frame kind provides ``frames(rng,
n, config, params) -> [n, *shape]`` of the family's ``frame_spec``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    run_seconds: int

    def reference(self):
        return load_module(os.path.join(
            BENCH, "reference", self.config["reference"] + ".py"),
            "bench_reference_" + self.config["reference"])

    def family(self):
        return load_family(self.config)

    def readers(self) -> dict:
        """``name -> module`` of this cell's per-layer readers."""
        return {m["name"]: load_reader(m["name"]) for m in self.per_layer}


def load_reader(name: str):
    """The reader of the per-layer metric ``name``."""
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


@functools.cache
def _family(name: str):
    return load_module(os.path.join(BENCH, "models", name + ".py"),
                       "bench_model_" + name)


def load_family(config: dict):
    """The model family module of a configuration."""
    return _family(config.get("model", "gru"))


@functools.cache
def load_kind(name: str):
    """The frame kind module ``name``."""
    return load_module(os.path.join(BENCH, "traffic", "kinds", name + ".py"),
                       "bench_kind_" + name.replace("-", "_"))


def workloads() -> list:
    """The names of the cells of ``BENCHMARK.json``, in its order."""
    return [w["name"] for w in
            _json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: str | None = None) -> Cell:
    spec = _json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = _json(os.path.join(ROOT, cfg["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        run_seconds=int(spec["run_seconds"]))


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))["chips"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)}); add its peaks "
                       "with their source")
    return table[device_kind]
