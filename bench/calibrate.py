"""Readings behind a cell's limits and offered load, many seeds in one
process (the set-up is paid once per seed, not once per process).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--controls int4,high,default] [--rates 40,50,60]

For each rate (default: the mix's own) and seed, one run of the cell as
``bench/run.py`` makes it, with tracing off, printed as one JSON line:
the end-to-end metrics, the queue at the window's open and close, the
numbers compared with their limits and, with ``--controls``, the same
numbers for each named control of the configuration's model family (its
``controls``: for ``gru`` the reference at a lower weight width or matmul
precision) put in the program's place.
The limits of ``bench/configs`` and the rates of ``bench/traffic``
were set from these lines. Needs the cell's TPU chips, like ``run.py``.
"""
import argparse
import copy
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import main as hm  # noqa: E402
from harness import spec  # noqa: E402


def run(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--rates", default=None)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    import repro.serve.engine  # noqa: F401  (the system under test)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: {a.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    hm._cache_dir()
    prec = cell.config.get("matmul_precision")
    if prec:
        jax.config.update("jax_default_matmul_precision", prec)
    known = cell.family().controls
    controls = [c for c in a.controls.split(",") if c]
    if set(controls) - set(known):
        print(f"calibrate: the family has no control "
              f"{sorted(set(controls) - set(known))}; it has {sorted(known)}",
              file=sys.stderr)
        return 2
    rates = [None] if not a.rates else [float(r) for r in a.rates.split(",")]
    for rate in rates:
        c = copy.copy(cell)
        if rate is not None:
            c.traffic = copy.deepcopy(cell.traffic)
            c.traffic["arrivals"]["rate_per_s"] = rate
        for seed in (int(s) for s in a.seeds.split(",")):
            t0 = time.perf_counter()
            res = hm.run_cell(c, seed, a.seconds, False, t0,
                              controls=controls, log=lambda *x, **k: None)
            line = {"workload": a.workload, "seed": seed, "rate": rate,
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "queued": res.get("queued"),
                    "correct": res["correct"],
                    "compared": {k: v["value"]
                                 for k, v in res["compared"].items()},
                    "control": res.get("control"),
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                    "run_s": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
