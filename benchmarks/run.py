"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

``--quick`` runs a reduced kernel-suite pass (small dims, no JSON writes)
suitable for CI; pair it with ``python -m benchmarks.check_regression``
(or ``make check-regression``) to gate wall-time/bytes against the
committed ``BENCH_*.json`` baselines.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def _suites(quick: bool):
    from benchmarks import (fig9_threshold_sweep, fig10_11_dual_threshold,
                            fig13_batch_sweep, fig14_15_latency_traces,
                            kernel_bench, lm_delta_bench, soak_serving,
                            table2_perfmodel, table6_7_comparison)
    if quick:
        # the LSTM and lm-delta quick passes are their own `make ci`
        # stages (`python -m benchmarks.kernel_bench --lstm --quick`,
        # `python -m benchmarks.lm_delta_bench --quick`), so they are
        # NOT repeated here — `make ci` would run them twice otherwise
        return [("kernel_quick", kernel_bench.run_quick)]
    suites = [
        ("table2", table2_perfmodel.run),
        ("table6_7", table6_7_comparison.run),
        ("fig13", fig13_batch_sweep.run),
        ("kernel", kernel_bench.run),
        # rewrites BENCH_deltagru_q4.json + BENCH_deltalstm_q4.json (int4
        # nibble-packed ladder, both cells); its quick pass is its own
        # `make ci` stage (`python -m benchmarks.kernel_bench --q4
        # --quick`), so it is NOT repeated in --quick here
        ("kernel_q4", kernel_bench.run_q4),
        ("fig14_15", fig14_15_latency_traces.run),
        ("fig9", fig9_threshold_sweep.run),
        ("fig10_11", fig10_11_dual_threshold.run),
        # rewrites BENCH_lm_delta.json (delta-ized RWKV6 / RG-LRU sweep);
        # its quick pass is its own `make ci` stage
        ("lm_delta", lm_delta_bench.run),
        # rewrites BENCH_soak.json; the CI spelling of the quick pass is
        # its own `make ci` stage (`python -m benchmarks.soak_serving
        # --quick`), so it is NOT repeated in --quick here
        ("soak", soak_serving.run),
    ]
    from benchmarks import roofline
    # kernel_bench.run writes BENCH_deltagru_q8.json and
    # BENCH_deltalstm_q8.json above, so both delta-RNN rooflines always
    # see fresh records
    suites.append(("roofline_deltagru", roofline.run_deltagru))
    suites.append(("roofline_deltalstm", roofline.run_deltalstm))
    # the LM roofline runs only when dry-run artifacts exist
    if os.path.isdir(roofline.ART_DIR) and os.listdir(roofline.ART_DIR):
        suites.append(("roofline", roofline.run))
    return suites


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced CI pass (small dims, no baseline writes)")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in _suites(args.quick):
        t0 = time.perf_counter()
        try:
            for line in fn():
                print(line)
            dt = time.perf_counter() - t0
            print(f"{name}.suite_wall,{dt * 1e6:.0f},suite wall time")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}.FAILED,0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    # machine-readable perf-trajectory records written by the suites
    from benchmarks.fig13_batch_sweep import BENCH_BATCH_JSON
    from benchmarks.kernel_bench import (BENCH_JSON, BENCH_LSTM_JSON,
                                         BENCH_LSTM_Q4_JSON,
                                         BENCH_LSTM_Q8_JSON, BENCH_Q4_JSON,
                                         BENCH_Q8_JSON)
    from benchmarks.lm_delta_bench import BENCH_LM_DELTA_JSON
    for p in (BENCH_JSON, BENCH_Q8_JSON, BENCH_Q4_JSON, BENCH_LSTM_JSON,
              BENCH_LSTM_Q8_JSON, BENCH_LSTM_Q4_JSON, BENCH_BATCH_JSON,
              BENCH_LM_DELTA_JSON):
        if os.path.exists(p):
            print(f"bench_json,0,{p}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
