"""Paper Table II: latency/throughput of the 6 DeltaGRU network sizes.

Reproduces the paper's Est. columns exactly from Eq. 7 (at the paper's
measured sparsity), and re-derives the throughput on a *trained* tiny
DeltaGRU's measured sparsity to show the model working end-to-end on live
numbers.
"""
from __future__ import annotations

import time

from repro.core.perf_model import EDGEDRNN, estimate_stack
from repro.core.sparsity import GruDims

# (name, I, H, L, Γ_dx, Γ_dh, paper_est_lat_us, paper_est_tput_gops)
PAPER_ROWS = [
    ("1L-256H", 40, 256, 1, 0.256, 0.900, 43.3, 10.5),
    ("2L-256H", 40, 256, 2, 0.789, 0.891, 91.6, 13.6),
    ("1L-512H", 40, 512, 1, 0.256, 0.895, 129.8, 13.1),
    ("2L-512H", 40, 512, 2, 0.855, 0.912, 262.9, 18.4),
    ("1L-768H", 40, 768, 1, 0.256, 0.913, 224.8, 16.6),
    ("2L-768H", 40, 768, 2, 0.870, 0.916, 541.6, 19.9),
]


def run() -> list[str]:
    lines = []
    t0 = time.perf_counter()
    for name, i, h, l, gdx, gdh, lat_p, tput_p in PAPER_ROWS:
        est = estimate_stack(GruDims(i, h, l), gdx, gdh, EDGEDRNN)
        lat = est.latency_s * 1e6
        tput = est.throughput_ops / 1e9
        lines.append(
            f"table2.{name},{lat:.1f},"
            f"est_tput={tput:.1f}GOp/s paper_est=({lat_p}us {tput_p}GOp/s) "
            f"err=({abs(lat - lat_p) / lat_p * 100:.1f}% "
            f"{abs(tput - tput_p) / tput_p * 100:.1f}%)")
    us = (time.perf_counter() - t0) * 1e6 / len(PAPER_ROWS)
    lines.append(f"table2.model_eval,{us:.1f},per-row perf-model eval time")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
