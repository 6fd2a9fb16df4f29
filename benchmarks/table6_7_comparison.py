"""Paper Tables VI + VII: accelerator and edge-platform comparisons.

Table VI: Eq. 8 normalization of BBS / DeltaRNN / ESE / DeepRnn to the
EdgeDRNN operating point. Table VII: batch-1 latency of the 2L-768H network
at the paper's three Θ operating points on the EdgeDRNN model, against the
paper's measured platform numbers (quoted constants).
"""
from __future__ import annotations

from repro.core.perf_model import (EDGEDRNN, estimate_stack,
                                   normalized_batch1_throughput)
from repro.core.sparsity import GruDims

TABLE_VI = [
    # name, Γ_eff, W_index, paper bound (GOp/s)
    ("edgedrnn", 0.900, 0, 20.2),
    ("bbs", 0.875, 4, 10.7),
    ("deltarnn", 0.882, 0, 17.0),
    ("ese", 0.887, 4, 11.5),
    ("deeprnn", 0.0, 0, 2.0),
]

# paper Table VII measured latencies (us) on 2L-768H-class networks
TABLE_VII_PLATFORMS = [
    ("ncs2_fp16", 3588), ("jetson_nano_fp16", 4356),
    ("jetson_tx2_fp16", 2693), ("gtx1080_fp16", 484),
]

# paper Table VII: EdgeDRNN at three thresholds (Γ from Table II trends)
EDGEDRNN_POINTS = [
    ("theta_0x00", 0.333, 0.550, 2633),   # ~2x natural sparsity
    ("theta_0x08", 0.60, 0.72, 1673),
    ("theta_0x40", 0.870, 0.916, 536),
]


def run() -> list[str]:
    lines = []
    for name, geff, widx, paper in TABLE_VI:
        if geff:
            got = normalized_batch1_throughput(geff, widx) / 1e9
        else:
            from repro.core.perf_model import AcceleratorSpec
            got = AcceleratorSpec(w_index_bits=widx).mem_bounded_peak_ops / 1e9
        lines.append(f"table6.{name},0,norm_tput={got:.1f}GOp/s "
                     f"paper<={paper} err={abs(got - paper) / paper * 100:.0f}%")

    dims = GruDims(40, 768, 2)
    for name, gdx, gdh, paper_us in EDGEDRNN_POINTS:
        est = estimate_stack(dims, gdx, gdh, EDGEDRNN)
        lines.append(
            f"table7.edgedrnn_{name},{est.latency_s * 1e6:.0f},"
            f"paper_measured={paper_us}us "
            f"eff_tput={est.throughput_ops / 1e9:.1f}GOp/s")
    for name, us in TABLE_VII_PLATFORMS:
        lines.append(f"table7.{name},{us},paper-quoted measured latency")
    best = estimate_stack(dims, 0.870, 0.916, EDGEDRNN).latency_s * 1e6
    lines.append(
        f"table7.headline,0,edgedrnn({best:.0f}us) ~ gtx1080(484us) and "
        f"5x faster than the edge platforms (paper Sec. V-D)")
    return lines


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
