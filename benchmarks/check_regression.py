"""CI perf gate: fresh kernel-bench pass vs the committed BENCH baselines.

Re-runs the sequence-level backend shootouts at the *same configuration*
the committed ``BENCH_deltagru_seq.json`` / ``BENCH_deltagru_q8.json`` /
``BENCH_deltalstm_seq.json`` / ``BENCH_deltalstm_q8.json`` records were
produced with (dims are read from the baseline's ``config`` block, so the
gate always compares apples to apples), then:

* fails on a > ``MAX_WALL_RATIO`` (1.5x) wall-time regression of the fused
  paths (``fused``, ``fused_q8``) at any measured theta — these are the
  inference hot paths the perf trajectory is about;
* fails if the *modeled bytes-streamed per step* of any backend moved —
  exactly on the baseline's machine class (the model is deterministic
  there), within 2% elsewhere (float threshold crossings in the synthetic
  input can flip a near-boundary fired block across machine classes); any
  larger drift is a real layout / compaction / packing change that must be
  intentional (regenerate the baseline in the same PR);
* fails if the quantized LSTM record's matched-firing invariant breaks:
  ``fused_q8`` must stream EXACTLY 0.25x the fp32 fused bytes over the
  same fired-column set (1 byte/weight vs 4) — checked on the fresh
  record's matched-count fields, so it holds on every machine class;
* fails if the int4 records' matched-firing ladder breaks: ``fused_q4``
  must stream EXACTLY 0.5x the ``fused_q8`` bytes (two nibble codes per
  streamed byte) and 0.125x the fp32 fused bytes over the same
  fired-column set — checked on the fresh ``BENCH_deltagru_q4.json`` /
  ``BENCH_deltalstm_q4.json`` records' unrounded matched-count fields,
  so it holds on every machine class; the q4 re-runs themselves
  hard-fail on fused_q4-kernel-vs-oracle bit drift and on dense drift
  beyond 2x the int8 budget;
* the LSTM re-runs themselves hard-fail on parity drift (fused vs dense
  in fp32; fused_q8 Pallas kernel vs its jnp oracle, bit-exact, plus the
  quantization-budget rail vs the fp32 dense reference) — those
  assertions are folded into the failure list;
* gates the batched stream-tile sweep (``BENCH_batch_sweep.json``) the
  same way: <= 1.5x wall per (backend, batch) row on the tile backends
  (``fused_batch``, ``fused_q8_batch``), tile-bytes model exact on the
  baseline machine class / 2% elsewhere, and a machine-independent HARD
  invariant evaluated on the fresh record's unrounded matched-firing
  fields: a replicated tile's weight fetch must EQUAL the batch-1 fetch
  (union compaction collapses identical streams), so weight bytes per
  stream per step at B=8 is *strictly below* the batch-1 baseline at
  matched firing — the whole point of serving a tile per weight pass;
* gates the resilient-serving soak (``BENCH_soak.json``): the chaos run
  is seeded and every policy trigger is tick-counted, so its completed/
  shed/rejected/quarantined/recovered counts, restart count, bitwise
  parity count, Θ trajectory peak and engine lifetime steps must
  reproduce EXACTLY on any machine (the soak re-run itself hard-fails if
  any completed stream's outputs drift bitwise from a clean reference or
  any quarantine fails to recover); its p99 steady-state tick wall is
  gated at 1.5x on the baseline's machine class;
* gates the distributed-fabric load run (``BENCH_fabric.json``) IN A
  SUBPROCESS (``python -m benchmarks.loadgen_fabric --gate``): the
  fabric bench forces ``--xla_force_host_platform_device_count=8``
  before jax initializes, which must not leak into this process's
  already-initialized backend, so the gate runs isolated and folds its
  exit code in here. Same split as the soak: the router/loadgen event
  history is tick-counted and seeded (counts reproduce EXACTLY on any
  machine, with the fabric re-run hard-failing on bitwise parity drift
  of any completed stream — through an elastic scale-down — or a
  non-closing conservation book), and only the p99 tick wall is
  machine-bound (1.5x, same machine class);
* wall-time comparison is only meaningful on the machine class that
  produced the baseline: when ``device``/``machine`` metadata disagree the
  gate downgrades wall checks to a warning and keeps the bytes gate.

Usage: ``PYTHONPATH=src python -m benchmarks.check_regression`` (exit code
1 on regression), or ``make check-regression``. Fresh numbers are NOT
written over the baselines; regenerate those with the full
``python -m benchmarks.run``.
"""
from __future__ import annotations

import json
import os
import sys

MAX_WALL_RATIO = 1.5
GATED_BACKENDS = ("fused", "fused_q8", "fused_q4")


def _load(path):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _row_key(row):
    return (row["theta"], row["backend"])


def _comparable(base_cfg, fresh_cfg):
    """Same measurement environment: numbers are only strictly comparable
    when the device class, machine, and compiler (jax/XLA version) all
    match — a same-machine jax upgrade changes both codegen (wall time)
    and last-ulp float behaviour (bytes-model inputs)."""
    return all(base_cfg.get(k) == fresh_cfg.get(k)
               for k in ("device", "machine", "jax_version"))


def _gate_walltime(name, base, fresh, failures):
    base_rows = {_row_key(r): r for r in base["rows"]}
    for row in fresh["rows"]:
        if row["backend"] not in GATED_BACKENDS:
            continue
        b = base_rows.get(_row_key(row))
        if b is None:
            continue
        ratio = row["us_per_step"] / max(b["us_per_step"], 1e-9)
        line = (f"{name} {row['backend']} theta={row['theta']}: "
                f"{b['us_per_step']:.1f} -> {row['us_per_step']:.1f} us/step "
                f"({ratio:.2f}x)")
        if ratio > MAX_WALL_RATIO:
            failures.append(f"WALL REGRESSION {line}")
        else:
            print(f"ok   {line}")


def _gate_bytes(name, base, fresh, failures, strict=True):
    """Exact on the baseline's machine class; elsewhere allow the small
    drift that last-ulp float differences in the synthetic input /
    threshold-crossing chain can cause in fired-block counts (the model
    itself is deterministic, but its *inputs* are computed in floats)."""
    rel_tol = 0.0 if strict else 0.02
    base_rows = {_row_key(r): r for r in base["rows"]}
    for row in fresh["rows"]:
        b = base_rows.get(_row_key(row))
        if b is None or "bytes_per_step" not in b:
            continue
        drift = abs(row["bytes_per_step"] - b["bytes_per_step"])
        if drift > rel_tol * max(b["bytes_per_step"], 1.0):
            failures.append(
                f"BYTES MODEL DRIFT {name} {row['backend']} "
                f"theta={row['theta']}: {b['bytes_per_step']} -> "
                f"{row['bytes_per_step']} (regenerate baseline if "
                "intentional)")
        else:
            print(f"ok   {name} {row['backend']} theta={row['theta']}: "
                  f"bytes/step={row['bytes_per_step']:.0f}")


def _gate_q8_matched_bytes(name, fresh, failures):
    """EXACT invariant of the quantized bytes model: at matched firing
    counts, ``fused_q8`` streams precisely 0.25x the fp32 fused bytes (1
    byte/weight vs 4 over the identical fired-column set). Evaluated on
    the fresh record's matched-count fields — stored UNROUNDED, because
    scaling a float sum by a power of two is exact while independently
    rounded copies need not satisfy the ratio — so float threshold
    crossings cannot blur it; any deviation is a real weight-width or
    row-extent bug in the bytes model."""
    for row in fresh["rows"]:
        if row["backend"] != "fused_q8":
            continue
        q8m = row.get("q8_bytes_matched_fp32")
        fm = row.get("fused_bytes_matched_fp32")
        if q8m is None or fm is None:
            failures.append(
                f"Q8 MATCHED BYTES {name} theta={row['theta']}: record is "
                "missing the matched-firing fields")
            continue
        if q8m != 0.25 * fm:
            failures.append(
                f"Q8 MATCHED BYTES {name} theta={row['theta']}: fused_q8 "
                f"streams {q8m} B/step vs fused {fm} at matched firing "
                f"(expected exactly 0.25x = {0.25 * fm})")
        else:
            print(f"ok   {name} theta={row['theta']}: fused_q8 bytes = "
                  f"0.25x fused at matched firing ({q8m:.0f} B/step)")


def _gate_q4_matched_bytes(name, fresh, failures):
    """EXACT invariant of the nibble-packed bytes model: at matched firing
    counts, ``fused_q4`` streams precisely 0.5x the ``fused_q8`` bytes
    (two int4 codes per byte vs one int8 code) and 0.125x the fp32 fused
    bytes, over the identical fired-column set. Evaluated on the fresh
    record's UNROUNDED matched-count fields, so it holds on every machine
    class; any deviation is a real weight-width or packing bug in the
    bytes model."""
    for row in fresh["rows"]:
        if row["backend"] != "fused_q4":
            continue
        q4m = row.get("q4_bytes_matched_fp32")
        q8m = row.get("q8_bytes_matched_fp32")
        fm = row.get("fused_bytes_matched_fp32")
        if q4m is None or q8m is None or fm is None:
            failures.append(
                f"Q4 MATCHED BYTES {name} theta={row['theta']}: record is "
                "missing the matched-firing fields")
            continue
        if q4m != 0.5 * q8m:
            failures.append(
                f"Q4 MATCHED BYTES {name} theta={row['theta']}: fused_q4 "
                f"streams {q4m} B/step vs fused_q8 {q8m} at matched "
                f"firing (expected exactly 0.5x = {0.5 * q8m})")
        elif q4m != 0.125 * fm:
            failures.append(
                f"Q4 MATCHED BYTES {name} theta={row['theta']}: fused_q4 "
                f"streams {q4m} B/step vs fp32 fused {fm} at matched "
                f"firing (expected exactly 0.125x = {0.125 * fm})")
        else:
            print(f"ok   {name} theta={row['theta']}: fused_q4 bytes = "
                  f"0.5x fused_q8 = 0.125x fused at matched firing "
                  f"({q4m:.0f} B/step)")


def _batch_row_key(row):
    return (row["backend"], row["batch"])


def _gate_batch_walltime(base, fresh, failures):
    base_rows = {_batch_row_key(r): r for r in base["rows"]}
    for row in fresh["rows"]:
        b = base_rows.get(_batch_row_key(row))
        if b is None:
            continue
        ratio = row["us_per_step"] / max(b["us_per_step"], 1e-9)
        line = (f"batch {row['backend']} B={row['batch']}: "
                f"{b['us_per_step']:.1f} -> {row['us_per_step']:.1f} us/step "
                f"({ratio:.2f}x)")
        if ratio > MAX_WALL_RATIO:
            failures.append(f"WALL REGRESSION {line}")
        else:
            print(f"ok   {line}")


def _gate_batch_bytes(base, fresh, failures, strict=True):
    rel_tol = 0.0 if strict else 0.02
    base_rows = {_batch_row_key(r): r for r in base["rows"]}
    for row in fresh["rows"]:
        b = base_rows.get(_batch_row_key(row))
        if b is None:
            continue
        drift = abs(row["tile_bytes_per_step"] - b["tile_bytes_per_step"])
        if drift > rel_tol * max(b["tile_bytes_per_step"], 1.0):
            failures.append(
                f"BYTES MODEL DRIFT batch {row['backend']} "
                f"B={row['batch']}: {b['tile_bytes_per_step']} -> "
                f"{row['tile_bytes_per_step']} (regenerate baseline if "
                "intentional)")
        else:
            print(f"ok   batch {row['backend']} B={row['batch']}: "
                  f"tile bytes/step={row['tile_bytes_per_step']:.0f}")


def _gate_batch_matched_bytes(fresh, failures):
    """HARD machine-independent invariant of the tile fetch, on the fresh
    record's UNROUNDED matched-firing fields: when one walk is replicated
    across the tile, union compaction collapses the identical streams, so
    the tile fetch must EQUAL the batch-1 fetch exactly — and bytes per
    stream per step at B=8 must then sit strictly below the batch-1
    baseline (it is exactly batch1/8). Any violation is a compaction or
    bytes-model bug, not measurement noise."""
    for row in fresh["rows"]:
        be, b = row["backend"], row["batch"]
        tm = row.get("tile_bytes_matched")
        b1 = row.get("batch1_bytes_matched")
        ps = row.get("bytes_per_stream_matched")
        if tm is None or b1 is None or ps is None:
            failures.append(f"BATCH MATCHED BYTES {be} B={b}: record is "
                            "missing the matched-firing fields")
            continue
        if tm != b1:
            failures.append(
                f"BATCH MATCHED BYTES {be} B={b}: replicated tile fetches "
                f"{tm} B/step vs {b1} at B=1 (union compaction must "
                "collapse identical streams to the batch-1 fetch)")
        elif b > 1 and not ps < b1:
            failures.append(
                f"BATCH MATCHED BYTES {be} B={b}: {ps} bytes/stream/step "
                f"is not strictly below the batch-1 baseline {b1}")
        else:
            print(f"ok   batch {be} B={b}: matched-firing bytes/stream "
                  f"{ps:.0f} (batch-1 fetch {b1:.0f})")


def _gate_soak(base, fresh, failures, same_machine):
    """The resilient-serving soak gates on EXACT reproduction: every
    policy trigger is tick-counted and the fault plan is seeded, so the
    shed/rejected/quarantined/recovered/completed counts, restart count,
    bitwise-parity count, Θ peak (Q8.8-gridded) and engine lifetime steps
    must match the committed record on ANY machine. Only the wall-clock
    p99 tick time is machine-bound (1.5x, same machine class only); the
    wall-derived straggler/heartbeat flags are never gated."""
    wall_keys = ("straggler_flags", "missed_heartbeats")

    def counts(phase):
        c = {k: v for k, v in phase["counters"].items()
             if k not in wall_keys}
        for k in ("statuses", "restarts", "parity_ok", "ticks",
                  "engine_steps", "engine_poison_steps", "theta_peak"):
            if k in phase:
                c[k] = phase[k]
        return c

    for name in ("phase_a", "phase_b"):
        b, f = counts(base[name]), counts(fresh[name])
        if b != f:
            diff = {k: (b.get(k), f.get(k))
                    for k in sorted(set(b) | set(f)) if b.get(k) != f.get(k)}
            failures.append(
                f"SOAK DETERMINISM {name}: tick-exact counts moved vs the "
                f"committed record: {diff} (regenerate baseline if "
                "intentional)")
        else:
            print(f"ok   soak {name}: tick-exact counts reproduced "
                  f"(completed={base[name]['counters']['completed']})")
        if same_machine:
            ratio = (fresh[name]["p99_tick_wall_s"]
                     / max(base[name]["p99_tick_wall_s"], 1e-9))
            line = (f"soak {name} p99 tick: "
                    f"{base[name]['p99_tick_wall_s'] * 1e6:.0f} -> "
                    f"{fresh[name]['p99_tick_wall_s'] * 1e6:.0f} us "
                    f"({ratio:.2f}x)")
            if ratio > MAX_WALL_RATIO:
                failures.append(f"WALL REGRESSION {line}")
            else:
                print(f"ok   {line}")


def _lm_delta_row_key(row):
    return (row["cell"], row["backend"], row["theta_q88"])


def _gate_lm_delta(base, fresh, failures, same_machine):
    """Gate the delta-ized LM-cell sweep (``BENCH_lm_delta.json``).

    Machine-independent HARD checks, evaluated on BOTH records:

    * Eq. 7 pricing identity — recompute
      ``dram_traffic_bytes_per_timestep`` (float64, host-side) from each
      row's recorded UNROUNDED gammas with the *current* pricing code; it
      must equal the recorded ``bytes_per_step`` EXACTLY. Any deviation
      is a real change to the generalized projection-volume model
      (``cell_dims`` x_weights/h_weights), not measurement noise —
      regenerate the baseline in the same PR if intentional.
    * theta=0 rows: measured gamma must be exactly 0.0 and the priced
      bytes exactly the cell's dense projection volume; the dense
      theta=0 row must have drift exactly 0.0 (it IS the reference).

    The fresh re-run itself hard-asserts the rest (theta=0 BITWISE
    decode parity per cell, and the >2x-reduction-at-bounded-drift
    operating point), so a completed fresh record certifies those; the
    baseline-vs-fresh comparison then pins bytes (exact on the
    baseline's machine class, 2% elsewhere) and fused wall time (1.5x,
    same machine class only)."""
    from repro.core.perf_model import dram_traffic_bytes_per_timestep
    from repro.core.sparsity import cell_dims

    for rec, tag in ((base, "baseline"), (fresh, "fresh")):
        cells = rec["config"]["cells"]
        bits = rec["config"]["weight_bits"]
        for row in rec["rows"]:
            c = cells[row["cell"]]
            dims = cell_dims(row["cell"], c["input"], c["hidden"],
                             c["layers"])
            want = float(dram_traffic_bytes_per_timestep(
                dims, row["gamma_dx"], row["gamma_dh"],
                w_weight_bits=bits))
            if want != row["bytes_per_step"]:
                failures.append(
                    f"LM DELTA PRICING {tag} {row['cell']}/"
                    f"{row['backend']} theta={row['theta_q88']}/256: "
                    f"recomputed Eq.7 bytes {want} != recorded "
                    f"{row['bytes_per_step']} (pricing model moved; "
                    "regenerate baseline if intentional)")
            if row["theta_q88"] == 0:
                if row["gamma_dx"] != 0.0 or row["gamma_dh"] != 0.0:
                    failures.append(
                        f"LM DELTA THETA0 {tag} {row['cell']}/"
                        f"{row['backend']}: measured gamma "
                        f"({row['gamma_dx']}, {row['gamma_dh']}) != 0.0")
                if row["bytes_per_step"] != c["dense_bytes"]:
                    failures.append(
                        f"LM DELTA THETA0 {tag} {row['cell']}/"
                        f"{row['backend']}: prices "
                        f"{row['bytes_per_step']} B/step != dense volume "
                        f"{c['dense_bytes']}")
                if row["backend"] == "dense" and row["drift"] != 0.0:
                    failures.append(
                        f"LM DELTA THETA0 {tag} {row['cell']}/dense: "
                        f"drift {row['drift']} != 0.0 vs itself")
    print("ok   lm_delta: Eq.7 pricing identity + theta=0 exactness hold "
          "on both records")

    rel_tol = 0.0 if same_machine else 0.02
    base_rows = {_lm_delta_row_key(r): r for r in base["rows"]}
    for row in fresh["rows"]:
        b = base_rows.get(_lm_delta_row_key(row))
        if b is None:
            continue
        drift = abs(row["bytes_per_step"] - b["bytes_per_step"])
        if drift > rel_tol * max(b["bytes_per_step"], 1.0):
            failures.append(
                f"BYTES MODEL DRIFT lm_delta {row['cell']}/"
                f"{row['backend']} theta={row['theta_q88']}/256: "
                f"{b['bytes_per_step']} -> {row['bytes_per_step']} "
                "(regenerate baseline if intentional)")
        else:
            print(f"ok   lm_delta {row['cell']}/{row['backend']} "
                  f"theta={row['theta_q88']}/256: "
                  f"bytes/step={row['bytes_per_step']:.0f}")
        if same_machine and row["backend"] == "fused":
            ratio = row["us_per_step"] / max(b["us_per_step"], 1e-9)
            line = (f"lm_delta {row['cell']}/fused "
                    f"theta={row['theta_q88']}/256: "
                    f"{b['us_per_step']:.1f} -> {row['us_per_step']:.1f} "
                    f"us/step ({ratio:.2f}x)")
            if ratio > MAX_WALL_RATIO:
                failures.append(f"WALL REGRESSION {line}")
            else:
                print(f"ok   {line}")


def main() -> int:
    from benchmarks import kernel_bench as kb

    failures: list[str] = []
    warnings: list[str] = []

    base_seq = _load(kb.BENCH_JSON)
    base_q8 = _load(kb.BENCH_Q8_JSON)
    base_lstm = _load(kb.BENCH_LSTM_JSON)
    if base_seq is None and base_q8 is None and base_lstm is None:
        print("no committed BENCH_*.json baselines found; nothing to gate")
        return 0

    def cfg_dims(base):
        c = base["config"]
        return dict(t=c["t"], i=c["input"], h=c["hidden"],
                    layers=c["layers"])

    fresh_seq = None
    if base_seq is not None:
        _, fresh_seq = kb.bench_seq_record(
            **cfg_dims(base_seq),
            thetas=tuple(sorted({r["theta"] for r in base_seq["rows"]})))
        if _comparable(base_seq["config"], fresh_seq["config"]):
            _gate_walltime("seq", base_seq, fresh_seq, failures)
        else:
            warnings.append(
                "seq baseline was recorded on "
                f"{base_seq['config'].get('device')}/"
                f"{base_seq['config'].get('machine')}; wall-time gate "
                "skipped on this machine")

    if base_q8 is not None:
        # reuse the walls just measured by the seq pass when both baselines
        # share a config — no point timing every backend twice
        times = None
        if (fresh_seq is not None
                and cfg_dims(base_q8) == cfg_dims(base_seq)):
            times = kb._times_from_record(fresh_seq)
        _, fresh_q8 = kb.bench_q8_record(
            **cfg_dims(base_q8),
            thetas=tuple(sorted({r["theta"] for r in base_q8["rows"]})),
            times_by_theta=times)
        same_machine = _comparable(base_q8["config"], fresh_q8["config"])
        _gate_bytes("q8", base_q8, fresh_q8, failures, strict=same_machine)
        if same_machine:
            _gate_walltime("q8", base_q8, fresh_q8, failures)
        else:
            warnings.append(
                "q8 baseline was recorded on a different machine class; "
                "wall-time gate skipped, bytes model enforced at 2% "
                "tolerance")

    fresh_lstm = None
    if base_lstm is not None:
        # bench_lstm_record itself hard-fails on fused-vs-dense parity
        # drift, so a completed fresh record already certifies parity;
        # the gate here is the fused wall-time trajectory. Parity drift is
        # folded into `failures` so the GRU gates' findings still print.
        try:
            _, fresh_lstm = kb.bench_lstm_record(
                **cfg_dims(base_lstm),
                thetas=tuple(sorted({r["theta"]
                                     for r in base_lstm["rows"]})))
        except AssertionError as e:
            failures.append(f"LSTM PARITY {e}")
        else:
            if _comparable(base_lstm["config"], fresh_lstm["config"]):
                _gate_walltime("lstm", base_lstm, fresh_lstm, failures)
            else:
                warnings.append(
                    "lstm baseline was recorded on "
                    f"{base_lstm['config'].get('device')}/"
                    f"{base_lstm['config'].get('machine')}; wall-time gate "
                    "skipped on this machine")

    base_lstm_q8 = _load(kb.BENCH_LSTM_Q8_JSON)
    if base_lstm_q8 is not None:
        # bench_lstm_q8_record hard-fails on (a) fused_q8 Pallas kernel
        # vs jnp-oracle bit drift and (b) quantization drift beyond the
        # Q8.8/LUT budget; a completed fresh record certifies both.
        times = None
        if (fresh_lstm is not None
                and cfg_dims(base_lstm_q8) == cfg_dims(base_lstm)):
            times = kb._times_from_record(fresh_lstm, kb.LSTM_BACKENDS)
        try:
            _, fresh_lstm_q8 = kb.bench_lstm_q8_record(
                **cfg_dims(base_lstm_q8),
                thetas=tuple(sorted({r["theta"]
                                     for r in base_lstm_q8["rows"]})),
                times_by_theta=times)
        except AssertionError as e:
            failures.append(f"LSTM Q8 PARITY {e}")
        else:
            same_machine = _comparable(base_lstm_q8["config"],
                                       fresh_lstm_q8["config"])
            _gate_bytes("lstm_q8", base_lstm_q8, fresh_lstm_q8, failures,
                        strict=same_machine)
            _gate_q8_matched_bytes("lstm_q8", fresh_lstm_q8, failures)
            if same_machine:
                _gate_walltime("lstm_q8", base_lstm_q8, fresh_lstm_q8,
                               failures)
            else:
                warnings.append(
                    "lstm_q8 baseline was recorded on a different machine "
                    "class; wall-time gate skipped, bytes model enforced "
                    "at 2% tolerance")

    for cell, path in (("gru", kb.BENCH_Q4_JSON),
                       ("lstm", kb.BENCH_LSTM_Q4_JSON)):
        base_q4 = _load(path)
        if base_q4 is None:
            continue
        # bench_q4_record hard-fails on (a) fused_q4 Pallas kernel vs
        # jnp-oracle bit drift and (b) drift beyond 2x the int8 budget
        # (plus fused_q8's own rail); a completed fresh record certifies
        # all three.
        name = f"{cell}_q4"
        try:
            _, fresh_q4 = kb.bench_q4_record(
                **cfg_dims(base_q4), cell=cell,
                thetas=tuple(sorted({r["theta"]
                                     for r in base_q4["rows"]})))
        except AssertionError as e:
            failures.append(f"Q4 PARITY {e}")
        else:
            same_machine = _comparable(base_q4["config"],
                                       fresh_q4["config"])
            _gate_bytes(name, base_q4, fresh_q4, failures,
                        strict=same_machine)
            _gate_q4_matched_bytes(name, fresh_q4, failures)
            if same_machine:
                _gate_walltime(name, base_q4, fresh_q4, failures)
            else:
                warnings.append(
                    f"{name} baseline was recorded on a different machine "
                    "class; wall-time gate skipped, bytes model enforced "
                    "at 2% tolerance")

    from benchmarks import fig13_batch_sweep as fbs
    base_batch = _load(fbs.BENCH_BATCH_JSON)
    if base_batch is not None:
        c = base_batch["config"]
        _, fresh_batch = fbs.bench_batch_record(
            t=c["t"], i=c["input"], h=c["hidden"], layers=c["layers"],
            theta=c["theta"], batches=tuple(c["batches"]))
        same_machine = _comparable(base_batch["config"],
                                   fresh_batch["config"])
        _gate_batch_bytes(base_batch, fresh_batch, failures,
                          strict=same_machine)
        _gate_batch_matched_bytes(fresh_batch, failures)
        if same_machine:
            _gate_batch_walltime(base_batch, fresh_batch, failures)
        else:
            warnings.append(
                "batch-sweep baseline was recorded on a different machine "
                "class; wall-time gate skipped, tile-bytes model enforced "
                "at 2% tolerance")

    from benchmarks import soak_serving as soak
    base_soak = _load(soak.SOAK_JSON)
    if base_soak is not None:
        cfg = {k: base_soak["config"][k] for k in soak.CFG_KEYS
               if k in base_soak["config"]}
        try:
            # bench_soak_record hard-fails on the recovery contract:
            # bitwise parity of every completed stream vs its clean
            # reference, exactly-one crash restore, every quarantine
            # recovered, Θ rise + decay. A completed record certifies all
            # of that; the gate then pins the counts to the baseline.
            _, fresh_soak = soak.bench_soak_record(**cfg)
        except AssertionError as e:
            failures.append(f"SOAK RECOVERY {e}")
        else:
            same_machine = _comparable(base_soak["config"],
                                       fresh_soak["config"])
            if not same_machine:
                warnings.append(
                    "soak baseline was recorded on a different machine "
                    "class; wall-time gate skipped, tick-exact count gate "
                    "still enforced")
            _gate_soak(base_soak, fresh_soak, failures, same_machine)

    from benchmarks import loadgen_fabric as fabric
    base_fabric = _load(fabric.FABRIC_JSON)
    if base_fabric is not None:
        # in this process, on the devices it has: a child started now
        # could not reach an accelerator this process already holds
        import jax
        need, have = base_fabric["config"]["n_shards"], len(jax.devices())
        if have < need:
            warnings.append(
                f"fabric gate not run: its record needs {need} devices and "
                f"this process has {have} (on CPU, `make check-regression` "
                f"forces {need} host devices)")
        elif fabric.run_gate() != 0:
            failures.append("FABRIC GATE: benchmarks.loadgen_fabric gate "
                            "failed (see its output above)")

    from benchmarks import lm_delta_bench as lmd
    base_lmd = _load(lmd.BENCH_LM_DELTA_JSON)
    if base_lmd is not None:
        try:
            # bench_lm_delta_record hard-fails on theta=0 bitwise decode
            # parity and the >2x-reduction-at-bounded-drift operating
            # point; a completed fresh record certifies both.
            _, fresh_lmd = lmd.bench_lm_delta_record(
                t=base_lmd["config"]["t"],
                thetas=tuple(sorted({r["theta_q88"]
                                     for r in base_lmd["rows"]})))
        except AssertionError as e:
            failures.append(f"LM DELTA INVARIANT {e}")
        else:
            same_machine = _comparable(base_lmd["config"],
                                       fresh_lmd["config"])
            if not same_machine:
                warnings.append(
                    "lm_delta baseline was recorded on a different "
                    "machine class; wall-time gate skipped, bytes model "
                    "enforced at 2% tolerance (pricing identity still "
                    "exact)")
            _gate_lm_delta(base_lmd, fresh_lmd, failures, same_machine)

    for w in warnings:
        print(f"warn {w}")
    for f in failures:
        print(f"FAIL {f}")
    if failures:
        return 1
    print("check_regression: all gates passed")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
