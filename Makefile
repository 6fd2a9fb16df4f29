# EdgeDRNN reproduction — tier-1 + perf-gate entry points.
export PYTHONPATH := src

.PHONY: test bench bench-quick bench-lstm-quick bench-lstm-q8-quick bench-q4-quick bench-batch-quick soak-quick bench-fabric-quick bench-lm-delta-quick check-regression ci

test:            ## tier-1 suite
	python -m pytest -x -q

ci: test bench-quick bench-lstm-quick bench-lstm-q8-quick bench-q4-quick bench-batch-quick soak-quick bench-fabric-quick bench-lm-delta-quick check-regression  ## full gate: tier-1 + quick benches (GRU + LSTM parity + LSTM q8 parity/bytes + int4 q4 parity/bytes + batched tile invariant + resilient-serving soak + distributed-fabric loadgen + delta-ized LM cells) + perf regression

bench:           ## full paper tables/figures + kernel benches (rewrites BENCH_*.json)
	python -m benchmarks.run

bench-quick:     ## reduced CI pass (no baseline writes)
	python -m benchmarks.run --quick

bench-lstm-quick:  ## DeltaLSTM parity/bench quick path (no baseline writes)
	python -m benchmarks.kernel_bench --lstm --quick

bench-lstm-q8-quick:  ## quantized DeltaLSTM parity/bytes quick path (hard fused_q8-vs-dense + kernel-oracle assertions)
	python -m benchmarks.kernel_bench --lstm-q8 --quick

bench-q4-quick:  ## int4 nibble-packed parity/bytes quick path, both cells (hard fused_q4 kernel-oracle bit-match + 2x-budget drift asserts)
	python -m benchmarks.kernel_bench --q4 --quick

bench-batch-quick:  ## measured batched-tile sweep quick path (hard matched-firing bytes/stream invariant, no baseline writes)
	python -m benchmarks.fig13_batch_sweep --quick

soak-quick:      ## resilient-serving chaos soak quick path (hard bitwise-parity + crash-recovery + dynamic-theta asserts, no baseline writes)
	python -m benchmarks.soak_serving --quick

bench-fabric-quick:  ## distributed-fabric loadgen quick path (hard conservation + bitwise parity through an elastic scale-down, 8 forced host devices, no baseline writes)
	python -m benchmarks.loadgen_fabric --quick

bench-lm-delta-quick:  ## delta-ized LM cells (RWKV6 / RG-LRU) quick path (hard theta=0 bitwise-decode + >2x byte-reduction asserts, no baseline writes)
	python -m benchmarks.lm_delta_bench --quick

check-regression:  ## gate fresh fused-path wall time / bytes model vs committed baselines (8 host devices for the in-process fabric gate)
	XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m benchmarks.check_regression
