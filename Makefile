# EdgeDRNN reproduction: tier-1 tests and the benchmark.
export PYTHONPATH := src

.PHONY: test bench ci

test:            ## tier-1 suite
	python -m pytest -x -q

ci: test         ## the CI gate is tier-1

bench:           ## the benchmark: cells, metrics and bounds are in BENCHMARK.json (needs a TPU)
	python3 bench/run.py
