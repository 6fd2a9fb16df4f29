"""The benchmark harness: spec lookup, traffic, timed loops, traces."""
