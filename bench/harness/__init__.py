"""The general machinery of the benchmark: specs, traces, work counts,
traffic, weights and the comparison with the plain reference."""
