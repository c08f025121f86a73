"""Shared yardstick of the benchmark: cell lookup, reference, work counts,
peaks, trace reduction, load generation and the correctness comparison."""
