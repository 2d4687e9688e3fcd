"""Benchmark harness: the yardstick that every change to the program is
measured against (see BENCHMARK.json at the repository root)."""
