"""The benchmark (BENCHMARK.json's `paths`): harness, drivers, traffic, reference, data files."""
