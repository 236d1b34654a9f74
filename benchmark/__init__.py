"""The chip benchmark: see README.md and ../BENCHMARK.json."""
