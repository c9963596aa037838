"""The chip benchmark: cells of BENCHMARK.json run on one machine's chips."""
