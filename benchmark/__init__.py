"""The chip benchmark: BENCHMARK.json at the root names what lives here."""
