"""The chip benchmark: ``bench/run.py`` runs one cell of BENCHMARK.json."""
