"""The benchmark of code2vec-tpu: BENCHMARK.json names every file here."""
