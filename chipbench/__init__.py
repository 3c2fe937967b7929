"""Benchmark of the private gossip learner on TPU chips (see BENCHMARK.json)."""
