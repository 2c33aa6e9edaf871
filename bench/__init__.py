"""Chip benchmark of private federated training (see BENCHMARK.json)."""
