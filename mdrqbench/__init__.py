"""Chip benchmark of the MDRQ engine's served path (see BENCHMARK.json)."""
