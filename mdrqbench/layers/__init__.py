"""Per-layer metric readers, one module per metric, named as in BENCHMARK.json.

Each has ``read(ctx) -> float | None`` (``ctx`` is ``harness.Context``) and
returns None where the run gave it nothing to read.
"""
