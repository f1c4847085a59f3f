"""Data and query generators, found by the name a config or traffic file gives.

Each module here is one generator. A data generator has
``build(cfg: dict, rng: np.random.Generator) -> (m, n) float32 columns``; a
query generator has ``make(cols, n_queries, rng, params) -> (lower, upper)``,
two (n_queries, m) float32 arrays whose unconstrained dimensions hold -inf
and +inf. They copy the program's generators so that the benchmark's data and
traffic do not change when the program does.
"""
import importlib


def load(name: str):
    """The generator module called ``name``."""
    if not name.replace("_", "").isalnum():
        raise ValueError(f"bad generator name {name!r}")
    return importlib.import_module(f"mdrqbench.gen.{name}")
