"""Result specs, found by the ``kind`` a traffic file gives.

Each module here is one kind: ``make()`` gives the program's spec object,
``answer(ids, cols)`` the reference's answer from the sorted ids of the
matching objects, and ``same(got, want)`` whether a served answer agrees
exactly.
"""
import importlib


def load(kind: str):
    if not kind.replace("_", "").isalnum():
        raise ValueError(f"bad spec kind {kind!r}")
    return importlib.import_module(f"mdrqbench.specs.{kind}")
