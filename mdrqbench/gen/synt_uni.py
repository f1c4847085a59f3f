"""SYNT-UNI data (arXiv 1801.03644 §7.2, Table 2): uniform in [0, 1]^m.

A copy of ``repro.data.synthetic.synt_uni``.
"""
import numpy as np


def build(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    return rng.random((int(cfg["dims"]), int(cfg["rows"])), dtype=np.float32)
