"""Peaks by device kind, and the least bytes a kernel's call has to move.

A kernel's roofline share is the least time its work could take at the
chip's peak over the time the trace shows it took. The work is what the
call has to do, not what the kernel's blocks happen to read, so an
implementation that reads less, or pads otherwise, is held to the same work.
"""
import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The peaks table has no entry for this device kind."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def constrained_dims(lower: np.ndarray, upper: np.ndarray) -> np.ndarray:
    """(m,) bool: the dimensions that some query of a (Q, m) bucket bounds."""
    return (~(np.isneginf(lower) & np.isposinf(upper))).any(axis=0)


RESULT_BYTES = {"count": 4}   # per query, by result spec


def scan_bytes(n_rows: int, lower: np.ndarray, upper: np.ndarray,
               spec_kind: str) -> float:
    """Least HBM bytes of one scan bucket: every row of each dimension the
    bucket's (Q, m) bounds constrain, read once as float32, plus the
    results the spec asks for."""
    read = float(n_rows) * int(constrained_dims(lower, upper).sum()) * 4
    return read + RESULT_BYTES[spec_kind] * lower.shape[0]
