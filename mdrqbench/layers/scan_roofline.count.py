"""Kernel layer: the scan kernels' share of their HBM roofline (%).

Kernel time is the sum of the scan family's device events in the traced
window (``trace/kernels.json``). The least time is the bytes the window's
scan buckets had to move at the chip's HBM peak: for each bucket the planner
sent to a scan path, every row of each dimension some query of the bucket
bounds, as float32, plus the results the spec asks for
(``roofline.scan_bytes``).
"""
import numpy as np

from mdrqbench import roofline

SCAN_PATHS = ("scan", "scan_vertical")


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace["kernel_s"].get("scan")
    if not kernel_s:
        return None
    nbytes = 0.0
    for _, queries, methods in ctx.plans:
        bucket = [q for q, m in zip(queries, methods) if m in SCAN_PATHS]
        if bucket:
            lower = np.stack([q.lower for q in bucket])
            upper = np.stack([q.upper for q in bucket])
            nbytes += roofline.scan_bytes(ctx.n_rows, lower, upper,
                                          ctx.spec_kind)
    if nbytes == 0.0:
        return None
    return nbytes / ctx.peaks["hbm_bytes_per_s"] / kernel_s * 100.0
