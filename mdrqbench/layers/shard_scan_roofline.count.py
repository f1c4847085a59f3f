"""Kernel layer: the sharded scan kernel's share of the chips' HBM roofline (%).

The least time is the bytes the window's scan buckets had to move over the
whole table (``roofline.scan_bytes``: every row of each dimension some query
of the bucket bounds, as float32, plus the counts) at the HBM peak of all
the chips that share it. The time is the sharded kernel's device time,
summed over the chips in the trace and averaged over them. The kernel is
the Pallas call inside ``DistributedScan``'s shard_map, which the trace
names after its ``sharded_scan`` scope (``sharded_scan.<k> s8[Q,n_local]``,
the instruction and its mask's shape, in the reduction's ``device_ops``);
``trace/kernels.json`` does not count it among the scans.
"""
import re

import numpy as np

from mdrqbench import roofline

KERNEL = re.compile(r"^sharded_scan\.\d+ s8\[\d+,\d+\]$")


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = sum(s for name, s in ctx.trace["device_ops"]
                   if KERNEL.match(name))
    if not kernel_s:
        return None
    nbytes = 0.0
    for _, queries, methods in ctx.plans:
        bucket = [q for q, m in zip(queries, methods) if m == "scan"]
        if bucket:
            lower = np.stack([q.lower for q in bucket])
            upper = np.stack([q.upper for q in bucket])
            nbytes += roofline.scan_bytes(ctx.n_rows, lower, upper,
                                          ctx.spec_kind)
    if nbytes == 0.0:
        return None
    chips = ctx.trace["n_devices"]
    least_s = nbytes / (chips * ctx.peaks["hbm_bytes_per_s"])
    return least_s / (kernel_s / chips) * 100.0
