"""Device layer: the cross-chip all-reduce's device time a window (ms).

The sharded Count merges each chip's (Q,) partial counts in one all-reduce,
the ``psum`` of ``DistributedScan``'s shard_map, which the trace names
``psum.<k> s32[Q]`` (the instruction and its shape, in the reduction's
``device_ops``). Its device time includes each chip's wait for the slowest
shard to reach it. Summed over the chips in the trace, averaged over them,
and divided by the windows launched inside the traced window.
"""
import re

ALL_REDUCE = re.compile(r"^psum\.\d+ s32\[\d+\]$")


def read(ctx):
    if ctx.trace is None or not ctx.plans:
        return None
    total_s = sum(s for name, s in ctx.trace["device_ops"]
                  if ALL_REDUCE.match(name))
    if not total_s:
        return None
    return total_s / ctx.trace["n_devices"] / len(ctx.plans) * 1e3
