"""Access-path layer: counted device->host syncs per served window.

The ``host_sync`` count of ``ops.counters()`` over the window divided by the
windows the server finalized: 1 for a window the scans serve alone, 2 more
for each two-phase bucket (kd-tree, R*-tree, VA-file).
"""


def read(ctx):
    if not ctx.stats.n_batches:
        return None
    return ctx.counters.get("host_sync", 0) / ctx.stats.n_batches
