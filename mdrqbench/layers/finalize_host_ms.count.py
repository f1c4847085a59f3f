"""Serve layer: the finalizer's host time per window (ms).

The seconds of the program's ``mdrq.finalize`` spans less those of the
``mdrq.sync`` spans with ``stage="finalize"`` nested in them (the wait for
the device and the payload readback), over the ``mdrq.finalize`` spans that
start in the traced window: the host finalizers, ticket resolution and the
window's stats and query log. Needs a trace taken with the program's
profiler sink on; None otherwise.
"""
from mdrqbench.trace import program


def read(ctx):
    n = program.total(ctx.trace, "finalize", "n")
    if not n:
        return None
    fin_s = program.total(ctx.trace, "finalize", "s")
    sync_s = program.total(ctx.trace, "sync", "s", stage="finalize")
    return (fin_s - sync_s) / n * 1e3
