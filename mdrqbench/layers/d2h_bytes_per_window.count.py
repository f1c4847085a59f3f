"""Access-path layer: device->host bytes per window (B).

The summed ``bytes`` of the program's ``mdrq.sync`` spans (both stages: the
survivor masks the device stage reads back and the payloads the finalizer
reads back) over the ``mdrq.flush`` spans in the traced window. Needs a
trace taken with the program's profiler sink on; None otherwise.
"""
from mdrqbench.trace import program


def read(ctx):
    n = program.windows(ctx.trace)
    if n is None:
        return None
    return program.total(ctx.trace, "sync", "bytes") / n
