"""Access-path layer: mid-launch device->host sync time per window (ms).

The seconds of the program's ``mdrq.sync`` spans with ``stage="launch"``
(the kd-tree and R*-tree prune masks, the VA-file survivor bits: syncs the
device stage must wait on before it can launch the visits) over the
``mdrq.flush`` spans in the traced window. Needs a trace taken with the
program's profiler sink on; None otherwise.
"""
from mdrqbench.trace import program


def read(ctx):
    n = program.windows(ctx.trace)
    if n is None:
        return None
    return program.total(ctx.trace, "sync", "s", stage="launch") / n * 1e3
