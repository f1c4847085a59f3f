"""Device layer: share of the traced window in which the device idles while
the server's device stage runs (%).

``idle_in_flush_s`` of the trace reduction (device-idle time inside the
admission thread's ``mdrq.flush`` spans) over the window. Needs a trace
taken with the program's profiler sink on; None otherwise.
"""
from mdrqbench.trace import program


def read(ctx):
    if program.windows(ctx.trace) is None:
        return None
    return ctx.trace["idle_in_flush_s"] / ctx.trace["window_s"] * 100.0
