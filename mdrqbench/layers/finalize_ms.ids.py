"""Serve layer: host finalize time per window (ms).

``ServerStats.finalize_seconds / n_batches`` over the window: the finalizer
thread's wall per window, which includes the wait in ``device_get`` for the
device to finish, the readback and the spec's host finalizers.
"""


def read(ctx):
    if not ctx.stats.n_batches:
        return None
    return ctx.stats.finalize_seconds / ctx.stats.n_batches * 1e3
