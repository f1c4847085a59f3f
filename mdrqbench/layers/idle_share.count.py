"""Device layer: share of the traced window with no device operation (%)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]) * 100.0
