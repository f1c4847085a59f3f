"""Engine and planner layer: planning time per query (us).

``ServerStats.plan_seconds / n_queries`` over the window.
"""


def read(ctx):
    if not ctx.stats.n_queries:
        return None
    return ctx.stats.plan_seconds / ctx.stats.n_queries * 1e6
