"""Client layer: how late the open-loop generator submitted (p95, ms).

Submit time minus due time, over the queries due in the window. A generator
that falls behind would otherwise read as a faster server.
"""
import numpy as np


def read(ctx):
    ks = ctx.in_window()
    if ks.size == 0:
        return None
    lag = np.array([ctx.log.t_submit[k] - ctx.log.due[k] for k in ks])
    return float(np.percentile(lag, 95)) * 1e3
