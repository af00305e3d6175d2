"""90th percentile of time to first token over the requests scheduled in
the window, from each request's scheduled send time (client clock)."""
import numpy as np


def read(ctx):
    t = [r.stamps[0] - r.t_sched for r in ctx.win.attempted if r.stamps]
    return float(np.percentile(t, 90) * 1e3) if t else None
