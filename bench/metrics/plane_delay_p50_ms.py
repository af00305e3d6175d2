"""Median time the request plane adds to a request's first token: the
client's time to first token (from sending) minus the engine's own
``ttft_s`` (arrival on the drive thread to the first sampled token)."""
import numpy as np


def read(ctx):
    eng = ctx.completions()
    d = [(r.stamps[0] - r.t_send) - eng[r.rid].ttft_s
         for r in ctx.win.attempted
         if r.stamps and r.rid in eng and eng[r.rid].token_times]
    return float(np.percentile(d, 50) * 1e3) if d else None
