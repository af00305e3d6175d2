"""90th percentile of the engine's admission wait (``queue_s``: arrival on
the drive thread to admission into a slot) over the window's requests."""
import numpy as np


def read(ctx):
    eng = ctx.completions()
    q = [eng[r.rid].queue_s for r in ctx.win.attempted if r.rid in eng]
    return float(np.percentile(q, 90) * 1e3) if q else None
