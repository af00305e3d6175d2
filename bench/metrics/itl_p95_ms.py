"""95th percentile of every gap between consecutive streamed tokens of a
request, over the gaps that end in the window (client clock)."""
import numpy as np


def read(ctx):
    gaps = ctx.token_gaps()
    return float(np.percentile(gaps, 95) * 1e3) if len(gaps) else None
