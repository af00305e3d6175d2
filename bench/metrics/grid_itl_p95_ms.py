"""``itl_p95_ms`` where it is read per layer: in a cell whose admissions
stall about one gap in twenty, the 95th percentile falls among the stalled
gaps or just below them as a window holds one admission more or less, too
unsteady to hold to a bound."""
import numpy as np


def read(ctx):
    gaps = ctx.token_gaps()
    return float(np.percentile(gaps, 95) * 1e3) if len(gaps) else None
