"""95th percentile over the gaps of ``itl_p50_ms``."""
import numpy as np


def read(ctx):
    gaps = np.concatenate([np.diff(tr.times) for tr in ctx.attempted
                           if len(tr.times) > 1] or [[np.nan]])
    return float(np.percentile(gaps, 95)) * 1e3
