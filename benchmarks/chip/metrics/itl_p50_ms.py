"""Median over all gaps between consecutive visible output tokens of all
requests due in the window (tokens made visible by one step share a
time, and their gap is 0)."""
import numpy as np


def read(ctx):
    gaps = np.concatenate([np.diff(tr.times) for tr in ctx.attempted
                           if len(tr.times) > 1] or [[np.nan]])
    return float(np.percentile(gaps, 50)) * 1e3
