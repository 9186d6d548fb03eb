"""95th percentile, over every request due in the window, of the time
from when it was due to the end of the step that made its first token
visible. A request that never got one counts as waiting until the end of
the drain."""
import numpy as np


def read(ctx):
    ttft = [(tr.times[0] if tr.times else ctx.stop_s) - tr.due
            for tr in ctx.attempted]
    return float(np.percentile(ttft, 95)) * 1e3
