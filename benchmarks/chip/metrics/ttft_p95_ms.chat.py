"""95th percentile, over every request due in the window, of the time
from when it was due to the end of the step that made its first token
visible. A request that never got one counts as waiting until the end of
the drain. A per-layer metric: a host stall delays every request due in
it, and one of 3 s holds some 17 of a window's 286 requests, more than
the 14 above the 95th percentile, so one such stall moves this tail
many-fold."""
import numpy as np


def read(ctx):
    ttft = [(tr.times[0] if tr.times else ctx.stop_s) - tr.due
            for tr in ctx.attempted]
    return float(np.percentile(ttft, 95)) * 1e3
