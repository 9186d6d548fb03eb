"""Open loop: requests due at Poisson arrivals, at a fixed offered rate.

Keys of the mix besides those of ``sizes``:
  rate_per_s   offered requests per second

The ``rate_per_s x seconds`` requests due in the window have one fixed
multiset of sizes and of inter-arrival gaps; the seed permutes both.
Each request is submitted when it is due, whether or not earlier ones
have finished; the engine steps while anything is queued or active, and
the host sleeps otherwise. After the window no request is due, and the
loop drains until every request is done or ``DRAIN_S`` more seconds
have passed. Every request due in the window is attempted.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from traffic import sizes

DRAIN_S = 120.0
now = time.perf_counter


def gaps(n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of mean 1, the same for every seed."""
    return np.random.default_rng(sizes.SIZES_SEED + 1).exponential(1.0, n)


class Load:
    def __init__(self, p: dict, seed: int, seconds: float, vocab: int):
        n = max(1, round(p["rate_per_s"] * seconds))
        g = np.random.default_rng([seed, 1]).permutation(gaps(n))
        due = np.concatenate([[0.0], np.cumsum(g)[:-1]]) * seconds / g.sum()
        reqs = sizes.requests(p, seed, n, vocab, permute=True)
        self.reqs = [(float(t), *next(reqs)) for t in due]

    def setup(self, drv) -> None:
        """Nothing is due before the window."""

    def window(self, drv, seconds: float, on_tick=None) -> dict:
        t0 = now()
        end, stop = t0 + seconds, t0 + seconds + DRAIN_S
        reqs, i, late = self.reqs, 0, []
        while True:
            t = now()
            if on_tick:
                on_tick(t - t0)
            while i < len(reqs) and t0 + reqs[i][0] <= t:
                due, prompt, max_new = reqs[i]
                drv.submit(prompt, max_new, t0 + due)
                i += 1
            if drv.busy():
                drv.step()
            elif i < len(reqs):
                due = t0 + reqs[i][0]
                with TraceAnnotation("wait"):
                    time.sleep(max(0.0, due - now()))
                late.append(now() - due)
            else:
                break
            if now() > stop:
                break
        return {"t0": t0, "end": end, "stop": stop, "late_s": late,
                "cut": False}


def make(p: dict, seed: int, seconds: float, vocab: int) -> Load:
    return Load(p, seed, seconds, vocab)
