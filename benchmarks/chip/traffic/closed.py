"""Closed backlog: the queue never runs dry, every slot is always busy.

Keys of the mix besides those of ``sizes``:
  cycle        requests before the size sequence repeats

Sizes follow their fixed order for every seed (not permuted), so every
run of a cell works through the same sequence of lengths; the seed draws
the token ids. Set-up fills every slot; the window keeps as many
requests queued as there are slots, so each slot that frees is refilled
at the next step. The backlog has no due times: what is still in flight
when the window closes is cut by it, is not attempted, and is not
waited for.
"""
from __future__ import annotations

import time

from traffic import sizes

now = time.perf_counter


class Load:
    def __init__(self, p: dict, seed: int, seconds: float, vocab: int):
        self.stream = sizes.requests(p, seed, p["cycle"], vocab,
                                     permute=False)

    def _top_up(self, drv) -> None:
        while len(drv.eng.queue) < drv.eng.n_slots:
            prompt, max_new = next(self.stream)
            drv.submit(prompt, max_new, now())

    def setup(self, drv) -> None:
        while len(drv.eng.active) < drv.eng.n_slots:
            self._top_up(drv)
            drv.step()

    def window(self, drv, seconds: float, on_tick=None) -> dict:
        t0 = now()
        while now() < t0 + seconds:
            if on_tick:
                on_tick(now() - t0)
            self._top_up(drv)
            drv.step()
        t = now()
        return {"t0": t0, "end": t, "stop": t, "late_s": [], "cut": True}


def make(p: dict, seed: int, seconds: float, vocab: int) -> Load:
    return Load(p, seed, seconds, vocab)
