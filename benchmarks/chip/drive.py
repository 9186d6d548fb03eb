"""Drive ``ServeEngine`` through a measured window, by its public calls.

The harness submits requests and calls ``step()``; each ``step()`` ends
with the host reading the step's tokens, so a token is visible to its
user when the ``step()`` that made it returns. Every request keeps the
time each of its tokens became visible; latencies run from when a
request was due, not from when it was submitted.

Host spans (``jax.profiler.TraceAnnotation``) mark ``submit`` and
``step`` here, and ``wait`` where a traffic kind sleeps until the next
due time, so that a traced run can say what the host was doing in each
idle gap of the device. The loop of a window is the traffic kind's
(``traffic/<kind>.py``).
"""
from __future__ import annotations

import dataclasses
import time

from jax.profiler import TraceAnnotation

from repro.serve import bucket

now = time.perf_counter


@dataclasses.dataclass
class Track:
    """One request as its user sees it."""
    req: object                  # the engine's Request
    due: float                   # perf_counter time it was due
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRec:
    """What one ``step()`` did, as the harness observes it."""
    start: float
    end: float
    # prompt lengths of the requests whose first token came now
    prompts: list = dataclasses.field(default_factory=list)
    decode_rows: int = 0         # requests given a decoded token now
    live_positions: int = 0      # K/V positions those rows attended


class Driver:
    """Submits, steps and records; one per measured window."""

    def __init__(self, eng):
        self.eng = eng
        self.live: dict[int, Track] = {}
        self.done: list[Track] = []
        self.steps: list[StepRec] = []

    def submit(self, prompt, max_new: int, due: float) -> Track:
        with TraceAnnotation("submit"):
            rid = self.eng.submit(prompt, max_new)
        tr = Track(self.eng.queue[-1], due)
        assert tr.req.rid == rid
        self.live[rid] = tr
        return tr

    def step(self) -> StepRec:
        rec = StepRec(now(), 0.0)
        with TraceAnnotation("step"):
            self.eng.step()
        rec.end = t = now()
        for rid, tr in list(self.live.items()):
            new = len(tr.req.out) - len(tr.times)
            if new:
                if not tr.times:
                    rec.prompts.append(len(tr.req.prompt))
                    new_decoded = new - 1
                else:
                    new_decoded = new
                if new_decoded:
                    rec.decode_rows += 1
                    rec.live_positions += tr.req.length
                tr.times.extend([t] * new)
            if tr.req.done:
                del self.live[rid]
                self.done.append(tr)
        self.steps.append(rec)
        return rec

    def busy(self) -> bool:
        return bool(self.eng.queue or self.eng.active)


def warm_up(eng, lengths: tuple[int, int], vocab: int, rng) -> None:
    """Run every program the cell's traffic can ask for once: a batched
    prefill for each (batch bucket, length bucket) that prompts of
    ``lengths`` (shortest, longest) and ``eng.n_slots`` can produce, and
    the decode."""
    lo, hi = lengths
    nbs = sorted({bucket(g, eng.n_slots) for g in range(1, eng.n_slots + 1)})
    lbs = sorted({bucket(n, eng.max_len) for n in range(lo, hi + 1)})
    for lb in lbs:
        n = max(lo, min(hi, lb))          # a prompt length in this bucket
        for nb in nbs:
            for _ in range(nb):
                eng.submit(rng.integers(0, vocab, n).tolist(), 1)
            eng.step()
    eng.submit(rng.integers(0, vocab, lo).tolist(), 2)
    while eng.queue or eng.active:
        eng.step()
