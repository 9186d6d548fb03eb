"""A run whose timed path is broken underneath comes out not correct.

Each case plants one fault in the served model's packed decode, the
program that produces every token after the first, then drives a whole
run of the cell at a CPU size (``conftest.tiny_run``) against the cell's
own limits. The unbroken run must come out correct, so the cases are
not vacuous. A one-chip cell has no exchange between chips to leave out.
"""
import jax.numpy as jnp
import pytest

from repro.models.model import Model

CELLS = ["smollm-135m.chat", "chatglm3-6b.offline"]
_decode = Model.decode_step_paged


def state_unchanged(self, params, pool, *a, **kw):
    """The step computes its logits but returns the pool it was given:
    no decoded token's K/V is ever stored."""
    logits, _ = _decode(self, params, pool, *a, **kw)
    return logits, pool


def half_batch(self, params, pool, tokens, page_indices, steps, **kw):
    """Half of the live slots (those that decode a position, ``steps`` >
    0), alternating from step to step, are not computed: each is given
    the logits of the next live slot. Leaving out a fixed half of the
    slots misses the rows a CPU-sized run samples where they never sat in
    that half (the engine fills the lowest free slot first)."""
    logits, new = _decode(self, params, pool, tokens, page_indices, steps,
                          **kw)
    live = steps > 0
    rank = jnp.cumsum(live) - 1
    order = jnp.argsort(~live, stable=True)      # live slots first
    nxt = order[(rank + 1) % jnp.maximum(live.sum(), 1)]
    out = (live & ((rank + steps) % 2 == 1))[:, None, None]
    return jnp.where(out, logits[nxt], logits), new


def upper_half(self, params, pool, *a, **kw):
    """Only the lower half of the slots is computed; the upper half is
    given the lower half's logits."""
    logits, new = _decode(self, params, pool, *a, **kw)
    h = logits.shape[0] // 2
    return jnp.concatenate([logits[:h], logits[:h]], 0), new


def token_altered(self, params, pool, tokens, page_indices, steps, **kw):
    """Where a row decodes position 3 mod 7, its best token is moved one
    id along the vocabulary."""
    logits, new = _decode(self, params, pool, tokens, page_indices, steps,
                          **kw)
    hit = (steps % 7 == 3)[:, None, None]
    return jnp.where(hit, jnp.roll(logits, 1, axis=-1), logits), new


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(tiny_run, cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(tiny_run, monkeypatch, cell, fault):
    monkeypatch.setattr(Model, "decode_step_paged", fault)
    res = tiny_run(cell)
    assert not res["correct"], res["checks"]


def test_fixed_half_is_not_correct(tiny_run, monkeypatch):
    """A fixed half of the slots left out, where every slot is busy (a
    closed backlog). Under an open-loop mix below capacity the engine
    fills the lowest free slots first, so the upper half holds few
    requests; ``half_batch`` covers that cell."""
    monkeypatch.setattr(Model, "decode_step_paged", upper_half)
    res = tiny_run("chatglm3-6b.offline")
    assert not res["correct"], res["checks"]
