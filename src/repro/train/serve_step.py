"""Serving-step factories: prefill + decode (the paper's inference setting —
quantized GEMMs through the Transitive Array path run here).

``make_decode_step`` is the unit the decode_* / long_* dry-run shapes lower:
one new token against a seq_len KV cache.

``greedy_generate`` is the host driver loop around them: one jitted
prefill, then one jitted decode step per generated token. The jitted
callables are memoised per model (``_jit_prefill`` / ``_jit_decode_step``)
so repeated ``greedy_generate`` calls — a serving loop — re-trace nothing,
and the decode step **donates its KV caches**: without donation every token
pays a full cache-buffer copy, which at production cache sizes is the
decode hot loop's single largest memory cost.

With ``mesh=`` the whole loop runs as a multi-device serve cell: the batch
is placed under ``P(("pod", "data"))`` on its leading axis (the logical
rules in ``distributed/sharding.py``), the mesh is ambient for prefill and
every decode step, and the model's internal sharding constraints keep
activations, caches, logits and the sampled tokens data-sharded between
steps. Params (and any attached DevicePlans) are placed by the caller —
replicated by default, which is the data-parallel decode topology.
"""
from __future__ import annotations

import contextlib
import weakref

import jax
import jax.numpy as jnp

from repro.distributed.sharding import spec
from repro.models.model import Model


def make_prefill(model: Model, max_len: int):
    def prefill(params, batch):
        return model.prefill(params, batch, max_len)
    return prefill


def make_decode_step(model: Model):
    def decode_step(params, caches, token, step):
        return model.decode_step(params, caches, token, step)
    return decode_step


# jitted step memo, weak-keyed by model: a fresh jax.jit wrapper per
# greedy_generate call would re-trace every time (jit caches on function
# identity, and the closure used to be rebuilt per call), while a strong
# cache would pin every Model + its compiled executables for the process
# lifetime
_STEP_JITS: "weakref.WeakKeyDictionary[Model, dict]" = \
    weakref.WeakKeyDictionary()


def _jit_prefill(model: Model, max_len: int, mesh=None):
    """One jitted prefill per (model, max_len, mesh).

    The ambient mesh is part of the key: tracing under ``set_mesh`` bakes
    the mesh into the step's sharding constraints, but the jit's own cache
    only keys on input avals/shardings — interleaved ``greedy_generate``
    calls with different ``mesh=`` values (or mesh then no-mesh) would
    otherwise silently reuse a step traced under the wrong mesh."""
    per = _STEP_JITS.setdefault(model, {})
    key = ("prefill", max_len, mesh)
    if key not in per:
        per[key] = jax.jit(make_prefill(model, max_len))
    return per[key]


def _jit_decode_step(model: Model, donate: bool, mesh=None):
    """One jitted decode step per (model, donate, mesh).

    Donating the caches lets XLA update them in place; the host loop only
    ever feeds the previous step's output back in, so the donated input
    buffer is dead by construction. ``mesh`` keys the memo for the same
    reason as :func:`_jit_prefill`."""
    per = _STEP_JITS.setdefault(model, {})
    key = ("decode", donate, mesh)
    if key not in per:
        per[key] = jax.jit(make_decode_step(model),
                           donate_argnums=(1,) if donate else ())
    return per[key]


def _place_batch(batch, mesh):
    """Shard the batch along the mesh's data axes: leading (batch) dim under
    the ``batch`` logical rule where divisible (``spec`` warns on a drop)."""
    from jax.sharding import NamedSharding

    def one(v):
        s = spec("batch", *([None] * (v.ndim - 1)), shape=v.shape,
                 mesh=mesh)
        return jax.device_put(v, NamedSharding(mesh, s))
    return jax.tree.map(one, batch)


def greedy_generate(model: Model, params, batch, max_len: int,
                    n_steps: int, *, mesh=None, donate: bool = True):
    """Prefill then greedy-decode; returns exactly ``n_steps`` tokens.

    Contract (explicit since PR 5): the result is ``(B, n_steps)`` int32.
    Token 0 is the argmax over the prefill logits at the last prompt
    position; tokens 1..n_steps-1 come from ``n_steps - 1`` decode steps.
    ``n_steps=0`` returns an empty ``(B, 0)`` array without running the
    model; negative ``n_steps`` raises. (The old loop ran
    ``range(n_steps - 1)`` decode steps *and* unconditionally emitted the
    prefill token, so ``n_steps=0`` still returned one token.)

    ``mesh=`` runs the loop as a multi-device serve cell: the batch is
    placed under the ``batch`` logical sharding rule and the mesh is
    ambient for prefill + every decode step — tokens come back
    bit-identical to the 1-device run (data parallelism never reorders a
    row's reductions). ``donate=False`` keeps the per-step cache copy, for
    callers that re-enter decode from a kept cache reference.
    """
    if n_steps < 0:
        raise ValueError(f"n_steps must be >= 0, got {n_steps}")
    b, prompt_len = batch["tokens"].shape
    if n_steps == 0:
        return jnp.zeros((b, 0), jnp.int32)
    ctx = jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with ctx:
        if mesh is not None:
            batch = _place_batch(batch, mesh)
        logits, caches = _jit_prefill(model, max_len, mesh)(params, batch)
        step_fn = _jit_decode_step(model, donate, mesh)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        toks = [tok]
        for i in range(n_steps - 1):
            logits, caches = step_fn(params, caches, tok,
                                     jnp.int32(prompt_len + i))
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            toks.append(tok)
        return jnp.concatenate(toks, axis=1)
