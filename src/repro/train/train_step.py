"""Train-step factory: microbatched grad accumulation, AdamW update,
optional int8+error-feedback cross-pod gradient compression.

State/step layout is donation-friendly: ``train_step(state, batch) ->
(state, metrics)`` with state donated, so parameters and optimizer moments
update in place on device.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.collectives import compressed_psum_tree
from repro.models.model import Model
from repro.optim import AdamW

TrainState = dict[str, Any]


def make_optimizer(cfg: ModelConfig) -> AdamW:
    mdt = jnp.bfloat16 if str(cfg.opt_state_dtype) in ("bfloat16", "bf16") \
        else jnp.float32
    return AdamW(moment_dtype=mdt, factored_v=cfg.factored_second_moment)


def init_state(model: Model, opt: AdamW, key) -> TrainState:
    params = model.init(key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_shape(model: Model, opt: AdamW):
    return jax.eval_shape(lambda: init_state(model, opt,
                                             jax.random.PRNGKey(0)))


def _accum_grads(loss_fn, params, batch, n_micro: int,
                 accum_dtype=jnp.float32):
    """Scan microbatches, averaging loss and grads.

    ``accum_dtype=bfloat16`` halves the gradient-carry HBM (12 GB/dev for
    the 0.8T llama4 config) at a small accumulation-noise cost — paired
    with the bf16 optimizer moments it already uses."""
    if n_micro == 1:
        mb = jax.tree.map(lambda a: a[0], batch)
        return jax.value_and_grad(loss_fn)(params, mb)

    def micro(carry, mb):
        loss_sum, gsum = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        gsum = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gsum, g)
        return (loss_sum + loss, gsum), None

    gzero = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
    (loss_sum, gsum), _ = jax.lax.scan(micro, (jnp.float32(0.0), gzero),
                                       batch)
    inv = 1.0 / n_micro
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, gsum)


def make_train_step(model: Model, opt: AdamW, lr_fn):
    """Returns train_step(state, batch) -> (state, metrics).

    batch leaves are (grad_accum, micro_batch, ...). When
    cfg.compress_pod_grads is set and the ambient mesh has a "pod" axis,
    the cross-pod gradient mean runs as an int8 error-feedback collective
    inside shard_map (XLA still does full-precision ICI reductions inside
    each pod — only the slow DCN hop is compressed).
    """
    cfg = model.cfg

    accum_dtype = jnp.bfloat16 \
        if str(cfg.opt_state_dtype) in ("bfloat16", "bf16") else jnp.float32

    def loss_fn(p, mb):
        return model.loss(p, mb)

    def train_step(state: TrainState, batch):
        params = state["params"]
        loss, grads = _accum_grads(loss_fn, params, batch, cfg.grad_accum,
                                   accum_dtype)
        lr = lr_fn(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    return train_step


def make_compressed_dp_train_step(model: Model, opt: AdamW, lr_fn, mesh,
                                  dp_axes=("pod", "data")):
    """Data-parallel train step fully inside shard_map, with the cross-pod
    gradient mean running as an int8 error-feedback collective
    (distributed-optimization trick, DESIGN.md §4).

    Params are replicated; the batch is sharded over ``dp_axes``. Intra-pod
    reduction ("data") stays full precision; only the slow DCN hop ("pod")
    is compressed. State carries the per-leaf quantization residuals.
    """
    cfg = model.cfg

    def local_step(state, batch):
        params = state["params"]
        loss, grads = _accum_grads(lambda p, mb: model.loss(p, mb),
                                   params, batch, cfg.grad_accum)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "data"), grads)
        loss = jax.lax.pmean(jax.lax.pmean(loss, "data"), "pod")
        grads, new_res = compressed_psum_tree(grads, state["residual"],
                                              "pod")
        lr = lr_fn(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1, "residual": new_res},
                {"loss": loss})

    rep = P()
    bspec = P(None, dp_axes)      # (accum, micro_batch, ...) — batch axis

    def specs_like(tree, s):
        return jax.tree.map(lambda _: s, tree)

    def step(state, batch):
        state_specs = specs_like(state, rep)
        batch_specs = jax.tree.map(
            lambda a: P(None, dp_axes, *([None] * (a.ndim - 2))), batch)
        return jax.shard_map(local_step, mesh=mesh,
                             in_specs=(state_specs, batch_specs),
                             out_specs=(state_specs, specs_like(
                                 {"loss": 0}, rep)),
                             check_vma=False)(state, batch)

    del bspec
    return step


def init_compressed_state(model: Model, opt: AdamW, key) -> TrainState:
    state = init_state(model, opt, key)
    state["residual"] = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
    return state
