"""A test-only reference for a configuration that is not the dense
decoder: the repository's ``moe`` family, whose layer is self-attention
followed by top-k routed experts, so its served pytree holds a router
and stacked expert weights where the decoder's holds an MLP, and its
``dims`` hold ``n_experts`` and ``top_k``.

It shows that such a configuration reaches ``run.run_cell`` through
files of its own (``stub-moe.json`` and this module). It draws the
served pytree on the device from the seed, as a reference does, but
compares nothing: ``gaps`` returns no gaps, so a run's
``checked_tokens`` is 0 and its verdict false.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

counts = None          # no metric that reads counts runs on this stub


def program_dims(cfg) -> dict:
    return {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.hd, "n_layers": cfg.n_layers,
            "vocab": cfg.vocab, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "tied": cfg.tie_embeddings,
            "n_experts": cfg.n_experts, "top_k": cfg.top_k}


def builder(dims: dict, bits: int):
    """key -> {"embed", "blocks": {"b0": attention, "m0": experts},
    "final_norm"[, "unembed"]}, layers stacked by a ``lax.map``."""
    d, hd, f = dims["d_model"], dims["head_dim"], dims["d_ff"]
    q, kv, e = dims["n_heads"] * hd, dims["n_kv_heads"] * hd, \
        dims["n_experts"]
    attn = {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q)}
    experts = {"router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
               "w_down": (e, f, d)}

    def layer(key, i):
        k = jax.random.fold_in(jax.random.fold_in(key, 1), i)

        def normal(j, shape, fan_in):
            return jax.random.normal(jax.random.fold_in(k, j), shape,
                                     jnp.float32) * fan_in ** -0.5
        ones = jnp.ones((d,), jnp.float32)
        b0 = {"norm": ones}
        for j, (name, (d_out, d_in)) in enumerate(attn.items()):
            qw, sg = W.quantize(normal(j, (d_out, d_in), d_in), bits)
            b0[name] = {"qw": qw, "sg": sg}
        m0 = {"norm": ones}
        for j, (name, shape) in enumerate(experts.items(), len(attn)):
            m0[name] = normal(j, shape, shape[-2]).astype(jnp.bfloat16)
        return {"b0": b0, "m0": m0}

    def build(key):
        def table(j):
            return (jax.random.normal(jax.random.fold_in(key, j),
                                      (dims["vocab"], d), jnp.float32)
                    * 0.02).astype(jnp.bfloat16)
        out = {"embed": table(2),
               "blocks": jax.lax.map(lambda i: layer(key, i),
                                     jnp.arange(dims["n_layers"])),
               "final_norm": jnp.ones((d,), jnp.float32)}
        if not dims["tied"]:
            out["unembed"] = table(3)
        return out
    return build


def program_params(seed: int, dims: dict, bits: int):
    return jax.jit(builder(dims, bits))(W.base_key(seed))


def gaps(seed, dims, bits, samples, length, rows, lowbits=0):
    return [np.zeros(0, np.float32) for _ in samples]
