"""Seeded weights of a decoder configuration, made on the device.

One generator serves both sides of the comparison: ``program_params``
builds the served pytree in one jitted call (a ``lax.map`` over layers,
so only one layer's float32 draw is live at a time), and the reference
calls ``layer`` / ``tables`` again, layer by layer, to draw the same
numbers. Nothing here reads the program's weights.

Linear weights are drawn N(0, 1/d_in), quantized to ``w_bits`` with one
float32 scale per output channel (absmax / (2^(bits-1) - 1), clipped to
the signed range) and stored as int8; embedding and output tables are
N(0, 0.02^2) in bfloat16; norm scales are 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def base_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31)


def linear_shapes(dims: dict) -> dict:
    """(d_out, d_in) of each linear of one layer."""
    d, hd = dims["d_model"], dims["head_dim"]
    q, kv, f = dims["n_heads"] * hd, dims["n_kv_heads"] * hd, dims["d_ff"]
    return {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
            "gate": (f, d), "up": (f, d), "down": (d, f)}


def quantize(w: jax.Array, bits: int):
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=1, keepdims=True),
                        1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale


def layer(key: jax.Array, index, dims: dict, bits: int) -> dict:
    """Layer ``index``'s linears as {name: (int8 values, f32 scales)}."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {}
    for i, (name, (d_out, d_in)) in enumerate(linear_shapes(dims).items()):
        w = jax.random.normal(jax.random.fold_in(k, i), (d_out, d_in),
                              jnp.float32) * d_in ** -0.5
        out[name] = quantize(w, bits)
    return out


def tables(key: jax.Array, dims: dict) -> dict:
    """Embedding (and untied output) table, bfloat16 (vocab, d_model)."""
    shape = (dims["vocab"], dims["d_model"])

    def draw(i):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * 0.02).astype(jnp.bfloat16)
    out = {"embed": draw(2)}
    if not dims["tied"]:
        out["unembed"] = draw(3)
    return out


def builder(dims: dict, bits: int):
    """key -> the served pytree, {"embed", "blocks", "final_norm"[,
    "unembed"]}: a ``lax.map`` over layers, for one ``jax.jit``."""
    def build(key):
        stacked = jax.lax.map(lambda i: layer(key, i, dims, bits),
                              jnp.arange(dims["n_layers"]))
        ones = jnp.ones((dims["n_layers"], dims["d_model"]), jnp.float32)
        lin = {n: {"qw": q, "sg": s} for n, (q, s) in stacked.items()}
        blocks = {"b0": {"norm": ones, "wq": lin["wq"], "wk": lin["wk"],
                         "wv": lin["wv"], "wo": lin["wo"]},
                  "m0": {"norm": ones, "gate": lin["gate"], "up": lin["up"],
                         "down": lin["down"]}}
        return {**tables(key, dims), "blocks": blocks,
                "final_norm": jnp.ones((dims["d_model"],), jnp.float32)}
    return build


def program_params(seed: int, dims: dict, bits: int):
    """The served pytree, made on the device in one jitted call."""
    return jax.jit(builder(dims, bits))(base_key(seed))


def check_layout(params, abstract) -> None:
    """Raise unless ``params`` has the program's pytree, shapes and
    dtypes (``abstract`` is ``jax.eval_shape`` of the program's init)."""
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(abstract)
    if got != want:
        raise ValueError(f"weight layout {got} is not the program's {want}")
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape} {a.dtype} is not the "
                             f"program's {b.shape} {b.dtype}")
