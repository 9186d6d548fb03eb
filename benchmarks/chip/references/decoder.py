"""Plain float32 reference of a pre-norm decoder (Llama and GLM layout).

Written from the published descriptions, in ``jax.numpy`` at float32
with ``highest`` matmul precision, no cache and no batching tricks:
RMSNorm, GQA self-attention with causal softmax, rotary embedding on the
first ``rope_fraction`` of each head's dimensions (adjacent pairs),
SwiGLU MLP, residual adds, final RMSNorm and the output table. Weights
are the benchmark's own seeded draw (``weights.py``), dequantized.

It runs one layer at a time over every sampled sequence, so the 6B model
never has more than one float32 layer on the chip. ``lowbits`` runs the
control: the same pass with each linear's input and the stored K/V
rounded per token to that many bits, the step below the served
precision's 8.

For the harness (``run.reference``) the module also says what the
architecture is: ``program_dims``, and ``builder``, ``program_params``
and ``counts``, which are ``weights.py``'s and ``counts.py``'s; and
``layer_step``, the pass over one layer, which ``rehearse.py`` compiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts  # noqa: F401  (the harness's operation and byte counts)
import weights as W
from weights import builder, program_params  # noqa: F401


def program_dims(cfg) -> dict:
    """Each key that a decoder configuration's ``dims`` may hold, as the
    program's ``ModelConfig`` has it. The program has no QKV bias."""
    return {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.hd, "n_layers": cfg.n_layers,
            "vocab": cfg.vocab, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "rope_fraction": 0.5 if cfg.rope_2d else 1.0,
            "tied": cfg.tie_embeddings, "qkv_bias": False}


def _round(x, bits: int):
    """Symmetric per-row (last axis) rounding to ``bits``, dequantized."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta, fraction):
    d = x.shape[-1]
    rd = int(d * fraction)
    freqs = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = pos[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    rot = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)
    return jnp.concatenate([rot.reshape(x[..., :rd].shape), x[..., rd:]], -1)


def _block(x, lin, dims, lowbits):
    """One layer over x (B, S, d) float32; lin: {name: (d_out, d_in)}."""
    b, s, _ = x.shape
    h, kv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]

    def mm(name, a):
        if lowbits:
            a = _round(a, lowbits)
        return a @ lin[name].T

    a = _norm(x, eps)
    q = mm("wq", a).reshape(b, s, h, hd)
    k = mm("wk", a).reshape(b, s, kv, hd)
    v = mm("wv", a).reshape(b, s, kv, hd)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = _rope(q, pos, dims["rope_theta"], dims["rope_fraction"])
    k = _rope(k, pos, dims["rope_theta"], dims["rope_fraction"])
    if lowbits:
        k, v = _round(k, lowbits), _round(v, lowbits)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def attend(qkv):                  # one sequence at a time
        q1, k1, v1 = qkv
        sc = jnp.einsum("qhd,khd->hqk", q1, k1) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v1)
    o = jax.lax.map(attend, (q, k, v)).reshape(b, s, h * hd)
    x = x + mm("wo", o)
    a = _norm(x, eps)
    return x + mm("down", jax.nn.silu(mm("gate", a)) * mm("up", a))


@functools.partial(jax.jit, static_argnames=("dims", "bits", "lowbits"))
def layer_step(x, key, index, dims, bits, lowbits):
    """Layer ``index`` over x (B, S, d_model) float32; ``dims`` as sorted
    items."""
    dims = dict(dims)
    lin = {n: q.astype(jnp.float32) * s
           for n, (q, s) in W.layer(key, index, dims, bits).items()}
    with jax.default_matmul_precision("highest"):
        return _block(x, lin, dims, lowbits)


def _logits(h, table, eps):
    with jax.default_matmul_precision("highest"):
        return _norm(h, eps) @ table.astype(jnp.float32).T


@functools.partial(jax.jit, static_argnames=("eps",))
def _row_gaps(h, hc, table, nxt, eps):
    """Gap of a token below the best reference logit at each position,
    in units of the standard deviation of the reference logits there.
    The token is ``nxt`` (the one served after each position) or, with
    the control's hidden states ``hc``, the one the control ranks first."""
    ref = _logits(h, table, eps)
    tok = nxt if hc is None else _logits(hc, table, eps).argmax(-1)
    got = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    return (ref.max(-1) - got) / ref.std(-1)


_tables = jax.jit(lambda key, fdims: W.tables(key, dict(fdims)),
                  static_argnums=1)


def _hidden(seed, fdims, bits, tokens, lowbits):
    key = W.base_key(seed)
    x = jnp.take(_tables(key, fdims)["embed"], tokens,
                 axis=0).astype(jnp.float32)
    for i in range(dict(fdims)["n_layers"]):
        x = layer_step(x, key, i, fdims, bits, lowbits)
    return x


def gaps(seed: int, dims: dict, bits: int, samples, length: int,
         rows: int, lowbits: int = 0):
    """Gaps of served tokens below the reference's best logit.

    ``samples`` is a list of at most ``rows`` (prompt, served tokens).
    Each sequence is run once, as prompt + served[:-1] padded to
    ``length`` (fixed per cell, so that every run compiles the same
    programs), through the reference; the logits at the last prompt
    position and after each served token but the last are read. Returns
    one float32 array per sample: the gap of each served token. With
    ``lowbits`` it returns the gaps of the tokens that the control (the
    same pass at ``lowbits``) ranks first, at the same positions.
    """
    fdims = tuple(sorted(dims.items()))
    seqs = [list(p) + list(t[:-1]) for p, t in samples]
    seqs += [[0]] * (rows - len(seqs))
    tokens = jnp.asarray([q + [0] * (length - len(q)) for q in seqs],
                         jnp.int32)
    h = _hidden(seed, fdims, bits, tokens, 0)
    hc = _hidden(seed, fdims, bits, tokens, lowbits) if lowbits else None
    table = _tables(W.base_key(seed), fdims)
    table = table.get("unembed", table["embed"])
    out = []
    for b, (p, t) in enumerate(samples):
        full = list(p) + list(t)
        nxt = jnp.asarray(full[1:] + [0] * (length + 1 - len(full)),
                          jnp.int32)
        g = _row_gaps(h[b], None if hc is None else hc[b], table, nxt,
                      dims["norm_eps"])
        out.append(jax.device_get(g)[len(p) - 1:len(p) - 1 + len(t)])
    return out
