"""Non-attention block types: MLP (swiglu/gelu), MoE (expert-parallel
ragged dispatch), RG-LRU recurrent block, mLSTM/sLSTM blocks.

Every block type exposes:
  init_<t>(key, cfg) -> params
  apply_<t>(params, x, cfg, *, cache, step, ...) -> (y, new_cache)
  cache_<t>(cfg, batch, max_len) -> cache pytree (or None)
Residual connections live in model.py; blocks are pre-norm bodies.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ambient_mesh, shard
from repro.models.attention import rms_norm
from repro.quant import linear_init, linear_apply

# --------------------------------------------------------------------------
# Dense MLP
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, gelu: bool = False):
    ks = jax.random.split(key, 3)
    p = {"norm": jnp.ones((cfg.d_model,), jnp.float32),
         "up": linear_init(ks[0], cfg.d_model, cfg.d_ff, cfg.quant, cfg.dtype),
         "down": linear_init(ks[1], cfg.d_ff, cfg.d_model, cfg.quant, cfg.dtype)}
    if not gelu:
        p["gate"] = linear_init(ks[2], cfg.d_model, cfg.d_ff, cfg.quant,
                                cfg.dtype)
    return p


def apply_mlp(params, x, cfg: ModelConfig):
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    up = linear_apply(params["up"], xn, cfg.quant)
    up = shard(up, "batch", None, "ffn")
    if "gate" in params:
        gate = linear_apply(params["gate"], xn, cfg.quant)
        gate = shard(gate, "batch", None, "ffn")
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return linear_apply(params["down"], h, cfg.quant).astype(x.dtype)


# --------------------------------------------------------------------------
# MoE with expert-parallel ragged dispatch (DESIGN.md §4)
# --------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    lim = 1.0 / math.sqrt(d)
    p = {
        "norm": jnp.ones((d,), jnp.float32),
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * lim,
        "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * lim,
        "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * lim,
        "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32)
        * (1.0 / math.sqrt(f)),
    }
    p = {k: (v.astype(cfg.dtype) if k != "norm" else v) for k, v in p.items()}
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg)
    return p


def _moe_local(x2, gates, eids, w_gate, w_up, w_down, n_local: int,
               capacity: int):
    """Expert computation on one shard's local tokens.

    x2 (N, D); gates/eids (N, K) *local* expert ids in [0, n_local) or
    n_local for not-owned. Sorted-capacity ragged_dot dispatch.
    """
    n, k = eids.shape
    d = x2.shape[-1]
    flat_e = eids.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e, stable=True)            # owned groups first
    keep = order[:capacity]
    e_kept = flat_e[keep]
    tok_kept = flat_tok[keep]
    g_kept = jnp.where(e_kept < n_local, flat_g[keep], 0.0)
    xs = x2[tok_kept]                                   # (C, D)
    group_sizes = jnp.bincount(jnp.minimum(e_kept, n_local),
                               length=n_local + 1)[:n_local].astype(jnp.int32)
    # pad rhs with nothing: rows beyond sum(group_sizes) fall into an
    # implicit tail we mask via g_kept == 0.
    # keep the expert math in the working dtype end-to-end: the MXU still
    # accumulates in f32 internally, but bf16 op outputs keep the forward
    # psum AND the backward cotangent psums/all-reduces at half the wire
    # bytes (§Perf HC3 — f32 cotangents were the dominant collective).
    acc = x2.dtype
    gate_h = jax.lax.ragged_dot(xs, w_gate, group_sizes,
                                preferred_element_type=acc)
    up_h = jax.lax.ragged_dot(xs, w_up, group_sizes,
                              preferred_element_type=acc)
    h = jax.nn.silu(gate_h) * up_h
    out = jax.lax.ragged_dot(h.astype(w_down.dtype), w_down, group_sizes,
                             preferred_element_type=acc)
    y = jnp.zeros((n, d), x2.dtype)
    y = y.at[tok_kept].add((out * g_kept[:, None].astype(out.dtype))
                           .astype(x2.dtype))
    return y


def apply_moe(params, x, cfg: ModelConfig):
    """Top-k MoE; experts sharded over the "model" axis via shard_map when a
    mesh is ambient, single-shard fallback otherwise."""
    b, s, d = x.shape
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    logits = (xn.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    gates, eids = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    mesh = ambient_mesh()
    ep = (mesh is not None and "model" in mesh.axis_names
          and cfg.n_experts % mesh.shape["model"] == 0)

    if not ep:
        x2 = xn.reshape(b * s, d)
        cap = int(b * s * cfg.top_k)
        y = _moe_local(x2, gates.reshape(b * s, -1).astype(x.dtype),
                       eids.reshape(b * s, -1), params["w_gate"],
                       params["w_up"], params["w_down"], cfg.n_experts, cap)
        y = y.reshape(b, s, d)
    else:
        n_shards = mesh.shape["model"]
        n_local = cfg.n_experts // n_shards
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        xspec = P(dp_axes, None, None)

        def ep_fn(xn_l, gates_l, eids_l, wg, wu, wd):
            idx = jax.lax.axis_index("model")
            bl, sl = xn_l.shape[0], xn_l.shape[1]
            n_tok = bl * sl
            x2 = xn_l.reshape(n_tok, d)
            e2 = eids_l.reshape(n_tok, cfg.top_k)
            g2 = gates_l.reshape(n_tok, cfg.top_k)
            owned = (e2 // n_local) == idx
            lid = jnp.where(owned, e2 % n_local, n_local)
            cap = int(n_tok * cfg.top_k * cfg.expert_capacity_factor
                      / n_shards) + 1
            y = _moe_local(x2, g2.astype(xn_l.dtype), lid, wg[0], wu[0], wd[0],
                           n_local, cap)
            y = jax.lax.psum(y.astype(xn_l.dtype), "model")
            return y.reshape(bl, sl, d)

        wspec = P(None, "model", None, None)
        y = jax.shard_map(
            ep_fn, mesh=mesh,
            in_specs=(xspec, xspec, xspec, wspec, wspec, wspec),
            out_specs=xspec, check_vma=False,
        )(xn, gates.astype(x.dtype), eids,
          params["w_gate"][None], params["w_up"][None], params["w_down"][None])

    if "shared" in params:
        y = y + apply_mlp(params["shared"], x, cfg)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RG-LRU recurrent block (recurrentgemma; arXiv:2402.19427)
# --------------------------------------------------------------------------

RGLRU_C = 8.0


def init_rglru(key, cfg: ModelConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    u = jax.random.uniform(ks[4], (d,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / RGLRU_C))   # softplus^-1(-log u / c)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "w_x": linear_init(ks[0], d, d, cfg.quant, cfg.dtype),
        "w_gate": linear_init(ks[1], d, d, cfg.quant, cfg.dtype),
        "w_r": linear_init(ks[2], d, d, cfg.quant, cfg.dtype),
        "w_i": linear_init(ks[3], d, d, cfg.quant, cfg.dtype),
        "lam": lam,
        "w_out": linear_init(ks[5], d, d, cfg.quant, cfg.dtype),
    }


def cache_rglru(cfg: ModelConfig, batch: int):
    return {"h": jnp.zeros((batch, cfg.d_model), jnp.float32)}


def apply_rglru(params, x, cfg: ModelConfig, *, cache=None, prefill=False):
    """Griffin-style recurrent block (temporal conv omitted; DESIGN.md §8).

    Returns (y, new_cache)."""
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    xi = linear_apply(params["w_x"], xn, cfg.quant)
    gate = jax.nn.gelu(linear_apply(params["w_gate"], xn, cfg.quant))
    r = jax.nn.sigmoid(linear_apply(params["w_r"], xn, cfg.quant)
                       .astype(jnp.float32))
    i = jax.nn.sigmoid(linear_apply(params["w_i"], xn, cfg.quant)
                       .astype(jnp.float32))
    log_a = -RGLRU_C * jax.nn.softplus(params["lam"]) * r    # (B,S,D) f32
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * xi.astype(jnp.float32))
    if cache is None or prefill:
        def combine(lhs, rhs):
            a1, b1 = lhs
            a2, b2 = rhs
            return a1 * a2, a2 * b1 + b2
        _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
        new_cache = {"h": h[:, -1]} if prefill else None
    else:
        h = a[:, 0] * cache["h"] + b[:, 0]
        new_cache = {"h": h}
        h = h[:, None]
    y = linear_apply(params["w_out"], (h.astype(x.dtype) * gate), cfg.quant)
    return y.astype(x.dtype), new_cache


# --------------------------------------------------------------------------
# xLSTM blocks (arXiv:2405.04517), chunkwise-parallel mLSTM + scanned sLSTM
# --------------------------------------------------------------------------

MLSTM_CHUNK = 64


def init_mlstm(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    return {
        "norm": jnp.ones((d,), jnp.float32),
        "w_q": linear_init(ks[0], d, h * hd, cfg.quant, cfg.dtype),
        "w_k": linear_init(ks[1], d, h * hd, cfg.quant, cfg.dtype),
        "w_v": linear_init(ks[2], d, h * hd, cfg.quant, cfg.dtype),
        "w_if": linear_init(ks[3], d, 2 * h, cfg.quant, cfg.dtype),
        "w_o": linear_init(ks[4], h * hd, d, cfg.quant, cfg.dtype),
    }


def cache_mlstm(cfg: ModelConfig, batch: int):
    h, hd = cfg.n_heads, cfg.hd
    return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32)}


def _mlstm_proj(params, x, cfg):
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = linear_apply(params["w_q"], xn, cfg.quant).reshape(b, s, h, hd)
    k = linear_apply(params["w_k"], xn, cfg.quant).reshape(b, s, h, hd) \
        * (hd ** -0.5)
    v = linear_apply(params["w_v"], xn, cfg.quant).reshape(b, s, h, hd)
    gif = linear_apply(params["w_if"], xn, cfg.quant).reshape(b, s, h, 2)
    log_i = gif[..., 0].astype(jnp.float32)               # input gate (log)
    log_f = -jax.nn.softplus(-gif[..., 1].astype(jnp.float32))  # log sigmoid
    return q, k, v, log_i, log_f


def apply_mlstm(params, x, cfg: ModelConfig, *, cache=None, prefill=False):
    """Matrix-memory LSTM; chunkwise parallel for sequences, one-step with
    cache for decode. Stabilizer-free formulation in f32 (DESIGN.md §8)."""
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q, k, v, log_i, log_f = _mlstm_proj(params, x, cfg)

    if cache is not None and not prefill:                  # decode step
        i_g = jnp.exp(log_i[:, 0])                         # (B,H)
        f_g = jnp.exp(log_f[:, 0])
        kv = jnp.einsum("bhk,bhv->bhkv", k[:, 0].astype(jnp.float32),
                        v[:, 0].astype(jnp.float32))
        C = f_g[..., None, None] * cache["C"] + i_g[..., None, None] * kv
        n = f_g[..., None] * cache["n"] + i_g[..., None] \
            * k[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhkv,bhk->bhv", C, q[:, 0].astype(jnp.float32))
        den = jnp.abs(jnp.einsum("bhk,bhk->bh", n,
                                 q[:, 0].astype(jnp.float32)))
        out = (num / jnp.maximum(den, 1.0)[..., None])[:, None]
        new_cache = {"C": C, "n": n}
    else:                                                  # chunkwise train
        c = MLSTM_CHUNK if s % MLSTM_CHUNK == 0 else s
        nc = s // c
        def resh(t):
            return t.reshape(b, nc, c, *t.shape[2:])
        qc, kc, vc = map(resh, (q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32)))
        lic, lfc = map(resh, (log_i, log_f))
        F = jnp.cumsum(lfc, axis=2)                        # (B,NC,C,H)
        Ftot = F[:, :, -1]
        # intra-chunk: A[t,u] = exp(F_t - F_u + log i_u)  for u <= t
        decay = F[:, :, :, None, :] - F[:, :, None, :, :] + lic[:, :, None]
        tri = jnp.tril(jnp.ones((c, c), bool))
        A = jnp.where(tri[None, None, :, :, None], jnp.exp(decay), 0.0)
        scores = jnp.einsum("bnthd,bnuhd->bntuh", qc, kc) * A
        intra = jnp.einsum("bntuh,bnuhd->bnthd", scores, vc)
        n_intra = jnp.einsum("bntuh,bnuhd->bnthd", A, kc)
        # inter-chunk recurrence over chunk summaries
        w_end = jnp.exp(Ftot[:, :, None, :] - F + lic)     # (B,NC,C,H)
        kv_sum = jnp.einsum("bnuh,bnuhk,bnuhv->bnhkv", w_end, kc, vc)
        k_sum = jnp.einsum("bnuh,bnuhk->bnhk", w_end, kc)

        def step(carry, xs):
            C_in, n_in = carry
            kv_c, k_c, ftot = xs
            C_out = jnp.exp(ftot)[..., None, None] * C_in + kv_c
            n_out = jnp.exp(ftot)[..., None] * n_in + k_c
            return (C_out, n_out), (C_in, n_in)

        C0 = cache["C"] if cache is not None else \
            jnp.zeros((b, h, hd, hd), jnp.float32)
        n0 = cache["n"] if cache is not None else \
            jnp.zeros((b, h, hd), jnp.float32)
        (C_fin, n_fin), (C_hist, n_hist) = jax.lax.scan(
            step, (C0, n0),
            (jnp.moveaxis(kv_sum, 1, 0), jnp.moveaxis(k_sum, 1, 0),
             jnp.moveaxis(Ftot, 1, 0)))
        C_hist = jnp.moveaxis(C_hist, 0, 1)                # (B,NC,H,K,V)
        n_hist = jnp.moveaxis(n_hist, 0, 1)
        inter = jnp.einsum("bnthd,bnhdv->bnthv", qc * jnp.exp(F)[..., None],
                           C_hist)
        n_inter = n_hist[:, :, None] * jnp.exp(F)[..., None]
        num = intra + inter
        den = jnp.abs(jnp.einsum("bnthd,bnthd->bnth", qc,
                                 n_intra + n_inter))
        out = (num / jnp.maximum(den, 1.0)[..., None]).reshape(b, s, h, hd)
        new_cache = {"C": C_fin, "n": n_fin} if prefill else None

    y = linear_apply(params["w_o"],
                     out.reshape(b, -1, h * hd).astype(x.dtype), cfg.quant)
    return y.astype(x.dtype), new_cache


def init_slstm(key, cfg: ModelConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 9)
    def lin(k_, i, o):
        return linear_init(k_, i, o, cfg.quant, cfg.dtype)
    return {"norm": jnp.ones((d,), jnp.float32),
            "w_z": lin(ks[0], d, d), "r_z": lin(ks[1], d, d),
            "w_i": lin(ks[2], d, d), "r_i": lin(ks[3], d, d),
            "w_f": lin(ks[4], d, d), "r_f": lin(ks[5], d, d),
            "w_o": lin(ks[6], d, d), "r_o": lin(ks[7], d, d),
            "w_out": lin(ks[8], d, d)}


def cache_slstm(cfg: ModelConfig, batch: int):
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"h": z, "c": z, "n": z, "m": z}


def _slstm_step(params, cfg, state, xt):
    """One stabilized exponential-gated step. xt (B, D)."""
    h, c, n, m = state["h"], state["c"], state["n"], state["m"]
    hd_ = h.astype(xt.dtype)
    def gate(wk, rk):
        return (linear_apply(params[wk], xt, cfg.quant)
                + linear_apply(params[rk], hd_, cfg.quant)).astype(jnp.float32)
    z = jnp.tanh(gate("w_z", "r_z"))
    o = jax.nn.sigmoid(gate("w_o", "r_o"))
    log_i = gate("w_i", "r_i")
    log_f = -jax.nn.softplus(-gate("w_f", "r_f"))
    m_new = jnp.maximum(log_f + m, log_i)
    c_new = jnp.exp(log_f + m - m_new) * c + jnp.exp(log_i - m_new) * z
    n_new = jnp.exp(log_f + m - m_new) * n + jnp.exp(log_i - m_new)
    h_new = o * (c_new / jnp.maximum(n_new, 1e-6))
    return {"h": h_new, "c": c_new, "n": n_new, "m": m_new}


def apply_slstm(params, x, cfg: ModelConfig, *, cache=None, prefill=False):
    b, s, d = x.shape
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    if cache is not None and not prefill:
        state = _slstm_step(params, cfg, cache, xn[:, 0])
        y = state["h"][:, None]
        new_cache = state
    else:
        state0 = cache if (prefill and cache is not None) \
            else cache_slstm(cfg, b)
        def body(st, xt):
            st = _slstm_step(params, cfg, st, xt)
            return st, st["h"]
        final, hs = jax.lax.scan(body, state0, jnp.moveaxis(xn, 1, 0))
        y = jnp.moveaxis(hs, 0, 1)
        new_cache = final if prefill else None
    y = linear_apply(params["w_out"], y.astype(x.dtype), cfg.quant)
    return y.astype(x.dtype), new_cache
