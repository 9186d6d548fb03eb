"""Attention: GQA self-attention (global/local/causal), cross-attention,
RoPE (incl. chatglm-style partial rotary), qk-norm, chunked (flash-style)
softmax for long sequences, rolling KV caches for local windows, and the
paper's dynamic int8 quantized attention GEMMs (Sec. 5.7: K/V treated as
weights with per-tile dynamic scoreboards → per-token dynamic quantization
on TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.quant import quantize_per_token

NEG_INF = -1e30
CHUNK_THRESHOLD = 2048        # direct softmax below, chunked scan above
Q_CHUNK = 1024                # query-chunk size for the flash-style path
ATTN_UNROLL: int | bool = 1   # roofline calibration unrolls the chunk scan
                              # (HloCostAnalysis counts while bodies once)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    out = (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale
    return out.astype(x.dtype)      # keep activations in the working dtype


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         partial: bool = False) -> jnp.ndarray:
    """x (B, S, H, D), positions (B, S). partial=True rotates only the first
    half of head_dim (chatglm's 2d RoPE keeps half the dims positional)."""
    d = x.shape[-1]
    rot_d = d // 2 if partial else d
    freqs = theta ** (-jnp.arange(0, rot_d, 2, dtype=jnp.float32) / rot_d)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, rd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot_d].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    out = out.reshape(xr.shape).astype(x.dtype)
    if partial:
        out = jnp.concatenate([out, x[..., rot_d:]], -1)
    return out


def _repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, KV, D) -> (B, S, KV*groups, D)."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _quantize_kv(t: jnp.ndarray):
    """KV8 cache quantization, pinned to float32 arithmetic.

    The per-token scale is max|t|/127 — computed in bf16 its final
    division may or may not keep the bf16 rounding depending on how XLA
    fuses it into the float32 cache store, so two programs writing the
    same K/V row (the dense prefill and the paged serve prefill) could
    store different scale bytes. Quantizing from f32 makes the stored
    (int8, scale) pair a pure function of the row values, program-shape
    independent — the bit-identity contract of repro.serve rests on it.

    The ``jax.named_scope`` tags every equation in this subgraph so the
    tracelint ``dtype-purity`` rule (repro.analysis) can statically
    reject any bf16 intermediate that sneaks back in — the rule anchors
    on the scope name, not on fragile equation positions.
    """
    with jax.named_scope("quantize_kv"):
        return quantize_per_token(t.astype(jnp.float32))


def _scores(q, k, scale, quant: bool):
    """einsum('bqhd,bkhd->bhqk'), optionally with dynamic-int8 operands —
    the TPU mapping of the paper's dynamic-scoreboard attention (Sec. 5.7:
    K/V treated as weights, quantized per tile at runtime)."""
    if quant:
        qq, sq = quantize_per_token(q)                    # (B,Sq,H,1)
        kk, sk = quantize_per_token(k)                    # (B,Sk,H,1)
        s32 = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                         preferred_element_type=jnp.int32)
        sq_b = jnp.moveaxis(sq, 2, 1)                     # (B,H,Sq,1)
        sk_b = jnp.moveaxis(sk, 2, 1)[..., 0][:, :, None, :]  # (B,H,1,Sk)
        return s32.astype(jnp.float32) * sq_b * sk_b * scale
    return jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale


def _pv(p, v, quant: bool):
    """P (B,H,Sq,Sk) @ V (B,Sk,H,D) -> (B,Sq,H,D), optionally int8."""
    if quant:
        qp, sp = quantize_per_token(p)                    # rows over Sk
        sv = jnp.max(jnp.abs(v), axis=1, keepdims=True) / 127.0 + 1e-8
        qv = jnp.clip(jnp.round(v / sv), -128, 127).astype(jnp.int8)
        o32 = jnp.einsum("bhqk,bkhd->bqhd", qp, qv,
                         preferred_element_type=jnp.int32)
        return o32.astype(jnp.float32) * jnp.moveaxis(sp, 1, 2) * sv
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@jax.named_scope("attention")
def attend_full(q, k, v, mask, scale, quant: bool = False):
    """Direct softmax attention. q (B,Sq,H,D), k/v (B,Sk,KV*,D) pre-repeat."""
    s = _scores(q, k, scale, quant)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _pv(p, v, quant)


@jax.named_scope("attention")
def attend_chunked(q, k, v, scale, causal: bool, window: int,
                   q_offset: int | jnp.ndarray = 0,
                   kv_len: jnp.ndarray | None = None):
    """Q-chunked attention: scan over query chunks with a rematerialised
    chunk body. Each chunk sees full K/V (cheap: K/V are (B,Sk,H,D) in the
    working dtype), so no online-softmax state is carried — the (Cq, Sk)
    score tile is transient in both forward AND backward (flash-style
    memory: the scan body is jax.checkpoint'ed, so AD recomputes scores per
    chunk instead of stashing the (Sq, Sk) attention matrix).

    q (B,Sq,H,D); k/v (B,Sk,H,D) already GQA-repeated.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    cq = Q_CHUNK if sq % Q_CHUNK == 0 else sq
    nc = sq // cq
    qc = jnp.moveaxis(q.reshape(b, nc, cq, h, d), 1, 0)
    kpos = jnp.arange(sk)

    def body(_, xs):
        qch, ci = xs
        qpos = q_offset + ci * cq + jnp.arange(cq)
        s = jnp.einsum("bqhd,bkhd->bhqk", qch, k).astype(jnp.float32) * scale
        ok = jnp.ones((cq, sk), bool)
        if causal:
            ok &= qpos[:, None] >= kpos[None, :]
        if window:
            ok &= qpos[:, None] - kpos[None, :] < window
        if kv_len is not None:
            ok &= kpos[None, :] < kv_len
        s = jnp.where(ok[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None,
                           (qc, jnp.arange(nc)), unroll=ATTN_UNROLL)
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, d)  # (B, Sq, H, D)


@jax.named_scope("attention")
def attend_cached(q, ck, cv, cks, cvs, valid, cfg: ModelConfig, scale,
                  sshard=None):
    """Decode-step attention against a contiguous (B, S, KV, D) cache view.

    q (B, Sq, H, D); ck/cv the cached keys/values — int8 with cks/cvs
    per-position scales for the KV8 layout, else the working dtype; valid
    (B', S) bool with B' in {1, B} — False keys are masked to NEG_INF.
    Grouped-head attention: the contraction runs against the cache directly
    in (KV, G) layout — no jnp.repeat materialisation of G x the cache
    (§Perf hillclimb 1, iteration 3). With a KV8 cache (iteration 4) the
    int8 values + stored scales feed the int GEMM directly. ``sshard``
    optionally constrains the score layout (the sequence-parallel dense
    decode path).

    This is the one implementation of cached-decode attention: the dense
    per-slot cache path AND the paged serve path both call it, so the two
    stay bit-identical by construction.
    """
    b, sq, h, hd = q.shape
    kv = ck.shape[2]
    groups = h // kv
    int8_cache = ck.dtype == jnp.int8
    qg = q.reshape(b, sq, kv, groups, hd)
    if cfg.quant_attention:
        qq, sqs = quantize_per_token(qg)             # (B,1,KV,G,1)
        if int8_cache:
            kk, sks = ck, cks
        else:
            kk, sks = quantize_per_token(ck)         # (B,S,KV,1)
        s32 = jnp.einsum("bqkgd,bskd->bkgqs", qq, kk,
                         preferred_element_type=jnp.int32)
        sk_b = sks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
        s = (s32.astype(jnp.float32) * scale
             * jnp.moveaxis(sqs, 1, 3)                # (B,KV,G,1,1)
             * sk_b)                                  # (B,KV,1,1,S)
    elif int8_cache:
        kf = ck.astype(jnp.float32) * cks
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                       kf) * scale
    else:
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck) \
            .astype(jnp.float32) * scale
    if sshard is not None:
        s = sshard(s)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if cfg.quant_attention:
        if int8_cache:
            # fold the per-position V scales into P before quantizing —
            # the int8 contraction then needs no per-s rescale.
            vs_b = cvs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
            qp, sps = quantize_per_token(p * vs_b)
            qv = cv
            sv_out = 1.0
        else:
            qp, sps = quantize_per_token(p)
            sv = jnp.max(jnp.abs(cv), axis=1, keepdims=True) / 127. + 1e-8
            qv = jnp.clip(jnp.round(cv / sv), -128, 127).astype(jnp.int8)
            sv_out = sv[:, :, :, None, :]
        o32 = jnp.einsum("bkgqs,bskd->bqkgd", qp, qv,
                         preferred_element_type=jnp.int32)
        out = (o32.astype(jnp.float32)
               * jnp.moveaxis(sps, -1, 1) * sv_out)
    elif int8_cache:
        vf = cv.astype(jnp.float32) * cvs
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    else:
        out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(cv.dtype), cv)
    return out.reshape(b, sq, h, hd)


# --------------------------------------------------------------------------
# Block-level self/cross attention with cache handling
# --------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, cross: bool = False):
    from repro.quant import linear_init
    hd, h, kv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    qcfg = cfg.quant
    p = {
        "norm": jnp.ones((cfg.d_model,), jnp.float32),
        "wq": linear_init(ks[0], cfg.d_model, h * hd, qcfg, cfg.dtype),
        "wk": linear_init(ks[1], cfg.d_model, kv * hd, qcfg, cfg.dtype),
        "wv": linear_init(ks[2], cfg.d_model, kv * hd, qcfg, cfg.dtype),
        "wo": linear_init(ks[3], h * hd, cfg.d_model, qcfg, cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    window: int = 0, cross: bool = False):
    size = min(max_len, window) if window else max_len
    shape = (batch, size, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_cache_bits == 8 and not cross:
        # KV8: int8 cache + per-position scales (QServe-style; the paper's
        # "K/V as weights" under dynamic quantization, Sec. 5.7)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:-1] + (1,), jnp.float32),
                "vs": jnp.zeros(shape[:-1] + (1,), jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def apply_attn(params, x, cfg: ModelConfig, *, positions, cache=None,
               step=None, causal=True, window=0, context=None,
               prefill=False):
    """Self- or cross-attention block body (pre-norm, residual outside).

    Modes: train (cache=None, prefill=False), prefill (cache given — zeros —
    filled with the prompt's K/V and returned), decode (cache given,
    step-wise update). Returns (out, new_cache).
    """
    from repro.quant import linear_apply
    qcfg = cfg.quant
    b, sq, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = linear_apply(params["wq"], xn, qcfg).reshape(b, sq, h, hd)
    decode_cross = context is not None and cache is not None and not prefill
    if decode_cross:
        k = v = None                          # context K/V already cached
    else:
        src = context if context is not None else xn
        k = linear_apply(params["wk"], src, qcfg) \
            .reshape(b, src.shape[1], kv, hd)
        v = linear_apply(params["wv"], src, qcfg) \
            .reshape(b, src.shape[1], kv, hd)
        k = shard(k, "batch", None, "kv_heads", None)
        v = shard(v, "batch", None, "kv_heads", None)
    q = shard(q, "batch", None, "heads", None)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        if k is not None:
            k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if context is None:                       # RoPE only for self-attention
        q = rope(q, positions, cfg.rope_theta, cfg.rope_2d)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_2d)

    scale = hd ** -0.5
    new_cache = cache
    groups = h // kv

    if cache is not None and prefill:
        # write the prompt's K/V into the (possibly rolling) cache
        size = cache["k"].shape[1]
        src_len = k.shape[1]
        take = min(size, src_len)
        slots = (jnp.arange(take) + (src_len - take)) % size
        if cache["k"].dtype == jnp.int8:
            qk, ks = _quantize_kv(k[:, -take:])
            qv, vs = _quantize_kv(v[:, -take:])
            new_cache = {"k": cache["k"].at[:, slots].set(qk),
                         "v": cache["v"].at[:, slots].set(qv),
                         "ks": cache["ks"].at[:, slots].set(ks),
                         "vs": cache["vs"].at[:, slots].set(vs)}
        else:
            ck = cache["k"].at[:, slots].set(
                k[:, -take:].astype(cache["k"].dtype))
            cv = cache["v"].at[:, slots].set(
                v[:, -take:].astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}

    if context is not None and not decode_cross:      # cross, full pass
        kf = _repeat_kv(k, groups)
        vf = _repeat_kv(v, groups)
        mask = jnp.ones((b, 1, sq, kf.shape[1]), bool)
        out = attend_full(q, kf, vf, mask, scale, cfg.quant_attention)
    elif cache is None or prefill:            # train / prefill full pass
        kf = _repeat_kv(k, groups)
        vf = _repeat_kv(v, groups)
        if sq > CHUNK_THRESHOLD:
            out = attend_chunked(q, kf, vf, scale, causal, window)
        else:
            qp = positions[:, :, None]
            kp = positions[:, None, :]
            mask = jnp.ones((b, sq, sq), bool)
            if causal:
                mask &= qp >= kp
            if window:
                mask &= qp - kp < window
            out = attend_full(q, kf, vf, mask[:, None], scale,
                              cfg.quant_attention)
    else:                                     # decode step against cache
        size = cache["k"].shape[1]
        # Sequence-parallel decode (DESIGN.md §4): when GQA kv heads don't
        # divide the model axis, the cache is sharded on its sequence axis.
        # Without explicit constraints SPMD "involuntarily rematerializes"
        # (all-gathers) the cache every step — §Perf hillclimb 1.
        from repro.distributed.sharding import mesh_axis_size
        model_n = mesh_axis_size("model")
        seq_mode = (model_n > 1 and kv % model_n != 0
                    and size % model_n == 0)

        def cshard(t):
            return shard(t, "batch", "kv_seq", None, None) if seq_mode else t
        int8_cache = cache["k"].dtype == jnp.int8
        cks = cvs = None
        if decode_cross:
            ck, cv = cache["k"], cache["v"]
            kv_len = size
        else:
            slot = step % size if window else step

            def dus(buf, val):
                return jax.lax.dynamic_update_slice(
                    buf, val, (0, slot) + (0,) * (buf.ndim - 2))
            if int8_cache:
                qk_new, ks_new = _quantize_kv(k)
                qv_new, vs_new = _quantize_kv(v)
                ck = cshard(dus(cache["k"], qk_new))
                cv = cshard(dus(cache["v"], qv_new))
                cks = cshard(dus(cache["ks"], ks_new.astype(jnp.float32)))
                cvs = cshard(dus(cache["vs"], vs_new.astype(jnp.float32)))
                new_cache = {"k": ck, "v": cv, "ks": cks, "vs": cvs}
            else:
                ck = cshard(dus(cache["k"], k.astype(cache["k"].dtype)))
                cv = cshard(dus(cache["v"], v.astype(cache["v"].dtype)))
                new_cache = {"k": ck, "v": cv}
            kv_len = jnp.minimum(step + 1, size)
        valid = jnp.arange(size)[None, :] < kv_len
        sshard = ((lambda t: shard(t, "batch", None, None, None, "kv_seq"))
                  if seq_mode else None)
        out = attend_cached(q, ck, cv, cks, cvs, valid, cfg, scale,
                            sshard=sshard)

    out = out.reshape(b, sq, h * hd)
    y = linear_apply(params["wo"], out.astype(x.dtype), qcfg)
    return y.astype(x.dtype), new_cache


# --------------------------------------------------------------------------
# Paged KV cache (the continuous-batching serve path, repro.serve)
# --------------------------------------------------------------------------
#
# The pool holds every slot's K/V (+ per-position scales under KV8) in
# pages addressed through an int32 page table — the same static-gather
# trick DevicePlan uses for forest schedules, so decode is one fixed-shape
# jit regardless of which requests occupy which slots. Logical position p
# of a slot lives at row p % page_size of page page_indices[slot, p //
# page_size]; page 0 is the null page (never allocated — inactive slots
# point at it; writes aimed at it are dropped, so it stays zeros).
#
# One attention layer's pool is ONE leaf (n_pages, page_elems): a page is
# one row of the leaf, its segments side by side — the page_size K rows,
# then the V rows (KV x D each), then under KV8 the K and V scale rows
# (KV f32 each, kept as their int8 bytes). The bytes of each position are
# the ones the unfolded (n_pages, page_size, KV, D) pool held. Model
# stacks the repeats in front and carries the stack through its layer
# scan; each layer writes its new rows into the stacked leaf at [layer,
# page] and gathers its pages from it. The shape is for the TPU:
#   * a page row is 6528 int8 lanes on smollm-135m, 8448 on chatglm3-6b —
#     whole 128-lane tiles, so the leaf keeps plain row-major tiles. A
#     (KV, D) minor tile such as (3, 64) int8, or 48 f32 scales a page,
#     the compiler stores pages-minor, and then relays out the layer's
#     slice around every scatter and gather: whole-pool copies per layer
#     per step;
#   * the page count is rounded up to PAGE_TILE, whole int8 sublane tiles,
#     so a scatter's (layer, page) index flattens without a copy;
#   * a row lies inside a page row, where the TPU scatters only whole
#     minor windows (a narrower window becomes a loop, one row an
#     iteration): a write zeroes the lanes of its rows and adds the rows
#     in, two full-page scatters on the leaf's integer view, exact for
#     any bytes. Decode writes one page a slot; a prefill writes each
#     page once with all of its rows, as the scatter's cost goes by
#     update, not by byte.
PAGE_TILE = 32


def pool_layout(cfg: ModelConfig) -> tuple:
    """The segments of a pool page, in order: ``(name, dtype, width)``,
    each ``page_size`` rows of ``(KV, width)``. The leaf's dtype is the
    first segment's; a segment of another dtype is kept as its bytes."""
    hd = cfg.hd
    if cfg.kv_cache_bits == 8:
        return (("k", jnp.int8, hd), ("v", jnp.int8, hd),
                ("ks", jnp.float32, 1), ("vs", jnp.float32, 1))
    return (("k", cfg.dtype, hd), ("v", cfg.dtype, hd))


def _bitcast(a, dtype):
    """``a``'s bytes as ``dtype`` (no op when it already is one)."""
    if a.dtype == jnp.dtype(dtype):
        return a
    return jax.lax.bitcast_convert_type(a, dtype)


def _int_view(a):
    """``a`` as the signed integer type of its width."""
    return _bitcast(a, jnp.dtype(f"int{8 * a.dtype.itemsize}"))


def _spans(layout, kvh: int):
    """(name, dtype, width, row elements in the leaf's dtype) a segment."""
    leaf = jnp.dtype(layout[0][1]).itemsize
    return [(name, dt, w, kvh * w * jnp.dtype(dt).itemsize // leaf)
            for name, dt, w in layout]


def page_rows(layout, kvh: int, page_elems: int) -> int:
    """Rows (positions) a page of ``page_elems`` leaf elements holds."""
    return page_elems // sum(r for *_, r in _spans(layout, kvh))


def unpack_pages(pages, layout, kvh: int, names=None) -> dict:
    """Split (..., page_elems) pages into ``{name: (..., page_size, KV,
    width)}`` views in each segment's dtype (``names``: only those)."""
    ps = page_rows(layout, kvh, pages.shape[-1])
    lead = pages.shape[:-1]
    out, start = {}, 0
    for name, dt, w, row in _spans(layout, kvh):
        seg = pages[..., start:start + ps * row]
        start += ps * row
        if names is not None and name not in names:
            continue
        if jnp.dtype(dt) != pages.dtype:
            seg = _bitcast(seg.reshape(lead + (ps * kvh * w, -1)), dt)
        out[name] = seg.reshape(lead + (ps, kvh, w))
    return out


def _page_size(pool, cfg: ModelConfig) -> int:
    return page_rows(pool_layout(cfg), cfg.n_kv_heads, pool["kv"].shape[-1])


def init_attn_page_pool(cfg: ModelConfig, n_pages: int, page_size: int):
    """One attention layer's page pool (unstacked; Model stacks repeats):
    ``{"kv": (n_pages rounded up to PAGE_TILE, page_elems)}``."""
    layout = pool_layout(cfg)
    elems = page_size * sum(r for *_, r in _spans(layout, cfg.n_kv_heads))
    n = -(-n_pages // PAGE_TILE) * PAGE_TILE
    return {"kv": jnp.zeros((n, elems), layout[0][1])}


def _store_rows(pool, layer, page, off, rows: dict, cfg: ModelConfig,
                group: int = 1):
    """``rows[name]`` (..., KV, width) written at row ``off`` of page
    ``page`` of layer ``layer`` of the stacked leaf, for every segment at
    once; ``page`` / ``off`` have the rows' leading shape, flattened to
    lanes. Lanes go by runs of ``group``: a run writes one page, its first
    lane's, and a lane of the run that names another page is not written
    (the prefills' lanes run through whole pages from a page boundary, so
    ``group=page_size`` holds each run to one page; decode's slots each
    name their own page, ``group=1``). Rows aimed at the null page are
    dropped. Two full-page scatters a run, straight into the carried leaf
    (see the section comment): one zeroes the rows' lanes, one adds the
    rows. Returns the new pool."""
    buf = pool["kv"]
    layout = pool_layout(cfg)
    kvh = cfg.n_kv_heads
    ps = page_rows(layout, kvh, buf.shape[-1])
    page, off = page.reshape(-1), off.reshape(-1)
    pad = -page.shape[0] % group
    page, off = jnp.pad(page, (0, pad)), jnp.pad(off, (0, pad))
    ng = page.shape[0] // group
    lanes = page.reshape(ng, group)
    first = lanes[:, 0]                                         # (ng,)
    live = (lanes == first[:, None]) & (first != 0)[:, None]
    onehot = (off.reshape(ng, group)[:, :, None] == jnp.arange(ps)) \
        & live[:, :, None]                                      # (ng, G, ps)
    slot = onehot.any(axis=1)                                   # (ng, ps)
    src = jnp.argmax(onehot, axis=1)[:, :, None]    # the lane of each slot
    vals, hits = [], []
    for name, dt, w, row in _spans(layout, kvh):
        r = rows[name].reshape(-1, kvh * w).astype(dt)
        r = _bitcast(r, buf.dtype).reshape(-1, row)
        r = jnp.pad(r, ((0, pad), (0, 0))).reshape(ng, group, row)
        r = (jnp.broadcast_to(r, (ng, ps, row)) if group == 1
             else jnp.take_along_axis(r, src, axis=1))          # (ng, ps, row)
        vals.append(r.reshape(ng, ps * row))
        hits.append(jnp.broadcast_to(slot[:, :, None], (ng, ps, row))
                    .reshape(ng, ps * row))
    hit = jnp.concatenate(hits, -1)
    val = _int_view(jnp.concatenate(vals, -1))
    ibuf = _int_view(buf)
    idx = jnp.stack([jnp.broadcast_to(layer, first.shape), first], -1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1))
    mode = jax.lax.GatherScatterMode.FILL_OR_DROP
    ibuf = jax.lax.scatter_mul(ibuf, idx, (~hit).astype(ibuf.dtype),
                               dnums, mode=mode)
    ibuf = jax.lax.scatter_add(ibuf, idx, jnp.where(hit, val, 0), dnums,
                               mode=mode)
    return {"kv": _bitcast(ibuf, buf.dtype)}


@jax.named_scope("kv_gather")
def _gather_pages(pool, layer, page_indices, cfg: ModelConfig, names):
    """Layer ``layer``'s pages gathered straight from the stacked leaf,
    each named segment as a contiguous (B, P*ps, KV, width) view in
    logical-position order — position p of slot b lands at index p, so
    the downstream attention sees exactly the layout the dense cache
    has."""
    g = pool["kv"][layer, page_indices]         # (B, P, page_elems)
    views = unpack_pages(g, pool_layout(cfg), cfg.n_kv_heads, names)
    b = page_indices.shape[0]
    return [views[n].reshape((b, -1) + views[n].shape[-2:]) for n in names]


def apply_attn_paged_prefill(params, x, cfg: ModelConfig, *, pool, layer,
                             prefix_page_ids, write_page_ids, write_offs,
                             write_from: int):
    """Suffix prefill for ONE request (B=1) against a page pool.

    ``x`` (1, Ls, d) embeds the prompt *suffix*: positions start..L-1 where
    ``start = len(prefix_page_ids) * page_size`` is the prefix-trie-shared
    range (0 when nothing is shared). The shared positions' K/V are
    gathered from the pool — bit-identical to recomputing them when the
    pool stores the working dtype, which is why the engine only skips
    computation for exact (non-KV8) pools. Suffix K/V for positions
    start+write_from..L-1 are written to ``(write_page_ids[i],
    write_offs[i])`` (``write_from`` > 0 lets a KV8 full-recompute skip
    re-writing pages it shares). The writes start at a page boundary and
    run through whole pages: each run of ``page_size`` writes names one
    page (the last run may stop short). ``pool`` is the layer-stacked
    pool (:func:`init_attn_page_pool`) and ``layer`` the traced layer
    index its rows are written and read at. Returns (out, new_pool).

    All lengths and index-array shapes are static: the jit retraces per
    (suffix_len, n_prefix_pages) pair — decode, by contrast, is a single
    shape (see :func:`apply_attn_paged_decode`).
    """
    from repro.quant import linear_apply
    qcfg = cfg.quant
    b, ls, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ps = _page_size(pool, cfg)
    n_pre = len(prefix_page_ids)
    start = n_pre * ps
    total = start + ls
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = linear_apply(params["wq"], xn, qcfg).reshape(b, ls, h, hd)
    k = linear_apply(params["wk"], xn, qcfg).reshape(b, ls, kvh, hd)
    v = linear_apply(params["wv"], xn, qcfg).reshape(b, ls, kvh, hd)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    q = shard(q, "batch", None, "heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    qpos = jnp.broadcast_to(start + jnp.arange(ls), (b, ls))
    q = rope(q, qpos, cfg.rope_theta, cfg.rope_2d)
    k = rope(k, qpos, cfg.rope_theta, cfg.rope_2d)
    scale = hd ** -0.5

    # write the suffix K/V into this request's (private) pages — same
    # quantization as the dense prefill cache write
    if cfg.kv_cache_bits == 8:
        qk, ks = _quantize_kv(k)
        qv, vs = _quantize_kv(v)
        stores = {"k": qk, "v": qv, "ks": ks, "vs": vs}
    else:
        stores = {"k": k, "v": v}
    new_pool = _store_rows(pool, layer, write_page_ids, write_offs,
                           {n: val[0, write_from:]
                            for n, val in stores.items()}, cfg, ps)

    # full K/V view: gathered shared prefix (exact working-dtype pools
    # only — the engine guarantees n_pre == 0 for KV8) + in-pass suffix.
    # The prefix pages are never written here, so they are read from the
    # updated leaf, which the scan then carries on without a copy.
    if n_pre:
        k_pre, v_pre = _gather_pages(new_pool, layer, prefix_page_ids[None],
                                     cfg, ("k", "v"))
        k_full = jnp.concatenate([k_pre.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([v_pre.astype(v.dtype), v], axis=1)
    else:
        k_full, v_full = k, v
    groups = h // kvh
    kf = _repeat_kv(k_full, groups)
    vf = _repeat_kv(v_full, groups)
    # branch on the TOTAL length, mirroring the dense prefill's threshold
    # (a shared-prefix suffix must attend the same way the reference
    # full-prompt pass did)
    if total > CHUNK_THRESHOLD:
        out = attend_chunked(q, kf, vf, scale, causal=True, window=0,
                             q_offset=start)
    else:
        kpos = jnp.arange(total)
        mask = qpos[:, :, None] >= kpos[None, None, :]
        out = attend_full(q, kf, vf, mask[:, None], scale,
                          cfg.quant_attention)
    out = out.reshape(b, ls, h * hd)
    y = linear_apply(params["wo"], out.astype(x.dtype), qcfg)
    return y.astype(x.dtype), new_pool


def apply_attn_paged_prefill_batched(params, x, cfg: ModelConfig, *, pool,
                                     layer, prefix_page_ids, prefix_lens,
                                     suffix_lens, write_page_ids, write_offs,
                                     write_pos):
    """Bucket-padded batched prefill: N requests' suffixes in ONE call.

    ``x`` (B, Lb, d) embeds each row's prompt suffix left-aligned and
    zero-padded to the bucket length Lb; row b's real extent is
    ``suffix_lens[b]``. ``prefix_page_ids`` (B, PPb) is the trie-shared
    prefix page table padded with the null page; ``prefix_lens[b]`` (a
    multiple of page_size) counts the row's real shared positions.
    Suffix K/V rows are written through ``(write_page_ids, write_offs)``
    (B, Lb) — ``write_pos[b, i]`` names the suffix row stored by write i,
    and dead write lanes target the null page. A row's writes start at a
    page boundary and run through whole pages, as in
    :func:`apply_attn_paged_prefill`. ``pool`` / ``layer`` as there.
    Returns (out, new_pool).

    Parity with the per-request path is per-row exact: positions, masks
    and stored bytes match :func:`apply_attn_paged_prefill` for every
    live lane, and padded K/V lanes are zeroed before attention so the
    int8-PV absmax scale (computed over the full padded extent under
    ``quant_attention``) sees ``max(|v|, 0) == max|v|`` — identical to
    the unpadded scale. Shapes are static per (B, Lb, PPb) bucket, which
    is what bounds the engine's prefill retraces to the bucket set.
    """
    from repro.quant import linear_apply
    qcfg = cfg.quant
    b, ls, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ps = _page_size(pool, cfg)
    n_pre = prefix_page_ids.shape[1]
    start = n_pre * ps
    total = start + ls
    if total > CHUNK_THRESHOLD:
        raise NotImplementedError(
            "bucketed prefill is full-extent only; the engine falls back "
            "to per-request chunked prefill above CHUNK_THRESHOLD")
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = linear_apply(params["wq"], xn, qcfg).reshape(b, ls, h, hd)
    k = linear_apply(params["wk"], xn, qcfg).reshape(b, ls, kvh, hd)
    v = linear_apply(params["wv"], xn, qcfg).reshape(b, ls, kvh, hd)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    q = shard(q, "batch", None, "heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    qpos = prefix_lens[:, None] + jnp.arange(ls)[None, :]       # (B, Lb)
    q = rope(q, qpos, cfg.rope_theta, cfg.rope_2d)
    k = rope(k, qpos, cfg.rope_theta, cfg.rope_2d)
    scale = hd ** -0.5

    # scatter each row's suffix K/V through its write lanes; lane i stores
    # suffix row write_pos[b, i] (rows, not a slice, so KV8 full-recompute
    # rows can skip re-writing shared pages); dead lanes hit the null page
    if cfg.kv_cache_bits == 8:
        qk, ks = _quantize_kv(k)
        qv, vs = _quantize_kv(v)
        stores = {"k": qk, "v": qv, "ks": ks, "vs": vs}
    else:
        stores = {"k": k, "v": v}
    new_pool = _store_rows(
        pool, layer, write_page_ids, write_offs,
        {n: jnp.take_along_axis(val, write_pos[:, :, None, None], axis=1)
         for n, val in stores.items()}, cfg, math.gcd(ps, ls))  # (B, Lb, ...)

    # full K/V view per row: gathered shared prefix (exact pools only —
    # the engine guarantees prefix_lens == 0 for KV8) + in-pass suffix.
    # Padded lanes are zeroed: masked out of the scores anyway, but the
    # quant-attention PV absmax must not see gathered/padded garbage. The
    # engine never batches a row with the writer of its prefix pages, so
    # real prefix lanes read the same bytes from the updated leaf.
    suf_idx = jnp.arange(ls)
    suf_valid = suf_idx[None, :] < suffix_lens[:, None]         # (B, Lb)
    if n_pre:
        pre_valid = jnp.arange(start)[None, :] < prefix_lens[:, None]
        k_pre, v_pre = _gather_pages(new_pool, layer, prefix_page_ids, cfg,
                                     ("k", "v"))
        k_full = jnp.concatenate([k_pre.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([v_pre.astype(v.dtype), v], axis=1)
        key_valid = jnp.concatenate([pre_valid, suf_valid], axis=1)
    else:
        k_full, v_full = k, v
        key_valid = suf_valid
    k_full = jnp.where(key_valid[:, :, None, None], k_full, 0)
    v_full = jnp.where(key_valid[:, :, None, None], v_full, 0)
    groups = h // kvh
    kf = _repeat_kv(k_full, groups)
    vf = _repeat_kv(v_full, groups)
    # per-row causal mask in logical positions: a prefix lane t is visible
    # iff real (qpos >= prefix_lens > t always holds); suffix lane j is
    # visible to query i iff j <= i and j is real — identical lane-for-lane
    # to the per-request qpos >= kpos mask
    causal = suf_idx[None, :, None] >= suf_idx[None, None, :]   # (1, Lb, Lb)
    mask_suf = causal & suf_valid[:, None, :]
    if n_pre:
        mask_pre = jnp.broadcast_to(pre_valid[:, None, :], (b, ls, start))
        mask = jnp.concatenate([mask_pre, mask_suf], axis=2)
    else:
        mask = mask_suf
    out = attend_full(q, kf, vf, mask[:, None], scale, cfg.quant_attention)
    out = out.reshape(b, ls, h * hd)
    y = linear_apply(params["wo"], out.astype(x.dtype), qcfg)
    return y.astype(x.dtype), new_pool


def apply_attn_paged_decode(params, x, cfg: ModelConfig, *, pool, layer,
                            page_indices, steps, kernel: bool | None = None):
    """One paged decode step over all slots. x (B, 1, d); page_indices
    (B, P) int32; steps (B,) int32 — the logical position the new token is
    written at (== tokens held so far). ``pool`` / ``layer`` as in
    :func:`apply_attn_paged_prefill`. Returns (out, new_pool).

    Inactive slots carry a page table of null pages (page 0) and step 0:
    their writes aim at the null page and are dropped, and their rows are
    garbage the scheduler never reads — the shapes never change, so
    decode re-traces exactly once per engine regardless of
    arrivals/evictions.

    ``kernel`` (default ``cfg.paged_kernel``) routes attention through the
    Pallas live-page kernel (:mod:`repro.kernels.paged_attention`), which
    walks only ``steps // page_size + 1`` pages per slot instead of
    gathering the full ``pages_per_slot`` extent. The gather +
    :func:`attend_cached` path below stays as the differential oracle.
    """
    from repro.quant import linear_apply
    qcfg = cfg.quant
    b, sq, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ps = _page_size(pool, cfg)
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    q = linear_apply(params["wq"], xn, qcfg).reshape(b, sq, h, hd)
    k = linear_apply(params["wk"], xn, qcfg).reshape(b, sq, kvh, hd)
    v = linear_apply(params["wv"], xn, qcfg).reshape(b, sq, kvh, hd)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    q = shard(q, "batch", None, "heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    pos = steps[:, None].astype(jnp.int32)
    q = rope(q, pos, cfg.rope_theta, cfg.rope_2d)
    k = rope(k, pos, cfg.rope_theta, cfg.rope_2d)
    scale = hd ** -0.5

    # scatter the new K/V row per slot: logical position steps[b] lives at
    # (page_indices[b, steps[b] // ps], steps[b] % ps)
    page = jnp.take_along_axis(page_indices, (steps // ps)[:, None],
                               axis=1)[:, 0]
    off = steps % ps
    int8_pool = cfg.kv_cache_bits == 8
    if int8_pool:
        qk, ks = _quantize_kv(k)
        qv, vs = _quantize_kv(v)
        stores = {"k": qk, "v": qv, "ks": ks, "vs": vs}
    else:
        stores = {"k": k, "v": v}
    new_pool = _store_rows(pool, layer, page, off,
                           {n: val[:, 0] for n, val in stores.items()}, cfg)

    if kernel is None:
        kernel = cfg.paged_kernel
    if kernel:
        from repro.kernels.paged_attention import paged_attention
        with jax.named_scope("attention"):
            out = paged_attention(
                q, new_pool["kv"], layer, page_indices, steps, cfg, scale,
                unpack=functools.partial(unpack_pages,
                                         layout=pool_layout(cfg), kvh=kvh))
    else:
        names = ("k", "v", "ks", "vs") if int8_pool else ("k", "v")
        ck, cv, *scales = _gather_pages(new_pool, layer, page_indices, cfg,
                                        names)
        cks, cvs = scales if int8_pool else (None, None)
        size = ck.shape[1]
        valid = jnp.arange(size)[None, :] < \
            jnp.minimum(steps + 1, size)[:, None]
        out = attend_cached(q, ck, cv, cks, cvs, valid, cfg, scale)
    out = out.reshape(b, sq, h * hd)
    y = linear_apply(params["wo"], out.astype(x.dtype), qcfg)
    return y.astype(x.dtype), new_pool
