"""Pallas kernel: live-page paged-attention decode over the serve pool.

The pure-jnp decode path (``models.attention.apply_attn_paged_decode``)
gathers the **full** ``pages_per_slot * page_size`` KV extent per slot per
step — at production ``max_len`` that gather is the decode memory hot
spot, and almost all of it is dead: a request that has produced 40
positions touches 3 pages, not 64. This kernel is the "pay only for live
state" counterpart (the serving twin of the paper's transitive reuse
argument): one grid step owns one slot, reads that slot's row of the
``(n_slots, pages_per_slot)`` page table plus its step count, and walks
only the ``steps // page_size + 1`` **live** pages. Dead pages are never
loaded — the walks are ``lax.scan``s over the page axis whose per-page
``lax.cond`` skips the loads and substitutes a ``NEG_INF`` score tile /
zero PV partial, so the work per slot is proportional to its live length,
every shape stays static, and the traced program stays O(1) equations no
matter how large ``pages_per_slot`` grows.

Parity with the gather path (the differential oracle, kept in
``apply_attn_paged_decode``) is by construction, not by tolerance:

* **scores** contract only over ``head_dim`` — each (kv, group, lane)
  score is an independent dot of the same two rows, so per-page tiles are
  bitwise slices of the full score matrix;
* the **softmax** runs over the full static extent with dead lanes at
  exactly ``NEG_INF`` (what the oracle's mask produces), so dead lanes
  collapse to exactly ``0.0``;
* the **P·V** contraction is int32 under ``quant_attention`` (exact under
  any page grouping); the float layouts accumulate per-page partials in
  f32, differing from the oracle's single dot only in f32 summation
  order — the same class of difference the suffix-prefill path already
  carries, and the engine's bit-identity bar (argmax tokens) is pinned by
  tests/test_serve_engine.py either way;
* interpret-mode pallas compiles ``x / <literal>`` to a reciprocal
  multiply (1 ulp off exact division, which is what the oracle's jit
  emits), so the in-kernel quantizers divide by ``qmax`` passed as a
  runtime operand — array-denominator division is exact on both sides.

All four pool layouts are covered (exact/int8 pool x quant_attention
on/off), mirroring ``attend_cached`` operation-for-operation — including
multiplication order of the scale factors and the working dtype of every
``quantize_per_token`` call, which is what makes the int8 layouts
bit-exact. Like the sibling kernels this runs interpret-mode on CPU; a
silicon lowering would stream K/V pages through VMEM with the same table
walk (the page table row and step count are scalar-prefetch operands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant import quantize_per_token

__all__ = ["paged_attention"]

NEG_INF = -1e30      # == models.attention.NEG_INF (kernels stay model-free)


def _quantize_rows(x, qmax):
    """``quantize_per_token`` with the quantization max as a traced array
    (``qmax`` (1,) f32 holding 127.0): bitwise the same math, but the
    divisions keep an array denominator so interpret-mode pallas cannot
    constant-fold them into reciprocal multiplies."""
    qm = qmax.astype(x.dtype)[0]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qm
    q = jnp.clip(jnp.round(x / scale), -128, 127).astype(jnp.int8)
    return q, scale


def _decode_kernel(*refs, quant: bool, int8_pool: bool, pages: int,
                   ps: int, scale: float, unpack):
    """One slot: cond-guarded live-page walk -> full-extent softmax ->
    cond-guarded live-page P·V accumulation."""
    refs = list(refs)
    table_ref, steps_ref, layer_ref, q_ref = refs[:4]
    refs = refs[4:]
    sq_ref = None
    if quant:
        sq_ref, refs = refs[0], refs[1:]
    pool_ref, qmax_ref, out_ref = refs
    qmax = qmax_ref[...]                              # (1,) f32: 127.0

    qh = q_ref[0]                                     # (KV, G, hd)
    kv, g, hd = qh.shape
    layer = layer_ref[0]

    def page(name, pid):
        """Segment ``name`` of page ``pid`` of this layer: (ps, KV, w)."""
        return unpack(pool_ref[layer, pid], names=(name,))[name]

    step = steps_ref[0]
    n_live = step // ps + 1                           # pages holding rows
    sq = sq_ref[0] if quant else None                 # (KV, G, 1)
    s_full = pages * ps

    # ---- phase 1: per-page score tiles (+ per-page V metadata) ----------
    def score_tile(pid):
        kpage = page("k", pid)                        # (ps, KV, hd)
        if quant:
            if int8_pool:
                kk, sks = kpage, page("ks", pid)      # stored f32 scales
            else:
                kk, sks = _quantize_rows(kpage, qmax)  # pool-dtype scales
            s32 = jnp.einsum("kgd,skd->kgs", qh, kk,
                             preferred_element_type=jnp.int32)
            sk_b = sks[..., 0].T[:, None, :]          # (KV, 1, ps)
            return s32.astype(jnp.float32) * scale * sq * sk_b
        if int8_pool:
            kf = kpage.astype(jnp.float32) * page("ks", pid)
            return jnp.einsum("kgd,skd->kgs", qh, kf) * scale
        return jnp.einsum("kgd,skd->kgs", qh, kpage) \
            .astype(jnp.float32) * scale

    def vmeta_tile(pid):
        """Per-page V metadata the P·V phase needs at full extent: stored
        per-position V scales (int8 pool fold) or the page's |V| max
        (dynamic re-quantization). Dead table entries point at the null
        page (pid 0), matching what the oracle's gather would read."""
        if quant and int8_pool:
            return page("vs", pid)[..., 0].T           # (KV, ps)
        if quant:
            return jnp.max(jnp.abs(page("v", pid)), axis=0)   # (KV, hd)
        return None

    # the page walks are lax.scans over the (static) page axis, not
    # Python-unrolled loops: the traced program stays O(1) equations no
    # matter how large pages_per_slot is (an unrolled walk at
    # max_len=512/page_size=4 is 128 conds per phase per layer — the
    # trace/compile cost swamps the live-page saving), while the op
    # order per page is identical, so results stay bitwise the same
    neg = jnp.full((kv, g, ps), NEG_INF, jnp.float32)
    idx = jnp.arange(pages, dtype=jnp.int32)

    def tile_step(vacc, j):
        pid = table_ref[0, j]
        parts = jax.lax.cond(
            j < n_live,
            lambda: (score_tile(pid), vmeta_tile(pid)),
            lambda: (neg, vmeta_tile(jnp.int32(0))))   # the null page
        if quant and int8_pool:                        # stack stored scales
            return None, parts
        if quant:                                      # running |V| max
            return jnp.maximum(vacc, parts[1]), parts[0]
        return None, parts[0]                          # no V metadata

    if quant and int8_pool:
        _, (tiles, vs_pages) = jax.lax.scan(tile_step, None, idx)
        vmeta = jnp.transpose(vs_pages, (1, 0, 2)) \
            .reshape(kv, s_full)                       # (KV, S)
    elif quant:
        vmax0 = jnp.full((kv, hd), -jnp.inf, pool_ref.dtype)
        vmax, tiles = jax.lax.scan(tile_step, vmax0, idx)
    else:
        _, tiles = jax.lax.scan(tile_step, None, idx)
    s = jnp.transpose(tiles, (1, 2, 0, 3)) \
        .reshape(kv, g, s_full)                        # (KV, G, S)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s_full,), 0)
    valid = lane < jnp.minimum(step + 1, s_full)       # == the oracle mask
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)                     # dead lanes -> 0.0

    # ---- phase 2: live-page P·V accumulation ----------------------------
    def walk(acc, partial):
        """Scan the page axis, accumulating live pages' partials in page
        order (the same left-to-right order the unrolled loop used)."""
        def step(a, j):
            pid = table_ref[0, j]
            return jax.lax.cond(j < n_live,
                                lambda a: a + partial(pid, j),
                                lambda a: a, a), None
        acc, _ = jax.lax.scan(step, acc, idx)
        return acc

    def ptile(pr, j):
        """pr[..., j*ps:(j+1)*ps] with a traced page index."""
        return jax.lax.dynamic_slice_in_dim(pr, j * ps, ps, axis=2)

    if quant and int8_pool:
        # fold the stored per-position V scales into P before quantizing
        # (attend_cached's int8-pool path) — the int8 contraction then
        # accumulates exactly, page by page
        vs_b = vmeta[:, None, :]                             # (KV, 1, S)
        qp, sps = _quantize_rows(p * vs_b, qmax)
        o32 = walk(jnp.zeros((kv, g, hd), jnp.int32),
                   lambda pid, j: jnp.einsum(
                       "kgs,skd->kgd", ptile(qp, j), page("v", pid),
                       preferred_element_type=jnp.int32))
        out_ref[0] = o32.astype(jnp.float32) * sps
    elif quant:
        qp, sps = _quantize_rows(p, qmax)
        # |V| max over the gathered extent == max over per-page maxes
        # (dead entries contribute the null page, as the gather would)
        sv = vmax / qmax.astype(vmax.dtype)[0] + 1e-8  # (KV, hd), pool dtype

        def pv(pid, j):
            qv = jnp.clip(jnp.round(page("v", pid) / sv),
                          -128, 127).astype(jnp.int8)
            return jnp.einsum("kgs,skd->kgd", ptile(qp, j), qv,
                              preferred_element_type=jnp.int32)
        o32 = walk(jnp.zeros((kv, g, hd), jnp.int32), pv)
        out_ref[0] = o32.astype(jnp.float32) * sps * sv[:, None, :]
    elif int8_pool:
        out_ref[0] = walk(
            jnp.zeros((kv, g, hd), jnp.float32),
            lambda pid, j: jnp.einsum(
                "kgs,skd->kgd", ptile(p, j),
                page("v", pid).astype(jnp.float32) * page("vs", pid)))
    else:
        pc = p.astype(pool_ref.dtype)
        out_ref[0] = walk(
            jnp.zeros((kv, g, hd), jnp.float32),
            lambda pid, j: jnp.einsum(
                "kgs,skd->kgd", ptile(pc, j), page("v", pid),
                preferred_element_type=jnp.float32))


def paged_attention(q, pool, layer, page_indices, steps, cfg, scale, *,
                    unpack, interpret: bool | None = None):
    """Live-page decode attention. ``q`` (B, 1, H, hd) post-RoPE;
    ``pool`` the layer-stacked page-pool leaf (L, n_pages, page_elems),
    read at layer ``layer`` (a traced int32 scalar); ``unpack(pages,
    names=...)`` splits (..., page_elems) pages into ``{name: (...,
    page_size, KV, width)}`` segments ("k", "v", + "ks", "vs" under KV8);
    ``page_indices`` (B, P) int32; ``steps`` (B,) int32 — the position
    written this step. Returns (B, 1, H, hd) in the dtype
    ``attend_cached`` would produce for the same layout."""
    if interpret is None:
        from repro.kernels import ops
        interpret = ops.default_interpret()
    b, sq_len, h, hd = q.shape
    if sq_len != 1:
        raise ValueError(f"decode kernel expects Sq == 1, got {sq_len}")
    kvh = cfg.n_kv_heads
    segs = jax.eval_shape(unpack, jax.ShapeDtypeStruct(pool.shape[-1:],
                                                       pool.dtype))
    ps = segs["k"].shape[0]
    g = h // kvh
    pages = page_indices.shape[1]
    quant = cfg.quant_attention
    int8_pool = "ks" in segs
    qg = q.reshape(b, kvh, g, hd)

    inputs = [page_indices.astype(jnp.int32), steps.astype(jnp.int32),
              jnp.reshape(layer, (1,)).astype(jnp.int32)]
    in_specs = [pl.BlockSpec((1, pages), lambda i: (i, 0)),
                pl.BlockSpec((1,), lambda i: (i,)),
                pl.BlockSpec((1,), lambda i: (0,))]
    qspec = pl.BlockSpec((1, kvh, g, hd), lambda i: (i, 0, 0, 0))
    if quant:
        qq, sqs = quantize_per_token(qg)       # pool-dtype scale, like the
        inputs += [qq, sqs]                    # oracle's quantize of q
        in_specs += [qspec, pl.BlockSpec((1, kvh, g, 1),
                                         lambda i: (i, 0, 0, 0))]
    else:
        # the int8-pool float path contracts q in f32 (oracle casts)
        inputs.append(qg.astype(jnp.float32) if int8_pool else qg)
        in_specs.append(qspec)
    inputs.append(pool)
    in_specs.append(pl.BlockSpec(pool.shape, lambda i: (0, 0, 0)))
    # 127.0 as a runtime operand: a literal denominator would let the
    # interpret-mode compiler fold the quantizer divisions into reciprocal
    # multiplies, 1 ulp off the oracle's exact division
    qmax = jnp.full((1,), 127.0, jnp.float32)
    inputs.append(qmax)
    in_specs.append(pl.BlockSpec((1,), lambda i: (0,)))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, quant=quant, int8_pool=int8_pool,
                          pages=pages, ps=ps, scale=scale, unpack=unpack),
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, hd), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        interpret=interpret,
    )(*inputs)
    out = out.reshape(b, 1, h, hd)
    if not quant and not int8_pool:
        out = out.astype(pool.dtype)           # the oracle's bf16 P·V dot
    return out
