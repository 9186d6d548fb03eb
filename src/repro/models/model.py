"""Model assembly: scan-over-super-blocks decoder (all 10 families), with
train / prefill / decode entry points and layer-stacked KV/recurrent caches.

A config's ``block_pattern`` defines one super-block; the super-block is
scanned ``n_repeats`` times (keeps HLO size O(pattern), essential for
512-device compiles). Pattern elements:
  attn   — GQA self-attention (+ MLP if d_ff > 0)
  cross  — cross-attention to ``context`` embeddings (+ MLP)
  rglru  — RG-LRU recurrent block (+ MLP)
  mlstm / slstm — xLSTM blocks (self-contained, no MLP)
Encoder-decoder (whisper): a separate non-causal encoder stack feeds
``context``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import attention as A
from repro.models import blocks as B

Params = dict[str, Any]

# Calibration knob (launch/calibrate.py): XLA's HloCostAnalysis counts a
# while-loop body ONCE regardless of trip count, so roofline calibration
# lowers shallow model variants with scans fully unrolled. 1 = rolled.
SCAN_UNROLL: int | bool = 1


def _scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=SCAN_UNROLL)


def _init_superblock(key, cfg: ModelConfig, pattern) -> Params:
    p = {}
    keys = jax.random.split(key, 2 * len(pattern))
    gelu = cfg.family == "audio"
    for i, kind in enumerate(pattern):
        k1, k2 = keys[2 * i], keys[2 * i + 1]
        if kind == "attn":
            p[f"b{i}"] = A.init_attn(k1, cfg)
        elif kind == "cross":
            p[f"b{i}"] = A.init_attn(k1, cfg, cross=True)
        elif kind == "rglru":
            p[f"b{i}"] = B.init_rglru(k1, cfg)
        elif kind == "mlstm":
            p[f"b{i}"] = B.init_mlstm(k1, cfg)
        elif kind == "slstm":
            p[f"b{i}"] = B.init_slstm(k1, cfg)
        else:
            raise ValueError(kind)
        wants_mlp = (kind in ("attn", "cross", "rglru") and cfg.d_ff
                     and (cfg.mlp_after is None or i in cfg.mlp_after))
        if wants_mlp:
            if cfg.family == "moe" and kind == "attn":
                p[f"m{i}"] = B.init_moe(k2, cfg)
            else:
                p[f"m{i}"] = B.init_mlp(k2, cfg, gelu=gelu)
    return p


def _apply_superblock(bp: Params, x, cfg: ModelConfig, pattern, *,
                      positions, caches=None, step=None, causal=True,
                      context=None, prefill=False):
    """One super-block pass; returns (x, new_caches or None)."""
    new_caches = {} if caches is not None else None
    sp = "seq_sp" if cfg.seq_shard else None
    for i, kind in enumerate(pattern):
        cache_i = caches.get(f"c{i}") if caches is not None else None
        if kind in ("attn", "cross"):
            window = cfg.local_window if kind == "attn" else 0
            y, nc = A.apply_attn(
                bp[f"b{i}"], x, cfg, positions=positions, cache=cache_i,
                step=step, causal=causal and kind == "attn", window=window,
                context=context if kind == "cross" else None,
                prefill=prefill)
        elif kind == "rglru":
            y, nc = B.apply_rglru(bp[f"b{i}"], x, cfg, cache=cache_i,
                                  prefill=prefill)
        elif kind == "mlstm":
            y, nc = B.apply_mlstm(bp[f"b{i}"], x, cfg, cache=cache_i,
                                  prefill=prefill)
        elif kind == "slstm":
            y, nc = B.apply_slstm(bp[f"b{i}"], x, cfg, cache=cache_i,
                                  prefill=prefill)
        else:
            raise ValueError(kind)
        x = shard(x + y, "batch", sp, None)
        if f"m{i}" in bp:
            if cfg.family == "moe" and kind == "attn":
                x = x + B.apply_moe(bp[f"m{i}"], x, cfg)
            else:
                x = x + B.apply_mlp(bp[f"m{i}"], x, cfg)
            x = shard(x, "batch", sp, None)
        if new_caches is not None:
            new_caches[f"c{i}"] = nc if nc is not None else cache_i
    return x, new_caches


def _apply_superblock_paged(bp: Params, x, cfg: ModelConfig, pattern, *,
                            pool, layer, mode: str, **attn_kw):
    """One super-block pass against a page pool (continuous-batching serve).

    ``pool`` holds the layer-stacked leaves and ``layer`` is the repeat
    being applied: each block writes and reads ``[layer, ...]`` of its
    stacked leaves. ``mode`` is "prefill", "prefill_batched" or "decode";
    ``attn_kw`` forwards to the paged attention entry point.
    Residual/MLP structure mirrors :func:`_apply_superblock` exactly —
    only the KV storage differs."""
    new_pool = {}
    sp = "seq_sp" if cfg.seq_shard else None
    paged_fns = {"prefill": A.apply_attn_paged_prefill,
                 "prefill_batched": A.apply_attn_paged_prefill_batched,
                 "decode": A.apply_attn_paged_decode}
    for i, kind in enumerate(pattern):
        if kind != "attn":
            raise NotImplementedError(
                f"paged serving supports self-attention blocks only, got "
                f"{kind!r} in pattern {pattern} (recurrent/cross blocks "
                f"keep per-slot dense state; see repro.serve)")
        fn = paged_fns[mode]
        y, npl = fn(bp[f"b{i}"], x, cfg, pool=pool[f"c{i}"], layer=layer,
                    **attn_kw)
        x = shard(x + y, "batch", sp, None)
        if f"m{i}" in bp:
            if cfg.family == "moe" and kind == "attn":
                x = x + B.apply_moe(bp[f"m{i}"], x, cfg)
            else:
                x = x + B.apply_mlp(bp[f"m{i}"], x, cfg)
            x = shard(x, "batch", sp, None)
        new_pool[f"c{i}"] = npl
    return x, new_pool


class Model:
    """Functional model: init / loss / prefill / decode_step."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern = cfg.block_pattern

    # ---- init --------------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        k_embed, k_blocks, k_enc, k_head = jax.random.split(key, 4)
        embed = (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model),
                                   jnp.float32) * 0.02).astype(cfg.dtype)
        bkeys = jax.random.split(k_blocks, cfg.n_repeats)
        blocks = jax.vmap(
            lambda k: _init_superblock(k, cfg, self.pattern))(bkeys)
        params: Params = {
            "embed": embed,
            "blocks": blocks,
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = (jax.random.normal(
                k_head, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
            ).astype(cfg.dtype)
        if cfg.block_tail:
            params["tail"] = _init_superblock(
                jax.random.fold_in(k_blocks, 7), cfg, cfg.block_tail)
        if cfg.is_encdec:
            ecfg = cfg.replace(mlp_after=None)
            ekeys = jax.random.split(k_enc, cfg.encoder_layers)
            params["encoder"] = jax.vmap(
                lambda k: _init_superblock(k, ecfg, ("attn",)))(ekeys)
            params["enc_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        return params

    # ---- serve-path plan warmup -------------------------------------------
    def precompile_plans(self, params: Params) -> dict:
        """Build every PTQ linear's engine ExecutionPlan ahead of serving.

        The offline half of the paper's offline/online split: walks the
        params pytree (including scan-stacked block weights) and warms the
        **process-level** plan cache — the only cache the qlinear hot-path
        callbacks consult (swap it via ``plancache.set_default_cache``) —
        so decode only ever pays ``run``. No-op (empty stats) unless this
        model's registered backend declares an offline plan half
        (``needs_plan`` capability, core/backend.py).
        """
        q = self.cfg.quant
        if q.mode != "ptq":
            return {"layers": 0, "plans": 0, "built": 0}
        from repro.core.backend import get_backend
        if not get_backend(q).needs_plan:
            return {"layers": 0, "plans": 0, "built": 0}
        from repro.core import plancache
        return plancache.precompile(params, q)

    def attach_device_plans(self, params: Params, *, mesh=None,
                            specs=None) -> Params:
        """Embed compiled DevicePlans into the params for pure-JAX serving.

        The device-resident half of the offline split: every PTQ layer
        gains a ``"dplan"`` pytree (stacked along scan-stacked leading
        axes) that ``lax.scan`` slices alongside the weights, so
        device-resident planned backends (``engine_jit``,
        ``engine_pallas``, any custom one declaring ``device_resident`` +
        ``needs_plan``) execute with zero host callbacks even though block
        weights are tracers inside the scan. With ``mesh=`` the plan
        leaves are placed under ``specs`` (``PartitionSpec``s — see
        ``repro.core.backend.shard_device_plan``) for multi-device
        serving. No-op unless the configured backend has both
        capabilities.
        """
        q = self.cfg.quant
        if q.mode != "ptq":
            return params
        from repro.core.backend import get_backend
        b = get_backend(q)
        if not (b.needs_plan and b.device_resident):
            return params
        from repro.core import plancache
        return plancache.attach_device_plans(params, q, mesh=mesh,
                                             specs=specs)

    # ---- shared ------------------------------------------------------------
    def _embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.cfg.dtype)
        return shard(x, "batch", None, None)

    def _logits(self, params, x):
        x = A.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        table = params.get("unembed", params["embed"])
        logits = jax.lax.dot_general(
            x, table.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())))
        return shard(logits.astype(jnp.float32), "batch", None, "vocab")

    def _encode(self, params, frames):
        cfg = self.cfg.replace(mlp_after=None)
        x = shard(frames.astype(cfg.dtype), "batch", None, None)
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

        def body(carry, bp):
            y, _ = _apply_superblock(bp, carry, cfg, ("attn",),
                                     positions=pos, causal=False)
            return y, None
        x, _ = _scan(body, x, params["encoder"])
        return A.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _context(self, params, batch):
        if self.cfg.is_encdec:
            return self._encode(params, batch["context"])
        if self.cfg.n_context_tokens:
            return shard(batch["context"].astype(self.cfg.dtype),
                         "batch", None, None)
        return None

    # ---- train -------------------------------------------------------------
    def loss(self, params: Params, batch: dict) -> jnp.ndarray:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        context = self._context(params, batch)
        x = self._embed_tokens(params, tokens)
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

        def body(carry, bp):
            y, _ = _apply_superblock(bp, carry, cfg, self.pattern,
                                     positions=pos, context=context)
            return y, None
        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = _scan(body, x, params["blocks"])
        if cfg.block_tail:
            x, _ = _apply_superblock(params["tail"], x, cfg, cfg.block_tail,
                                     positions=pos, context=context)
        logits = self._logits(params, x)
        # fused CE: no (B,S,V) log-softmax materialisation; the one-hot dot
        # reduces over the vocab-sharded axis in place.
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
        ll = jnp.einsum("...v,...v->...", logits, onehot)
        return (lse - ll).mean()

    # ---- serve -------------------------------------------------------------
    @staticmethod
    def _shard_cache_batch(tree, axis: int):
        """Batch-dim sharding constraint on every cache leaf (no-op without
        an ambient mesh). Caches are created inside the prefill jit; the
        constraint keeps them data-sharded from the first write, so the
        mesh serve cell never materialises a replicated KV cache and the
        donated decode buffers keep a stable sharding across steps."""
        def one(a):
            axes: list[str | None] = [None] * a.ndim
            axes[axis] = "batch"
            return shard(a, *axes)
        return jax.tree.map(one, tree)

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.max_target_positions:
            max_len = min(max_len, cfg.max_target_positions)

        def one(kind):
            if kind == "attn":
                return A.init_attn_cache(cfg, batch, max_len,
                                         cfg.local_window)
            if kind == "cross":
                return A.init_attn_cache(cfg, batch,
                                         cfg.n_context_tokens or 1,
                                         cross=True)
            if kind == "rglru":
                return B.cache_rglru(cfg, batch)
            if kind == "mlstm":
                return B.cache_mlstm(cfg, batch)
            if kind == "slstm":
                return B.cache_slstm(cfg, batch)
            raise ValueError(kind)

        def stack(tree):
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.n_repeats,) + a.shape, a.dtype),
                tree)
        caches = {"body": self._shard_cache_batch(
            {f"c{i}": stack(one(kind))
             for i, kind in enumerate(self.pattern)}, axis=1)}
        if cfg.block_tail:
            caches["tail"] = self._shard_cache_batch(
                {f"c{i}": one(kind)
                 for i, kind in enumerate(cfg.block_tail)}, axis=0)
        return caches

    # ---- paged serve (continuous batching, repro.serve) --------------------
    def supports_paged(self) -> str | None:
        """None when the paged serve path covers this config, else why not."""
        cfg = self.cfg
        if any(k != "attn" for k in self.pattern):
            return f"block pattern {self.pattern} has non-attn blocks"
        if cfg.block_tail:
            return f"block_tail {cfg.block_tail} is not paged"
        if cfg.local_window:
            return "local-window (rolling) caches are not paged"
        if cfg.n_context_tokens or cfg.is_encdec:
            return "cross-attention context caches are not paged"
        return None

    def init_page_pool(self, n_pages: int, page_size: int):
        """Layer-stacked paged KV pool: one leaf (n_repeats, n_pages
        rounded up to ``attention.PAGE_TILE``, page_elems) per pattern
        position, a page's K, V (and KV8 scale) rows side by side in one
        row (``attention.init_attn_page_pool``). No batch axis — slots
        exist only in the page table the serve engine packs per step."""
        reason = self.supports_paged()
        if reason is not None:
            raise NotImplementedError(f"paged KV pool: {reason}")
        cfg = self.cfg
        one = A.init_attn_page_pool(cfg, n_pages, page_size)
        stacked = jax.tree.map(
            lambda a: jnp.zeros((cfg.n_repeats,) + a.shape, a.dtype), one)
        return {"body": {f"c{i}": stacked
                         for i in range(len(self.pattern))}}

    def _scan_paged(self, params: Params, x, pool, mode: str, **attn_kw):
        """The layer scan of the paged entry points. The stacked pool
        rides in the carry, not in ``xs`` -> ``ys``: each layer updates
        its rows in place at ``[layer, ...]`` and gathers straight from
        the stack, so no layer slice is cut out, relaid out or written
        back whole. Returns (x, new pool)."""
        cfg = self.cfg

        def body(carry, xs):
            h, pl = carry
            bp, layer = xs
            h, pl = _apply_superblock_paged(
                bp, h, cfg, self.pattern, pool=pl, layer=layer, mode=mode,
                **attn_kw)
            return (h, pl), None
        layers = jnp.arange(cfg.n_repeats, dtype=jnp.int32)
        (x, new_body), _ = _scan(body, (x, pool["body"]),
                                 (params["blocks"], layers))
        return x, {"body": new_body}

    def prefill_paged(self, params: Params, tokens, pool, *,
                      prefix_page_ids, write_page_ids, write_offs,
                      write_from: int = 0):
        """Suffix prefill for one request through the page pool.

        ``tokens`` (1, Ls) is the prompt suffix after the shared range
        (``len(prefix_page_ids) * page_size`` positions, gathered from the
        pool). Returns (last-position logits, new pool). Static shapes:
        retraces per (Ls, n_prefix_pages, write_from) combination."""
        x = self._embed_tokens(params, tokens)
        x, pool = self._scan_paged(
            params, x, pool, "prefill", prefix_page_ids=prefix_page_ids,
            write_page_ids=write_page_ids, write_offs=write_offs,
            write_from=write_from)
        return self._logits(params, x[:, -1:]), pool

    def prefill_paged_batched(self, params: Params, tokens, pool, *,
                              prefix_page_ids, prefix_lens, suffix_lens,
                              write_page_ids, write_offs, write_pos):
        """Bucket-padded batched prefill: N requests' suffixes in one call.

        ``tokens`` (B, Lb) holds each row's prompt suffix left-aligned and
        zero-padded to the bucket length; see
        :func:`repro.models.attention.apply_attn_paged_prefill_batched`
        for the index-array contract. Returns (per-row last-real-position
        logits (B, 1, V), new pool). Static per (B, Lb, PPb) bucket."""
        x = self._embed_tokens(params, tokens)
        x, pool = self._scan_paged(
            params, x, pool, "prefill_batched",
            prefix_page_ids=prefix_page_ids, prefix_lens=prefix_lens,
            suffix_lens=suffix_lens, write_page_ids=write_page_ids,
            write_offs=write_offs, write_pos=write_pos)
        last = jnp.take_along_axis(
            x, (suffix_lens - 1)[:, None, None].astype(jnp.int32), axis=1)
        return self._logits(params, last), pool

    def decode_step_paged(self, params: Params, pool, tokens, page_indices,
                          steps, kernel: bool | None = None):
        """One packed decode step over every slot. tokens (B, 1) int32;
        page_indices (B, P) int32; steps (B,) int32 per-slot positions.
        Returns (logits (B, 1, V), new pool). One fixed shape — zero
        retraces as requests come and go. ``kernel`` (static under jit)
        selects the Pallas live-page attention path; None defers to
        ``cfg.paged_kernel``."""
        x = self._embed_tokens(params, tokens)
        x, pool = self._scan_paged(
            params, x, pool, "decode", page_indices=page_indices,
            steps=steps, kernel=kernel)
        return self._logits(params, x), pool

    def prefill(self, params: Params, batch: dict, max_len: int):
        """Process the prompt, fill caches; returns (last-pos logits, caches)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        context = self._context(params, batch)
        caches = self.init_cache(b, max_len)
        x = self._embed_tokens(params, tokens)
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))

        def body(carry, xs):
            bp, cache_slice = xs
            y, nc = _apply_superblock(
                bp, carry, cfg, self.pattern, positions=pos,
                caches=cache_slice, context=context, prefill=True)
            return y, nc
        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        x, new_body = _scan(body, x, (params["blocks"], caches["body"]))
        out = {"body": new_body}
        if cfg.block_tail:
            x, out["tail"] = _apply_superblock(
                params["tail"], x, cfg, cfg.block_tail, positions=pos,
                caches=caches["tail"], context=context, prefill=True)
        logits = self._logits(params, x[:, -1:])
        return logits, out

    def decode_step(self, params: Params, caches, token, step):
        """One decode step. token (B, 1) int32; step scalar int32 position."""
        cfg = self.cfg
        b = token.shape[0]
        # context K/V live in the cross caches after prefill; only the
        # stub-embedding shape is needed to signal cross blocks.
        context = (jnp.zeros((b, cfg.n_context_tokens, cfg.d_model),
                             cfg.dtype)
                   if (cfg.n_context_tokens or cfg.is_encdec) else None)
        if cfg.is_encdec and context is None:
            context = jnp.zeros((b, 1, cfg.d_model), cfg.dtype)
        x = self._embed_tokens(params, token)
        pos = jnp.broadcast_to(step, (b, 1)).astype(jnp.int32)

        def body(carry, xs):
            bp, cache_slice = xs
            y, nc = _apply_superblock(bp, carry, cfg, self.pattern,
                                      positions=pos, caches=cache_slice,
                                      step=step, context=context)
            return y, nc
        x, new_body = _scan(body, x, (params["blocks"], caches["body"]))
        out = {"body": new_body}
        if cfg.block_tail:
            x, out["tail"] = _apply_superblock(
                params["tail"], x, cfg, cfg.block_tail, positions=pos,
                caches=caches["tail"], step=step, context=context)
        logits = self._logits(params, x)
        return logits, out
