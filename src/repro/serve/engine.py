"""Continuous-batching serve engine over the paged KV pool.

:class:`ServeEngine` is the host scheduler the ROADMAP's serving story
needs around the quantized GEMM core: requests ``submit()`` at any time,
``step()`` admits arrivals into free batch slots, runs **one packed decode
step** over every active slot, and retires finished requests — freeing
their pages and re-opening their slots — without ever retracing. The
device only ever sees three programs:

  * a **bucketed batched prefill** (``Model.prefill_paged_batched``):
    pending same-wave prefills whose suffixes round up to the same
    power-of-two bucket run as ONE padded call, jit-keyed on
    ``(batch_bucket, suffix_bucket, n_prefix_pages)`` — the bucket set
    bounds prefill retraces regardless of prompt-length diversity
    (``bucket_prefill=False`` or an over-``CHUNK_THRESHOLD`` extent
    falls back to the per-request path below);
  * a per-request **suffix prefill** (``Model.prefill_paged``, batch 1),
    jit-keyed on ``(suffix_len, n_prefix_pages, write_from)``;
  * one fixed-shape **packed decode** (``Model.decode_step_paged``) over
    ``(n_slots, 1)`` tokens + the ``(n_slots, pages_per_slot)`` int32
    page table + per-slot ``steps`` — the same static-gather trick
    ``DevicePlan`` uses for forest schedules. Inactive slots point every
    table entry at the null page and carry step 0; their lanes compute
    garbage that is never read. ``paged_kernel=True`` routes its
    attention through the Pallas live-page kernel
    (:mod:`repro.kernels.paged_attention`), which walks only each
    slot's live pages instead of gathering the full ``pages_per_slot``
    extent.

Prompt prefixes are shared through the :class:`~repro.serve.paging.
PrefixTrie` at full-page granularity: a request whose prompt extends an
indexed prefix takes refcounts on those pages instead of re-prefilling
them. With an exact (fp/bf16) pool the shared range is *skipped at
compute time* (prefill sees only the suffix and gathers the shared K/V);
with an int8 pool (``kv_cache_bits=8``) the shared range is recomputed —
the dense reference attends over full-precision K/V during prefill, so
skipping compute would break bit-identity — but the shared pages are
still shared (per-token quantization is deterministic, the bytes match)
and only the non-shared tail is written.

Correctness bar, and the invariant the tests pin: every request's token
stream is **bit-identical** to running it alone through
``greedy_generate`` with the same ``max_len`` — the gathered cache view
has the same sequence extent, masked lanes contribute exact zeros, and
per-row math is batch-independent.

Weight updates hot-swap without draining: the engine's per-weight state
(params, page pool, allocator, prefix trie, slot arrays) lives in a
**generation cell**, and :meth:`ServeEngine.swap_params` stages a new
cell that is attached atomically at the next ``step()`` boundary — never
mid-step. In-flight requests finish on the generation that admitted them
(K/V bytes are a function of tokens *and* weights, so a request's cell —
pool, trie and all — stays alive until its last token); requests
admitted after the swap run on the new generation. The jitted device
programs are created once per engine and shared across generations, so a
swap whose params keep the same leaf avals (weight *values* changed, not
shapes — see ``repro.fleet.replan.align_device_plans`` for keeping
``DevicePlan`` pads stable) re-uses every existing trace:
``stats()["decode_jit_traces"]`` stays at 1 through the swap. See
docs/FLEET.md for the full protocol (staging, rollback, accounting).
All scheduling state is host-side; ``swap_params`` may be called from a
background replan thread (it only stages, under a lock).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.attention import CHUNK_THRESHOLD
from repro.models.model import Model
from repro.serve.paging import PageAllocator, PrefixTrie
from repro.train.serve_step import _place_batch

__all__ = ["Request", "ServeEngine", "SwapMismatchError", "bucket"]


def bucket(n: int, cap: int) -> int:
    """Smallest power of two >= ``n``, clamped to ``cap``.

    The bucket set {1, 2, 4, ..., cap} is what bounds the engine's
    prefill jit specializations: suffix lengths, write widths and batch
    widths are all padded up to a bucket before reaching the device.
    """
    if n < 1:
        raise ValueError(f"bucket of non-positive {n}")
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class SwapMismatchError(ValueError):
    """``swap_params`` was handed params the engine cannot serve: the
    pytree structure differs from the serving generation's. A hot swap
    replaces weight *values* (and, for planned backends, the DevicePlans
    riding inside the params); it never changes model architecture —
    that needs a new engine."""


@dataclasses.dataclass
class Request:
    """One generation request plus the engine's bookkeeping for it."""
    rid: int
    prompt: tuple
    max_new_tokens: int
    eos_id: int | None = None
    # -- engine state ------------------------------------------------------
    out: list = dataclasses.field(default_factory=list)
    page_ids: list = dataclasses.field(default_factory=list)
    slot: int | None = None
    gen: int = 0               # weight generation that admitted (and owns) it
    length: int = 0            # K/V rows written: prompt, then +1 per step
    shared_pages: int = 0      # prompt pages taken from the prefix trie
    prefill_computed: int = 0  # prompt positions the prefill forward ran
    # -- timeline (perf_counter seconds / engine decode-step counts) ------
    # t_first / t_done: the end of the step() that produced the first /
    # last token, when the caller sees it
    t_submit: float = 0.0
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None
    submit_step: int = 0
    admit_step: int | None = None
    done_step: int | None = None

    @property
    def done(self) -> bool:
        return self.t_done is not None

    @property
    def tokens(self) -> list:
        """Generated token ids (token 0 is the prefill argmax)."""
        return list(self.out)


@dataclasses.dataclass
class _Cell:
    """One weight generation's serving state.

    Everything whose bytes are a function of the weights lives here —
    params, page pool, allocator, prefix trie (it indexes K/V *bytes*),
    the packed slot arrays — so a hot swap is "append a new cell" and a
    request's generation is pinned by which cell admitted it. The jitted
    device programs stay on the engine: cells share them, which is what
    makes an aval-stable swap retrace-free.
    """
    gen: int
    params: Any
    pool: Any
    alloc: PageAllocator
    trie: PrefixTrie
    slots: list
    tokens: np.ndarray
    steps: np.ndarray
    table: np.ndarray
    tag: Any = None            # caller's label (checkpoint step, ...)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)


class ServeEngine:
    """Paged-KV continuous-batching scheduler around one model.

    ``n_slots`` fixes the packed decode batch; ``max_len`` bounds any
    request's total (prompt + generated - 1) positions and must be a
    multiple of ``page_size``. ``n_pages`` defaults to
    ``n_slots * max_len / page_size + 1`` (page 0 is the null page), which
    guarantees admission and decode never run out of pages — trie-held
    pages beyond that working set are evicted LRU on demand. ``mesh=``
    runs both device programs under an ambient mesh with the packed slot
    arrays placed under the ``batch`` sharding rule (the same serve-cell
    topology as ``greedy_generate(mesh=)``). ``donate=False`` keeps the
    pool un-donated for callers that hold references across steps.

    ``paged_kernel=True`` decodes through the Pallas live-page attention
    kernel (cost grows with live pages, not ``max_len``);
    ``bucket_prefill=False`` reverts admission to per-request batch-1
    prefills. Both default to the pure-jnp oracle paths.

    Weights are swappable at runtime via :meth:`swap_params` — see the
    module docstring and docs/FLEET.md. ``params``/``pool``/``alloc``/
    ``trie``/``slots`` read through to the *current* generation's cell.
    """

    def __init__(self, model: Model, params, *, n_slots: int = 4,
                 max_len: int = 256, page_size: int = 16,
                 n_pages: int | None = None, mesh=None,
                 donate: bool = True, paged_kernel: bool = False,
                 bucket_prefill: bool = True):
        reason = model.supports_paged()
        if reason is not None:
            raise NotImplementedError(f"paged serving: {reason}")
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_len % page_size:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size}) so a slot's page table covers it exactly")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        self.n_pages = (n_slots * self.pages_per_slot + 1
                        if n_pages is None else n_pages)
        self.mesh = mesh
        self.paged_kernel = bool(paged_kernel)
        self.bucket_prefill = bool(bucket_prefill)
        # int8 pools share pages but must not skip prefill compute: the
        # dense reference attends over full-precision K/V while prefilling,
        # and a dequantized prefix would break bit-identity
        self.exact_pool = model.cfg.kv_cache_bits != 8
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}
        self.finished: list[Request] = []
        self._seated: list[Request] = []     # first token made this step
        self.step_count = 0
        self._next_rid = 0
        # generation cells: [-1] is current (admission target), earlier
        # entries are draining their in-flight requests on old weights
        self._cells: list[_Cell] = [self._new_cell(0, params)]
        self._staged: tuple | None = None
        self._swap_lock = threading.Lock()
        self.swap_steps: list[int] = []
        # true trace counts: the wrapped bodies below run exactly once per
        # jit trace, so these count actual (re)traces — the observable the
        # hot-swap no-retrace guarantee is asserted on (trace *keys* in
        # _trace_keys count requested specializations, not compilations)
        self.jit_traces = {"prefill": 0, "prefill_batched": 0, "decode": 0}
        traces = self.jit_traces

        def _prefill_fn(params, tokens, pool, *, prefix_page_ids,
                        write_page_ids, write_offs, write_from=0):
            traces["prefill"] += 1
            return model.prefill_paged(
                params, tokens, pool, prefix_page_ids=prefix_page_ids,
                write_page_ids=write_page_ids, write_offs=write_offs,
                write_from=write_from)

        def _prefill_batched_fn(params, tokens, pool, *, prefix_page_ids,
                                prefix_lens, suffix_lens, write_page_ids,
                                write_offs, write_pos):
            traces["prefill_batched"] += 1
            return model.prefill_paged_batched(
                params, tokens, pool, prefix_page_ids=prefix_page_ids,
                prefix_lens=prefix_lens, suffix_lens=suffix_lens,
                write_page_ids=write_page_ids, write_offs=write_offs,
                write_pos=write_pos)

        def _decode_fn(params, pool, tokens, page_indices, steps,
                       kernel=None):
            traces["decode"] += 1
            return model.decode_step_paged(params, pool, tokens,
                                           page_indices, steps,
                                           kernel=kernel)

        self._prefill = jax.jit(_prefill_fn,
                                static_argnames=("write_from",),
                                donate_argnums=(2,) if donate else ())
        self._prefill_batched = jax.jit(_prefill_batched_fn,
                                        donate_argnums=(2,) if donate
                                        else ())
        self._decode = jax.jit(_decode_fn,
                               static_argnames=("kernel",),
                               donate_argnums=(1,) if donate else ())
        # distinct jit specializations actually requested, per program —
        # the observable the bucketing win is measured by
        self._trace_keys: dict[str, set] = {"prefill": set(),
                                            "decode": set()}
        self.counters = {"admitted": 0, "completed": 0, "decode_steps": 0,
                         "decode_tokens": 0, "prefix_hits": 0,
                         "pages_shared": 0, "prefill_computed": 0,
                         "prefill_skipped": 0, "prefill_written": 0,
                         "prefill_calls": 0, "prefill_batched_calls": 0,
                         "prefill_batched_rows": 0, "prefill_pad_rows": 0,
                         "bucket_hits": 0, "swaps": 0, "swaps_staged": 0,
                         "swaps_superseded": 0, "swap_shape_drift": 0,
                         "generations_retired": 0}

    def _new_cell(self, gen: int, params, tag=None) -> _Cell:
        return _Cell(
            gen=gen, params=params,
            pool=self.model.init_page_pool(self.n_pages, self.page_size),
            alloc=PageAllocator(self.n_pages),
            trie=PrefixTrie(self.page_size),
            slots=[None] * self.n_slots,
            tokens=np.zeros((self.n_slots, 1), np.int32),
            steps=np.zeros((self.n_slots,), np.int32),
            table=np.zeros((self.n_slots, self.pages_per_slot), np.int32),
            tag=tag)

    # -- current-generation views (admission target; old cells drain) -----
    @property
    def cell(self) -> _Cell:
        return self._cells[-1]

    @property
    def generation(self) -> int:
        return self.cell.gen

    @property
    def params(self):
        return self.cell.params

    @property
    def pool(self):
        return self.cell.pool

    @property
    def alloc(self) -> PageAllocator:
        return self.cell.alloc

    @property
    def trie(self) -> PrefixTrie:
        return self.cell.trie

    @property
    def slots(self) -> list:
        return self.cell.slots

    # -- hot swap ----------------------------------------------------------
    def swap_params(self, params, *, tag=None) -> int:
        """Stage a weight-generation swap; returns the new generation id.

        Applied atomically at the start of the next :meth:`step` — never
        mid-step. Non-draining: requests already in flight keep decoding
        on the generation that admitted them (its cell — params, pool,
        trie — stays alive until they finish); requests admitted after
        the swap run on the new weights. Thread-safe: this only *stages*
        (a background replan worker may call it); the scheduling thread
        applies. Staging again before the next step supersedes the
        earlier staged params (newest weights win — counted in
        ``swaps_superseded``).

        ``params`` must have the serving generation's pytree structure
        (else :class:`SwapMismatchError`; the caller's rollback is to
        simply not swap). Leaf-shape drift is allowed — it happens when a
        planned backend's ``DevicePlan`` direct width grows past the pad
        (see ``repro.fleet.replan.align_device_plans``) — but costs one
        retrace and is surfaced in ``swap_shape_drift``.
        """
        cur = self.cell.params
        if (jax.tree_util.tree_structure(params)
                != jax.tree_util.tree_structure(cur)):
            raise SwapMismatchError(
                "swap_params: new params pytree structure differs from "
                "the serving generation's — a hot swap replaces weight "
                "values, not model architecture (build a new engine for "
                "that)")
        # trust boundary: a replan worker's DevicePlans are verified at
        # staging time — a malformed plan never waits in _staged where
        # the scheduling thread would attach it mid-serve
        from repro.analysis.planlint import gate_params
        gate_params(params, where="swap-staging")
        drift = sum(
            getattr(a, "shape", None) != getattr(b, "shape", None)
            or getattr(a, "dtype", None) != getattr(b, "dtype", None)
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(cur)))
        with self._swap_lock:
            superseded = self._staged is not None
            self._staged = (params, tag, drift)
        self.counters["swaps_staged"] += 1
        if superseded:
            self.counters["swaps_superseded"] += 1
        return self.cell.gen + 1

    def _apply_staged(self) -> None:
        """Attach a staged generation (scheduling thread, step boundary)."""
        with self._swap_lock:
            staged, self._staged = self._staged, None
        if staged is None:
            return
        params, tag, drift = staged
        self._cells.append(self._new_cell(self.cell.gen + 1, params,
                                          tag=tag))
        self.counters["swaps"] += 1
        self.counters["swap_shape_drift"] += drift
        self.swap_steps.append(self.step_count)

    def _retire_cells(self) -> None:
        """Drop old generations whose last in-flight request finished
        (frees their pool/trie); the current cell always stays."""
        for cell in [c for c in self._cells[:-1] if c.n_active == 0]:
            self._cells.remove(cell)
            self.counters["generations_retired"] += 1

    def _cell_of(self, gen: int) -> _Cell:
        for cell in self._cells:
            if cell.gen == gen:
                return cell
        raise KeyError(f"generation {gen} already retired")

    # -- submission --------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: int | None = None) -> int:
        """Queue a request; returns its id. Admission happens in step()."""
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        # token 0 comes from prefill; decode i writes K/V position
        # len(prompt) + i - 1, so the last write lands at
        # L + max_new_tokens - 2 and must stay under max_len
        if len(prompt) + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) - 1 exceeds max_len ({self.max_len})")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens), eos_id=eos_id,
                      t_submit=time.perf_counter(),
                      submit_step=self.step_count)
        self.queue.append(req)
        return rid

    # -- scheduling --------------------------------------------------------
    def _mesh_ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _alloc_page(self, cell: _Cell) -> int | None:
        """One page, evicting trie-only pages (LRU) under pressure."""
        pid = cell.alloc.alloc()
        if pid is None and cell.trie.evict(cell.alloc, 1):
            pid = cell.alloc.alloc()
        return pid

    def _note_trace(self, kind: str, key: tuple) -> bool:
        """Record a jit-specialization key; True when already traced."""
        keys = self._trace_keys[kind]
        if key in keys:
            return True
        keys.add(key)
        return False

    def _reserve(self, req: Request) -> dict | None:
        """Match/pin/allocate ``req``'s prompt pages; None = no pages yet.

        Reserved pages carry the request's refcount, so later same-wave
        reservations can evict around them but never reclaim them. The
        prompt is indexed into the trie immediately — a request arriving
        later in the same wave already shares these pages (the run
        partitioning in :meth:`_admit` keeps its prefill *after* the
        batch that writes them). Always against the current cell: only
        the current generation admits.
        """
        cell = self.cell
        L, ps = len(req.prompt), self.page_size
        n_prompt_pages = -(-L // ps)
        # cap the match so the suffix keeps >= 1 token: the last prompt
        # position must run through prefill to produce the step-0 logits,
        # and decode must never append to a page another request holds
        shared = cell.trie.match(req.prompt, max_pages=(L - 1) // ps)
        for pid in shared:            # pin before eviction can see them
            cell.alloc.incref(pid)
        need = n_prompt_pages - len(shared)
        if cell.alloc.free_count < need:
            cell.trie.evict(cell.alloc, need - cell.alloc.free_count)
        if cell.alloc.free_count < need:
            for pid in shared:
                cell.alloc.decref(pid)
            return None
        page_ids = list(shared) + [cell.alloc.alloc() for _ in range(need)]
        cell.trie.insert(req.prompt, page_ids, cell.alloc)
        return {"req": req, "page_ids": page_ids, "shared": len(shared)}

    def _seat(self, res: dict, tok: int) -> None:
        """Post-prefill bookkeeping: record token, counters, slot/table."""
        cell = self.cell
        req = res["req"]
        L, ps = len(req.prompt), self.page_size
        shared = res["shared"]
        shared_len = shared * ps
        start = shared_len if self.exact_pool else 0
        req.gen = cell.gen
        req.out.append(tok)
        req.length = L
        req.page_ids = res["page_ids"]
        req.shared_pages = shared
        req.prefill_computed = L - start
        req.t_admit = time.perf_counter()
        req.admit_step = self.step_count
        self._seated.append(req)
        self.counters["admitted"] += 1
        self.counters["prefix_hits"] += bool(shared)
        self.counters["pages_shared"] += shared
        self.counters["prefill_computed"] += L - start
        self.counters["prefill_skipped"] += shared_len
        self.counters["prefill_written"] += L - shared_len
        if len(req.out) >= req.max_new_tokens or tok == req.eos_id:
            self._finish(req)
        else:
            slot = cell.slots.index(None)
            req.slot = slot
            cell.slots[slot] = req.rid
            self.active[req.rid] = req
            cell.tokens[slot, 0] = tok
            cell.steps[slot] = req.length
            cell.table[slot, :len(req.page_ids)] = req.page_ids

    def _prefill_one(self, res: dict) -> None:
        """Per-request batch-1 prefill (the original, always-exact path)."""
        cell = self.cell
        req, page_ids = res["req"], res["page_ids"]
        L, ps = len(req.prompt), self.page_size
        shared_len = res["shared"] * ps
        if self.exact_pool:
            start, write_from = shared_len, 0   # skip shared compute
        else:
            start, write_from = 0, shared_len   # recompute, share bytes
        suffix = np.asarray([req.prompt[start:]], np.int32)
        prefix = np.asarray(page_ids[:start // ps], np.int32)
        wp = np.asarray([page_ids[p // ps] for p in range(shared_len, L)],
                        np.int32)
        wo = np.asarray([p % ps for p in range(shared_len, L)], np.int32)
        self.counters["prefill_calls"] += 1
        self._note_trace("prefill", ("one", L - start, start // ps,
                                     write_from))
        with self._mesh_ctx():
            logits, cell.pool = self._prefill(
                cell.params, jnp.asarray(suffix), cell.pool,
                prefix_page_ids=jnp.asarray(prefix),
                write_page_ids=jnp.asarray(wp), write_offs=jnp.asarray(wo),
                write_from=write_from)
            with TraceAnnotation("engine.sync"):
                tok = int(np.asarray(
                    jnp.argmax(logits[:, -1], -1).astype(jnp.int32))[0])
        self._seat(res, tok)

    def _bucket_key(self, res: dict) -> tuple:
        """(suffix_bucket, n_prefix_pages) jit grouping key for a
        reservation. The prefix page count stays EXACT (not bucketed):
        padding it would interleave zero lanes mid-extent and shift the
        suffix lanes' reduction association — trailing suffix/batch
        padding is the bit-exact kind (see attention.py)."""
        L, ps = len(res["req"].prompt), self.page_size
        start = res["shared"] * ps if self.exact_pool else 0
        return bucket(L - start, self.max_len), start // ps

    def _prefill_group(self, group: list[dict]) -> None:
        """One padded batched prefill over same-bucket reservations."""
        cell = self.cell
        ps = self.page_size
        lb, n_pre = self._bucket_key(group[0])
        if not self.bucket_prefill or n_pre * ps + lb > CHUNK_THRESHOLD:
            for res in group:
                self._prefill_one(res)
            return
        nb = bucket(len(group), self.n_slots)
        tokens = np.zeros((nb, lb), np.int32)
        prefix = np.zeros((nb, n_pre), np.int32)
        plens = np.zeros((nb,), np.int32)
        slens = np.ones((nb,), np.int32)    # dead rows read garbage row 0
        wp = np.zeros((nb, lb), np.int32)   # dead lanes hit the null page
        wo = np.zeros((nb, lb), np.int32)
        wpos = np.zeros((nb, lb), np.int32)
        for r, res in enumerate(group):
            req, page_ids = res["req"], res["page_ids"]
            L = len(req.prompt)
            shared_len = res["shared"] * ps
            start = shared_len if self.exact_pool else 0
            ls = L - start
            tokens[r, :ls] = req.prompt[start:]
            plens[r] = start
            prefix[r, :start // ps] = page_ids[:start // ps]
            slens[r] = ls
            for i, p in enumerate(range(shared_len, L)):
                wp[r, i] = page_ids[p // ps]
                wo[r, i] = p % ps
                wpos[r, i] = p - start
        self.counters["prefill_batched_calls"] += 1
        self.counters["prefill_batched_rows"] += len(group)
        self.counters["prefill_pad_rows"] += nb - len(group)
        if self._note_trace("prefill", ("batched", nb, lb, n_pre)):
            self.counters["bucket_hits"] += 1
        with self._mesh_ctx():
            logits, cell.pool = self._prefill_batched(
                cell.params, jnp.asarray(tokens), cell.pool,
                prefix_page_ids=jnp.asarray(prefix),
                prefix_lens=jnp.asarray(plens),
                suffix_lens=jnp.asarray(slens),
                write_page_ids=jnp.asarray(wp), write_offs=jnp.asarray(wo),
                write_pos=jnp.asarray(wpos))
            with TraceAnnotation("engine.sync"):
                toks = np.asarray(jnp.argmax(logits[:, -1], -1)
                                  .astype(jnp.int32))
        for r, res in enumerate(group):
            self._seat(res, int(toks[r]))

    def _admit(self) -> None:
        while self.queue and None in self.slots:
            free = self.slots.count(None)
            wave: list[dict] = []
            while self.queue and len(wave) < free:
                res = self._reserve(self.queue[0])
                if res is None:
                    break             # page pressure: retry next step
                self.queue.popleft()
                wave.append(res)
            if not wave:
                break
            # partition into runs: a reservation whose trie-shared pages
            # are WRITTEN by an earlier same-wave reservation must prefill
            # after the batch that fills them — runs flush in order, and
            # within a run no request reads another's pending writes
            runs: list[list[dict]] = []
            cur: list[dict] = []
            pending_writes: set[int] = set()
            for res in wave:
                shared_ids = set(res["page_ids"][:res["shared"]])
                if cur and (shared_ids & pending_writes):
                    runs.append(cur)
                    cur, pending_writes = [], set()
                cur.append(res)
                pending_writes |= set(res["page_ids"][res["shared"]:])
            if cur:
                runs.append(cur)
            for run in runs:
                groups: dict[tuple, list[dict]] = {}
                for res in run:
                    groups.setdefault(self._bucket_key(res),
                                      []).append(res)
                for group in groups.values():
                    self._prefill_group(group)

    def _finish(self, req: Request) -> None:
        cell = self._cell_of(req.gen)
        if req.slot is not None:
            cell.slots[req.slot] = None
            del self.active[req.rid]
            cell.tokens[req.slot, 0] = 0
            cell.steps[req.slot] = 0
            cell.table[req.slot, :] = 0
            req.slot = None
        for pid in req.page_ids:
            cell.alloc.decref(pid)    # trie-held pages survive (refcount)
        req.done_step = self.step_count
        self.counters["completed"] += 1
        self.finished.append(req)

    def _decode_cell(self, cell: _Cell,
                     packed: list[tuple[int, Request]]) -> np.ndarray:
        """One packed decode over ``cell``'s active slots; returns the
        (n_slots,) tokens it produced, on the host."""
        with TraceAnnotation("engine.launch"):
            self.counters["decode_steps"] += 1
            for s, req in packed:
                # this step writes K/V position req.length — grow the
                # request's table when it crosses a page boundary; the
                # persistent host arrays only take the per-slot deltas
                # (_seat/_finish maintain the rest)
                if req.length // self.page_size >= len(req.page_ids):
                    pid = self._alloc_page(cell)
                    if pid is None:
                        raise RuntimeError(
                            f"page pool exhausted ({cell.alloc!r}) — "
                            f"size n_pages for the slot working set")
                    req.page_ids.append(pid)
                    cell.table[s, len(req.page_ids) - 1] = pid
                cell.tokens[s, 0] = req.out[-1]
                cell.steps[s] = req.length
            batch = {"tokens": cell.tokens, "table": cell.table,
                     "steps": cell.steps}
            self._note_trace("decode", ("decode", self.paged_kernel))
            with self._mesh_ctx():
                if self.mesh is not None:
                    batch = _place_batch(batch, self.mesh)
                tokens = jnp.asarray(batch["tokens"])
                table = jnp.asarray(batch["table"])
                steps = jnp.asarray(batch["steps"])
                logits, cell.pool = self._decode(
                    cell.params, cell.pool, tokens, table, steps,
                    kernel=self.paged_kernel)
        with TraceAnnotation("engine.sync"), self._mesh_ctx():
            return np.asarray(
                jnp.argmax(logits[:, -1], -1).astype(jnp.int32))

    def _take_tokens(self, packed: list[tuple[int, Request]],
                     toks: np.ndarray) -> None:
        """Append one decode's tokens; finish requests that are done."""
        done = []
        for s, req in packed:
            tok = int(toks[s])
            req.out.append(tok)
            req.length += 1
            self.counters["decode_tokens"] += 1
            if (len(req.out) >= req.max_new_tokens
                    or tok == req.eos_id):
                done.append(req)
        for req in done:
            self._finish(req)

    def step(self) -> list[Request]:
        """Attach a staged swap, admit arrivals, run one packed decode
        step per live generation, retire finished requests and drained
        generations.

        Returns the requests that finished during this call (their
        ``tokens`` are final). A request admitted this step decodes this
        step: its prefill token feeds the packed decode exactly like
        ``greedy_generate``'s first loop iteration. A staged swap is
        applied *before* admission, so requests taken off the queue this
        step already run on the new weights, while earlier generations
        keep decoding their in-flight requests in the same call —
        swapping never skips anyone's decode step.

        Each phase runs inside a profiler span (``TraceAnnotation``, a
        no-op unless a trace is being recorded): ``engine.admit``
        (reservations, trie, prefill arrays and dispatch),
        ``engine.launch`` (page growth, slot arrays, their upload and the
        decode dispatch), ``engine.sync`` (argmax and the host's read of
        the tokens, also nested in ``engine.admit`` for prefill) and
        ``engine.retire`` (tokens appended, requests and generations
        retired).
        """
        n_done = len(self.finished)
        self._seated = []
        self._apply_staged()
        with TraceAnnotation("engine.admit"):
            self._admit()
        packed_by_cell = [
            (cell, [(s, self.active[rid])
                    for s, rid in enumerate(cell.slots) if rid is not None])
            for cell in list(self._cells)]
        decoded = []
        if any(packed for _, packed in packed_by_cell):
            self.step_count += 1
            decoded = [(packed, self._decode_cell(cell, packed))
                       for cell, packed in packed_by_cell if packed]
        with TraceAnnotation("engine.retire"):
            for packed, toks in decoded:
                self._take_tokens(packed, toks)
            self._retire_cells()
            t = time.perf_counter()
            for req in self._seated:
                req.t_first = t
            for req in self.finished[n_done:]:
                req.t_done = t
        return self.finished[n_done:]

    def run(self, max_steps: int = 100_000) -> list[Request]:
        """Drive step() until every submitted request finished."""
        n_done = len(self.finished)
        steps = 0
        while self.queue or self.active:
            if steps >= max_steps:
                raise RuntimeError(f"run() exceeded {max_steps} steps")
            steps += 1
            before = (len(self.queue), len(self.active),
                      len(self.finished))
            self.step()
            if not self.active and before == (len(self.queue),
                                              len(self.active),
                                              len(self.finished)):
                raise RuntimeError(
                    f"scheduler stalled: {len(self.queue)} queued "
                    f"request(s) cannot be admitted "
                    f"(pages: {self.alloc!r}, trie: {self.trie!r})")
        return self.finished[n_done:]

    # -- introspection -----------------------------------------------------
    def stats(self) -> dict:
        active_by_gen: dict[int, int] = {}
        for r in self.active.values():
            active_by_gen[r.gen] = active_by_gen.get(r.gen, 0) + 1
        cur = self.cell.gen
        return {**self.counters, "queued": len(self.queue),
                "active": len(self.active),
                "finished": len(self.finished),
                "prefill_traces": len(self._trace_keys["prefill"]),
                "decode_traces": len(self._trace_keys["decode"]),
                "prefill_jit_traces": (self.jit_traces["prefill"]
                                       + self.jit_traces["prefill_batched"]),
                "decode_jit_traces": self.jit_traces["decode"],
                "generation": cur,
                "draining_generations": len(self._cells) - 1,
                "active_by_gen": active_by_gen,
                "in_flight_prev_gen": sum(n for g, n in active_by_gen.items()
                                          if g != cur),
                "pages": self.alloc.stats(), "trie": self.trie.stats()}

    def report(self) -> dict:
        """Latency/throughput summary over the finished requests."""
        reqs = self.finished
        per = [{"rid": r.rid, "prompt_len": len(r.prompt),
                "n_tokens": len(r.out),
                "gen": r.gen,
                "shared_pages": r.shared_pages,
                "prefill_computed": r.prefill_computed,
                "ttft_s": r.t_first - r.t_submit,
                "latency_s": (r.t_done - r.t_submit) if r.done else None}
               for r in reqs]
        total_tokens = sum(len(r.out) for r in reqs)
        t0 = min((r.t_submit for r in reqs), default=0.0)
        t1 = max((r.t_done for r in reqs if r.done), default=t0)
        wall = max(t1 - t0, 1e-9)
        return {"requests": per, "n_requests": len(reqs),
                "total_tokens": total_tokens, "wall_s": wall,
                "tokens_per_s": total_tokens / wall,
                "counters": self.stats()}

    def __repr__(self) -> str:
        return (f"ServeEngine(gen={self.cell.gen} "
                f"slots={self.cell.n_active}/{self.n_slots} "
                f"queued={len(self.queue)} "
                f"finished={len(self.finished)} steps={self.step_count})")
