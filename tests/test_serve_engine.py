"""Continuous-batching serve engine (ISSUE 6): paged KV pool, prefix trie,
request scheduler.

The acceptance property pinned here: every request's token stream out of
``ServeEngine`` — packed decode slots, staggered arrivals, pages shared
through the prefix trie — is **bit-identical** to running that request
alone through ``greedy_generate`` with the same ``max_len``, for every
device-resident backend in the registry. Around it: unit tests for the
page allocator and the prefix trie (LRU leaf-only eviction, refcount
pinning), the exact-pool compute-skip counters (shared prefixes re-prefill
zero shared pages), KV8 parity (shared bytes, recomputed activations),
scheduler admission/eviction/stall behaviour, and the
``serve_engine_bench`` JSON contract (``serve_engine.tokens_per_s``).
"""
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core.backend import get_backend, list_backends
from repro.launch.specs import serve_config
from repro.models.model import Model
from repro.serve import (NULL_PAGE, PageAllocator, PrefixTrie, ServeEngine,
                         bucket)
from repro.train.serve_step import greedy_generate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEVICE_BACKENDS = [n for n in list_backends()
                   if get_backend(n).device_resident
                   and get_backend(n).cpu_ok]


@pytest.fixture
def cache():
    """Fresh process-default plan cache per test; restores the previous."""
    from repro.core.plancache import PlanCache, set_default_cache
    c = PlanCache(capacity=64)
    prev = set_default_cache(c)
    yield c
    set_default_cache(prev)


@pytest.fixture(scope="module")
def fp_cell():
    """Exact-pool (KV16) cell: the compute-skip prefix path."""
    cfg = get_reduced("smollm_135m").replace(n_layers=2)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, plen=8, n=4, seed=7):
    """n prompts; evens replay prompt 0, odds share its first half."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab, size=plen).tolist()
    return [list(base) if i % 2 == 0 else
            base[:plen // 2]
            + rng.integers(0, cfg.vocab, size=plen - plen // 2).tolist()
            for i in range(n)]


def _reference(model, params, prompt, max_len, n_new):
    """The request alone through today's one-shot path, same max_len."""
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    return np.asarray(greedy_generate(model, params, batch,
                                      max_len=max_len, n_steps=n_new))[0]


# -- page allocator ----------------------------------------------------------

def test_allocator_basics():
    a = PageAllocator(5)                  # pages 1..4; 0 is the null page
    got = [a.alloc() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4] and NULL_PAGE not in got
    assert a.alloc() is None              # exhausted, no exception
    assert a.free_count == 0 and a.used == 4
    assert a.decref(got[0]) is True       # refcount 1 -> freed
    assert a.free_count == 1
    pid = a.alloc()
    assert pid == got[0]                  # freed page comes back
    s = a.stats()
    assert s["allocated"] == 5 and s["freed"] == 1 and s["peak_used"] == 4


def test_allocator_refcounts():
    a = PageAllocator(4)
    pid = a.alloc()
    a.incref(pid)                         # a second holder (the trie, say)
    assert a.refcount(pid) == 2
    assert a.decref(pid) is False         # still held
    assert a.decref(pid) is True          # last ref -> freed
    assert a.free_count == 3
    with pytest.raises(ValueError):
        a.decref(pid)                     # double-free is loud


# -- prefix trie -------------------------------------------------------------

def test_trie_match_insert():
    a = PageAllocator(16)
    t = PrefixTrie(page_size=4)
    prompt = list(range(40, 49))          # 9 tokens: 2 full pages + 1 tail
    pages = [a.alloc() for _ in range(3)]
    added = t.insert(prompt, pages, a)
    assert added == 2 and len(t) == 2     # only fully-covered pages indexed
    assert a.refcount(pages[0]) == 2      # trie pins what it indexes
    assert a.refcount(pages[2]) == 1      # the tail page is not indexed
    # full-prefix hit, capped so the suffix keeps >= 1 token
    assert t.match(prompt, max_pages=2) == pages[:2]
    assert t.match(prompt, max_pages=1) == pages[:1]
    # divergence inside page 2: only page 1 shared
    other = prompt[:4] + [99] * 5
    assert t.match(other, max_pages=2) == pages[:1]
    assert t.match([99] * 8, max_pages=2) == []
    s = t.stats()
    assert s["pages_inserted"] == 2 and s["pages_matched"] == 4


def test_trie_evict_leaf_lru_only():
    a = PageAllocator(16)
    t = PrefixTrie(page_size=2)
    p1 = [1, 2, 3, 4]
    p2 = [1, 2, 7, 8]
    t.insert(p1, [a.alloc(), a.alloc()], a)
    t.insert(p2, [t.match(p2, max_pages=1)[0], a.alloc()], a)
    # drop the request refs: pages now live only in the trie
    for pid in range(1, 4):
        a.decref(pid)
    t.match(p1, max_pages=2)              # touch p1's leaf -> p2's is LRU
    assert t.evict(a, 1) == 1
    assert t.match(p2, max_pages=2) == [1]    # p2's leaf gone, root kept
    assert t.match(p1, max_pages=2) == [1, 2]  # p1 intact (leaf-only LRU)
    # the shared root page is only evictable once its children are gone
    assert t.evict(a, 2) == 2
    assert len(t) == 0 and a.free_count == a.n_pages - 1


def test_trie_never_evicts_held_pages():
    a = PageAllocator(8)
    t = PrefixTrie(page_size=2)
    t.insert([5, 6], [a.alloc()], a)      # refcount 2: request + trie
    assert t.evict(a, 1) == 0             # pinned -> not evictable
    a.decref(1)
    assert t.evict(a, 1) == 1


# -- engine construction / submission validation -----------------------------

def test_engine_validation(fp_cell):
    model, params = fp_cell
    with pytest.raises(ValueError, match="multiple of page_size"):
        ServeEngine(model, params, max_len=10, page_size=4)
    with pytest.raises(ValueError, match="n_slots"):
        ServeEngine(model, params, n_slots=0, max_len=8, page_size=4)
    eng = ServeEngine(model, params, n_slots=2, max_len=8, page_size=4)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], 2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], 0)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit([1, 2, 3, 4], 6)       # 4 + 6 - 1 > 8


# -- exact pool: shared prefixes skip prefill compute ------------------------

def test_prefix_reuse_skips_shared_compute(fp_cell):
    """KV16: the second request over the same prompt re-prefills ZERO
    shared pages — compute starts at the shared boundary and only the
    non-shared tail is written."""
    model, params = fp_cell
    cfg = model.cfg
    assert cfg.kv_cache_bits != 8
    eng = ServeEngine(model, params, n_slots=2, max_len=16, page_size=4)
    plen, gen = 9, 3                      # 2 full pages + 1 tail page
    prompts = _prompts(cfg, plen=plen, n=3)
    for p in prompts:
        eng.submit(p, gen)
    done = eng.run()
    assert len(done) == 3
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].shared_pages == 0
    assert by_rid[0].prefill_computed == plen
    # rid 2 replays prompt 0 entirely: both full pages shared, compute
    # covers only the tail (9 - 8 = 1 position)
    assert by_rid[2].shared_pages == 2
    assert by_rid[2].prefill_computed == plen - 8
    # rid 1 shares the first half (page 0 only)
    assert by_rid[1].shared_pages == 1
    assert by_rid[1].prefill_computed == plen - 4
    c = eng.counters
    assert c["prefix_hits"] == 2 and c["pages_shared"] == 3
    assert c["prefill_skipped"] == 12     # 2*4 + 1*4 positions never ran
    # written rows never overlap a shared page
    assert c["prefill_written"] == 3 * plen - c["prefill_skipped"]
    # identical prompts -> identical greedy continuations
    assert by_rid[0].tokens == by_rid[2].tokens
    # and the engine's tokens match the one-shot path
    for r in done:
        ref = _reference(model, params, list(r.prompt), 16, gen)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)


def test_staggered_equals_batch_submit(fp_cell):
    """Scheduling is invisible in the tokens: staggered arrivals through
    busy slots produce the same streams as submit-all-then-run."""
    model, params = fp_cell
    prompts = _prompts(model.cfg, plen=6, n=4, seed=11)
    eng_a = ServeEngine(model, params, n_slots=2, max_len=12, page_size=4)
    for p in prompts:
        eng_a.submit(p, 4)
    toks_a = {r.rid: r.tokens for r in eng_a.run()}

    eng_b = ServeEngine(model, params, n_slots=2, max_len=12, page_size=4)
    submitted = 0
    while submitted < len(prompts) or eng_b.queue or eng_b.active:
        if submitted < len(prompts):
            eng_b.submit(prompts[submitted], 4)
            submitted += 1
        eng_b.step()
    toks_b = {r.rid: r.tokens for r in eng_b.finished}
    assert toks_a == toks_b


# -- bit-identity across backends (the acceptance property) ------------------

@pytest.mark.parametrize("kernel", [False, True],
                         ids=["gather", "paged-kernel"])
@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_tokens_bit_identical_per_backend(backend, kernel, cache):
    """Every device-resident backend: ServeEngine tokens == the request
    alone through greedy_generate, under the full serving config (W4A8 +
    KV8 + quantized attention), with prefix sharing active — on both the
    gather-decode oracle and the Pallas live-page kernel path."""
    cfg = serve_config(get_reduced("smollm_135m").replace(n_layers=2),
                       backend=backend)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = get_backend(backend)
    if b.needs_plan:
        model.precompile_plans(params)
        params = model.attach_device_plans(params)
    max_len, gen = 12, 4
    prompts = _prompts(cfg, plen=6, n=3, seed=5)
    eng = ServeEngine(model, params, n_slots=2, max_len=max_len,
                      page_size=4, paged_kernel=kernel)
    for p in prompts:
        eng.submit(p, gen)
    done = eng.run()
    assert len(done) == len(prompts)
    assert eng.counters["pages_shared"] > 0    # sharing actually engaged
    for r in done:
        ref = _reference(model, params, list(r.prompt), max_len, gen)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref,
                                      err_msg=f"rid={r.rid} {backend}")


def test_kv8_shares_bytes_recomputes_activations(cache):
    """KV8 pools share pages (per-token quantization is deterministic) but
    never skip prefill compute — the counters must show both."""
    cfg = serve_config(get_reduced("smollm_135m").replace(n_layers=2),
                       backend="int_dot")
    assert cfg.kv_cache_bits == 8
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plen = 8
    prompts = _prompts(cfg, plen=plen, n=3, seed=9)
    eng = ServeEngine(model, params, n_slots=2, max_len=16, page_size=4)
    for p in prompts:
        eng.submit(p, 3)
    done = eng.run()
    c = eng.counters
    # match is capped at (8-1)//4 = 1 page, so both sharers take one
    assert c["pages_shared"] == 2
    assert c["prefill_skipped"] == 8           # bytes skipped, shared rows
    assert c["prefill_computed"] == 3 * plen   # ... but compute never is
    for r in done:
        ref = _reference(model, params, list(r.prompt), 16, 3)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)


# -- the stacked pool's bytes ------------------------------------------------

POOL_BYTES = os.path.join(ROOT, "tests", "data", "pool_bytes.npz")


@pytest.mark.parametrize("kv8", [False, True], ids=["exact", "kv8"])
def test_pool_bytes_match_unfolded_layout(kv8, cache):
    """After staggered arrivals, shared prefixes and decode across page
    boundaries, every (layer, page, offset) of the stacked pool holds the
    bytes that the unfolded (n_repeats, n_pages, page_size, KV, D) pool
    held after the same run (recorded from it in ``POOL_BYTES``), the
    null page and the rounding pages stay zero, and the tokens equal the
    per-request ``greedy_generate`` stream."""
    from repro.models import attention as A
    cfg = get_reduced("smollm_135m").replace(n_layers=2)
    if kv8:
        cfg = serve_config(cfg, backend="int_dot")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len, gen = 16, 5
    eng = ServeEngine(model, params, n_slots=2, max_len=max_len,
                      page_size=4)
    prompts = _prompts(cfg, plen=6, n=4, seed=5)
    for p in prompts[:2]:
        eng.submit(p, gen)
    for _ in range(3):
        eng.step()
    for p in prompts[2:]:
        eng.submit(p, gen)
    done = eng.run()
    assert len(done) == 4 and eng.counters["pages_shared"] > 0
    for r in done:
        ref = _reference(model, params, list(r.prompt), max_len, gen)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)
    (leaf,) = jax.tree.leaves(eng.pool)
    assert leaf.shape[1] % A.PAGE_TILE == 0
    segs = A.unpack_pages(leaf, A.pool_layout(cfg), cfg.n_kv_heads)
    recorded = np.load(POOL_BYTES)
    tag = "kv8" if kv8 else "exact"
    assert sorted(segs) == sorted(k[len(tag) + 1:] for k in recorded
                                  if k.startswith(tag + "_"))
    for name, a in segs.items():
        a = np.asarray(a)
        if a.dtype == jnp.bfloat16:
            a = a.view(np.uint16)
        want = recorded[f"{tag}_{name}"]
        np.testing.assert_array_equal(a[:, 1:eng.n_pages], want[:, 1:],
                                      err_msg=name)
        assert not a[:, 0].any() and not a[:, eng.n_pages:].any(), name


@pytest.mark.parametrize("group,page,off", [
    # decode: one lane a slot, several lanes to one page, one to the null
    # page
    (1, [3, 3, 3, 5, 0, 7], [0, 2, 3, 1, 1, 3]),
    # prefill: runs of page_size lanes through whole pages, a short last
    # run padded with null-page lanes; the lane of the last run that names
    # another page than the run's first is not written
    (4, [3, 3, 3, 3, 5, 5, 0, 0, 6, 6, 2, 6], [0, 1, 2, 3, 0, 1, 0, 0,
                                                0, 1, 2, 3]),
], ids=["decode", "prefill"])
@pytest.mark.parametrize("kv8", [False, True], ids=["exact", "kv8"])
def test_store_rows_matches_unfolded_writes(kv8, group, page, off):
    """Rows written through the stacked leaf read back as the same rows
    written into an unfolded (L, n_pages, page_size, KV, width) pool, and
    every other byte of the leaf is left as it was."""
    from repro.models import attention as A
    cfg = get_reduced("smollm_135m").replace(n_layers=2)
    if kv8:
        cfg = serve_config(cfg, backend="int_dot")
    layout = A.pool_layout(cfg)
    kvh, ps, n_pages, n_layers = cfg.n_kv_heads, 4, 9, 3
    one = A.init_attn_page_pool(cfg, n_pages, ps)["kv"]
    rng = np.random.default_rng(0)
    leaf = jnp.asarray(rng.integers(-100, 100, (n_layers,) + one.shape)
                       .astype(np.int8)) if kv8 else jnp.asarray(
        rng.standard_normal((n_layers,) + one.shape), one.dtype)
    n = len(page)
    rows = {name: jnp.asarray(rng.standard_normal((n, kvh, w)), dt)
            if jnp.issubdtype(dt, jnp.floating) else
            jnp.asarray(rng.integers(-128, 128, (n, kvh, w)), dt)
            for name, dt, w in layout}
    new = A._store_rows({"kv": leaf}, jnp.int32(1),
                        jnp.asarray(page, jnp.int32),
                        jnp.asarray(off, jnp.int32), rows, cfg,
                        group)["kv"]
    before = A.unpack_pages(leaf, layout, kvh)
    after = A.unpack_pages(new, layout, kvh)
    pg, of = np.asarray(page), np.asarray(off)
    first = np.repeat(pg[::group], group)
    live = (pg != 0) & (pg == first)
    for name, *_ in layout:
        want = np.asarray(before[name]).copy()
        want[1, pg[live], of[live]] = np.asarray(rows[name])[live]
        got = np.asarray(after[name])
        assert got.tobytes() == want.tobytes(), name
    gathered = A._gather_pages({"kv": new}, jnp.int32(1),
                               jnp.asarray([[3, 5], [7, 0]], jnp.int32),
                               cfg, ("k", "v"))
    for g, name in zip(gathered, ("k", "v")):
        full = np.asarray(after[name])[1]
        want = np.stack([np.concatenate([full[3], full[5]]),
                         np.concatenate([full[7], full[0]])])
        assert np.asarray(g).tobytes() == want.tobytes(), name


# -- scheduler ---------------------------------------------------------------

def test_more_requests_than_slots(fp_cell):
    """5 requests through 2 slots: all finish, slots turn over, the page
    pool returns to its idle level (trie-held pages only)."""
    model, params = fp_cell
    prompts = _prompts(model.cfg, plen=5, n=5, seed=3)
    eng = ServeEngine(model, params, n_slots=2, max_len=8, page_size=4)
    rids = [eng.submit(p, 4) for p in prompts]
    done = eng.run()
    assert sorted(r.rid for r in done) == rids
    assert eng.counters["completed"] == 5
    assert not eng.active and not eng.queue
    assert all(len(r.tokens) == 4 for r in done)
    # finished requests released their pages; only the trie still holds
    assert eng.alloc.used == eng.trie.stats()["pages"]
    for r in done:
        ref = _reference(model, params, list(r.prompt), 8, 4)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)


def test_lazy_page_growth_across_boundary(fp_cell):
    """Decode allocates pages lazily when a request's length crosses a
    page boundary mid-generation."""
    model, params = fp_cell
    prompt = _prompts(model.cfg, plen=5, n=1, seed=13)[0]
    eng = ServeEngine(model, params, n_slots=1, max_len=16, page_size=4)
    eng.submit(prompt, 8)                 # rows 5..11: pages 2 and 3 lazily
    (req,) = eng.run()
    assert len(req.page_ids) == 3         # ceil(12 / 4): grown from 2
    ref = _reference(model, params, prompt, 16, 8)
    np.testing.assert_array_equal(np.asarray(req.tokens), ref)


def test_eos_stops_early(fp_cell):
    model, params = fp_cell
    prompt = _prompts(model.cfg, plen=5, n=1, seed=17)[0]
    eng = ServeEngine(model, params, n_slots=1, max_len=16, page_size=4)
    ref = _reference(model, params, prompt, 16, 6).tolist()
    eos = ref[2]
    eng.submit(prompt, 6, eos_id=eos)
    (req,) = eng.run()
    # stops AT the first eos occurrence (which may be earlier than idx 2
    # if the greedy stream happens to repeat the token)
    assert req.tokens == ref[:ref.index(eos) + 1]


def test_run_stall_raises(fp_cell):
    """A request that can never be admitted (pool smaller than its prompt)
    stalls loudly instead of spinning forever."""
    model, params = fp_cell
    eng = ServeEngine(model, params, n_slots=1, max_len=8, page_size=4,
                      n_pages=2)          # 1 usable page, prompt needs 2
    eng.submit(list(range(5)), 2)
    with pytest.raises(RuntimeError, match="stalled"):
        eng.run()


def test_requires_paged_support(fp_cell):
    _, params = fp_cell
    cfg = get_reduced("recurrentgemma_9b")     # non-attn blocks
    with pytest.raises(NotImplementedError, match="paged"):
        ServeEngine(Model(cfg), params, max_len=8, page_size=4)


# -- the fast path: bucketed batched prefill + Pallas live-page decode -------

def test_bucket_unit():
    assert [bucket(n, 64) for n in (1, 2, 3, 4, 5, 8, 9, 33)] == \
        [1, 2, 4, 4, 8, 8, 16, 64]
    assert bucket(100, 64) == 64          # clamped to the cap
    with pytest.raises(ValueError):
        bucket(0, 64)


def _fresh_prompts(cfg, lens, seed=21):
    """Distinct random prompts (no accidental prefix sharing)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).tolist() for n in lens]


def test_bucket_boundary_identity(fp_cell):
    """Prompt lengths at bucket edges, edge+-1 and exact page_size
    multiples stay bit-identical to the per-request oracle through the
    bucketed batched prefill, and the jit specializations are bounded by
    the bucket set, not the length set."""
    model, params = fp_cell
    max_len, gen, ps = 32, 3, 4
    # buckets 4 / 8 / 16 / 32: each edge, edge+-1, and the page_size
    # multiples 4, 8, 12, 16 (12 is a multiple that is NOT a power of two)
    lens = [3, 4, 5, 7, 8, 9, 12, 15, 16, 17]
    prompts = _fresh_prompts(model.cfg, lens)
    eng = ServeEngine(model, params, n_slots=len(lens), max_len=max_len,
                      page_size=ps)
    for p in prompts:
        eng.submit(p, gen)
    done = eng.run()
    assert len(done) == len(lens)
    for r in done:
        ref = _reference(model, params, list(r.prompt), max_len, gen)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref,
                                      err_msg=f"plen={len(r.prompt)}")
    # one admission wave: the 10 lengths collapse into 4 suffix buckets
    # (4, 8, 16, 32), one batched call and one trace each
    c = eng.counters
    assert c["prefill_batched_calls"] == 4
    assert c["prefill_batched_rows"] == len(lens)
    assert eng.stats()["prefill_traces"] == 4
    assert c["bucket_hits"] == 0          # every key was new
    # a second wave re-using a seen (batch, bucket) key is a bucket hit
    # and must not add a specialization
    for p in _fresh_prompts(model.cfg, [3, 4], seed=22):
        eng.submit(p, gen)
    done2 = eng.run()
    for r in done2:
        ref = _reference(model, params, list(r.prompt), max_len, gen)
        np.testing.assert_array_equal(np.asarray(r.tokens), ref)
    assert eng.counters["bucket_hits"] >= 1
    assert eng.stats()["prefill_traces"] == 4


def test_bucketed_vs_per_request_prefill_identical(fp_cell):
    """bucket_prefill on/off is invisible in the tokens (same engine,
    same prompts, prefix sharing active)."""
    model, params = fp_cell
    prompts = _prompts(model.cfg, plen=7, n=4, seed=19)
    toks = {}
    for on in (True, False):
        eng = ServeEngine(model, params, n_slots=4, max_len=16,
                          page_size=4, bucket_prefill=on)
        for p in prompts:
            eng.submit(p, 4)
        toks[on] = {r.rid: r.tokens for r in eng.run()}
        calls = eng.counters["prefill_batched_calls"]
        assert (calls > 0) if on else (calls == 0)
    assert toks[True] == toks[False]


@pytest.mark.parametrize("page_size", [2, 4, 8])
def test_paged_kernel_vs_gather_parity(fp_cell, page_size):
    """decode_step_paged(kernel=True) == the gather oracle, bit for bit
    (logits and written pool bytes), over slots with ragged live-page
    counts and random pool contents."""
    model, params = fp_cell
    n_slots, max_len = 4, 32
    pps = max_len // page_size
    pool = model.init_page_pool(n_slots * pps + 1, page_size)
    leaves, treedef = jax.tree_util.tree_flatten(pool)
    key = jax.random.PRNGKey(3)
    pool = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                          jnp.float32).astype(leaf.dtype)
        for i, leaf in enumerate(leaves)])
    # ragged: 1, 1, 2 and 3 live pages across the four slots
    steps = [0, 1, page_size, 3 * page_size - 1]
    table = np.zeros((n_slots, pps), np.int32)
    nxt = 1
    for s in range(n_slots):
        for p in range(steps[s] // page_size + 1):
            table[s, p], nxt = nxt, nxt + 1
    tok = jnp.asarray([[5], [11], [23], [42]], jnp.int32)
    fn = jax.jit(model.decode_step_paged, static_argnames=("kernel",))
    args = (params, pool, tok, jnp.asarray(table),
            jnp.asarray(steps, jnp.int32))
    lg, pool_g = fn(*args, kernel=False)
    lk, pool_k = fn(*args, kernel=True)
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lk))
    for a, b in zip(jax.tree_util.tree_leaves(pool_g),
                    jax.tree_util.tree_leaves(pool_k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_kernel_engine_ragged_identity(fp_cell):
    """Kernel-path engine over slots with ragged live-page counts: equal
    to the per-request oracle AND to the gather-path engine, token for
    token, with decode crossing page boundaries mid-generation."""
    model, params = fp_cell
    max_len, gen = 32, 6
    prompts = _fresh_prompts(model.cfg, [3, 6, 11, 20], seed=23)
    toks = {}
    for kern in (False, True):
        eng = ServeEngine(model, params, n_slots=4, max_len=max_len,
                          page_size=4, paged_kernel=kern)
        for p in prompts:
            eng.submit(p, gen)
        toks[kern] = {r.rid: r.tokens for r in eng.run()}
        assert eng.stats()["decode_traces"] == 1   # one shape either way
        for r in eng.finished:
            ref = _reference(model, params, list(r.prompt), max_len, gen)
            np.testing.assert_array_equal(
                np.asarray(r.tokens), ref,
                err_msg=f"kernel={kern} plen={len(r.prompt)}")
    assert toks[False] == toks[True]


# -- profiler spans and the request timeline -------------------------------

class _SpanLog:
    """Stands in for ``TraceAnnotation``: logs (depth, name) on entry."""
    log: list = []
    depth = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _SpanLog.log.append((_SpanLog.depth, self.name))
        _SpanLog.depth += 1

    def __exit__(self, *exc):
        _SpanLog.depth -= 1


@pytest.mark.parametrize("bucketed", [True, False])
def test_step_phase_spans_in_order(fp_cell, monkeypatch, bucketed):
    """A step() with an arrival: admission (its prefill read back inside
    it), then the decode launch, its read-back and retirement, each in
    its own span and none at the top of another."""
    import repro.serve.engine as engine_mod
    model, params = fp_cell
    eng = ServeEngine(model, params, n_slots=2, max_len=16, page_size=4,
                      bucket_prefill=bucketed)
    eng.submit(_prompts(model.cfg, plen=5, n=1)[0], 3)
    monkeypatch.setattr(engine_mod, "TraceAnnotation", _SpanLog)
    monkeypatch.setattr(_SpanLog, "log", [])
    eng.step()
    assert _SpanLog.log == [(0, "engine.admit"), (1, "engine.sync"),
                            (0, "engine.launch"), (0, "engine.sync"),
                            (0, "engine.retire")]
    _SpanLog.log.clear()
    eng.step()                                 # no arrival: no admission
    assert [n for _, n in _SpanLog.log] == [
        "engine.admit", "engine.launch", "engine.sync", "engine.retire"]
    assert _SpanLog.depth == 0


def test_request_timeline_first_token(fp_cell):
    """``t_first`` is stamped at the end of the step() that made the first
    token, and ``report()`` times TTFT to it; a request done at prefill
    has its first and last token at the same step end."""
    model, params = fp_cell
    eng = ServeEngine(model, params, n_slots=2, max_len=16, page_size=4)
    prompts = _prompts(model.cfg, plen=5, n=3, seed=11)
    eng.submit(prompts[0], 4)
    eng.submit(prompts[1], 1)                  # done at its prefill
    eng.step()
    (one,) = eng.finished
    assert one.t_first == one.t_done and one.t_admit <= one.t_first
    eng.submit(prompts[2], 2)
    done = eng.run()
    reqs = sorted([one] + done, key=lambda r: r.rid)
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
    assert reqs[0].t_first < reqs[0].t_done    # 4 tokens over 4 steps
    ttft = {x["rid"]: x["ttft_s"] for x in eng.report()["requests"]}
    assert all(ttft[r.rid] == r.t_first - r.t_submit for r in reqs)


# -- bench contract ----------------------------------------------------------

def test_serve_engine_bench_emits_tokens_per_s(cache):
    """The BENCH_engine.json ``serve_engine`` entry: throughput series +
    prefix counters (the CI perf-trajectory contract)."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.bench_kernel import serve_engine_bench
    finally:
        sys.path.remove(ROOT)
    r = serve_engine_bench(smoke=True)
    assert r["tokens_per_s"] > 0
    assert r["total_tokens"] == r["n_requests"] * r["gen"]
    assert r["series"] and r["series"][-1]["tokens"] == r["total_tokens"]
    assert len(r["ttft_s"]) == r["n_requests"]
    assert r["counters"]["pages_shared"] > 0
    assert r["counters"]["completed"] == r["n_requests"]
