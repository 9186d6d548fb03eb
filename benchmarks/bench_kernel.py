"""Kernel-level microbench (CPU container): (a) op-count ratios of the
transitive dataflow vs dense / bit-sparse accumulation — the paper's actual
speedup source; (b) wall-clock of the batched multi-tile engine
(core/engine.py) vs the seed per-tile Python-loop walker
(core/transitive_ref.py), split into plan (offline) and run (online);
(c) interpret-mode correctness timing of the Pallas kernels; (d) HLO
flops/bytes of the W4A8 MXU path vs a bf16 matmul at equal shape (the
TPU-side memory win).

``--smoke`` shrinks every shape for CI: a few seconds total, still
exercising every code path end-to-end. ``--serve-bench`` switches to the
cached-vs-uncached serving comparison (plan built per call vs plan from
core/plancache.py) and writes ``BENCH_engine.json``; the kernel microbench
is then skipped (CI runs the two as separate steps). The serving bench
enumerates the **backend registry** (core/backend.py) — one keyed entry
per backend under ``"backends"`` in the JSON (e.g.
``engine_jit.device_decode_us``) — so the perf trajectory distinguishes
backends instead of overwriting one flat dict. Device-resident backends
additionally get a ``mesh_decode_us`` series: the same decode through the
multi-device serve cell (batch sharded ``P("data")``, DevicePlans placed
on the mesh) over the largest data extent that divides the decode batch —
1 on a plain host, 4 in the CI forced-multi-device leg.
"""
from __future__ import annotations

import argparse
import json
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, synth_weights, timed
from repro.core.engine import BatchedTransitiveEngine
from repro.core.transitive import transitive_gemm_stats
from repro.core.transitive_ref import transitive_gemm_ref
from repro.kernels import ops


def run(smoke: bool = False):
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)

    # (a) op-count ratios (N=256-row sub-tiles, T=8, int8 weights)
    na = 64 if smoke else 256
    w = synth_weights(na, na, 8, seed=0)
    x = rng.integers(-128, 128, (na, 32))
    _, tot = transitive_gemm_stats(w, x, 8, 8)
    emit("kernel_opcount", 0.0,
         f"dense={tot['dense_ops']} bit={tot['bit_ops']} "
         f"transitive={max(tot['ppe_ops'], tot['ape_ops'])} "
         f"reduction_vs_dense=x{tot['dense_ops']/max(tot['ppe_ops'], tot['ape_ops']):.2f} "
         f"(paper: 8x at T=8)")

    # (b) batched engine vs seed per-tile walker (ISSUE 1 acceptance:
    # >= 5x on 256x256x256 int8; plan is reusable across activations)
    nb = 64 if smoke else 256
    w = synth_weights(nb, nb, 8, seed=1)
    x = rng.integers(-128, 128, (nb, nb))
    eng = BatchedTransitiveEngine(bits=8, t=8)
    plan, us_plan = timed(lambda: eng.plan(w), reps=1)
    out_run, us_run = timed(lambda: eng.run(plan, x), reps=1)
    _, us_e2e = timed(lambda: eng(w, x), reps=1)
    ref, us_ref = timed(lambda: transitive_gemm_ref(w, x, 8, 8),
                        reps=1, warmup=0)
    np.testing.assert_array_equal(out_run, ref)
    np.testing.assert_array_equal(out_run,
                                  w.astype(np.int64) @ x.astype(np.int64))
    emit("kernel_engine_vs_ref", us_e2e,
         f"{nb}x{nb}x{nb} int8 T=8: ref={us_ref:.0f}us plan={us_plan:.0f}us "
         f"run={us_run:.0f}us speedup_e2e=x{us_ref/us_e2e:.1f} "
         f"speedup_run=x{us_ref/us_run:.1f} (floor: 5x)")

    # (c) interpret-mode kernel wall-times (correctness path, not perf)
    mc, nc, kc = (16, 8, 64) if smoke else (128, 64, 256)
    qx = jnp.asarray(rng.integers(-128, 128, (mc, kc)), jnp.int8)
    qw = jnp.asarray(synth_weights(nc, kc, 4), jnp.int8)
    _, us = timed(lambda: jax.block_until_ready(
        ops.transitive_gemm(qx, qw, w_bits=4, t=8)))
    emit("kernel_transitive_interpret", us,
         f"{mc}x{nc}x{kc} w4 (interpret mode)")

    if not smoke:
        sx = jnp.ones((128, 1), jnp.float32)
        sg = jnp.ones((64, 2), jnp.float32)
        _, us = timed(lambda: jax.block_until_ready(
            ops.w4a8_gemm(qx, sx, qw, sg, group=128)))
        emit("kernel_w4a8_interpret", us, "128x64x256 (interpret mode)")

        # (d) dry-lowered flops/bytes: W4A8 int path vs bf16 dense
        m, n, k = 256, 512, 1024
        def int_path(qx, qw):
            return jax.lax.dot_general(qx, qw, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.int32)
        def bf16_path(a, b):
            return a @ b.T
        def cost(ca):
            # old jax returns a per-device list of dicts, new jax one dict
            return ca[0] if isinstance(ca, (list, tuple)) else ca
        ca_int = cost(jax.jit(int_path).lower(
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((n, k), jnp.int8)).compile().cost_analysis())
        ca_bf = cost(jax.jit(bf16_path).lower(
            jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((n, k), jnp.bfloat16)).compile().cost_analysis())
        emit("kernel_w4a8_vs_bf16_bytes", 0.0,
             f"int8_bytes={ca_int.get('bytes accessed', 0):.0f} "
             f"bf16_bytes={ca_bf.get('bytes accessed', 0):.0f} "
             f"ratio={ca_bf.get('bytes accessed', 1)/max(ca_int.get('bytes accessed', 1),1):.2f}x")
    emit("kernel_total", (time.perf_counter() - t0) * 1e6,
         "smoke" if smoke else "ok")


def serve_engine_bench(smoke: bool = False, backend: str = "engine_jit",
                       mesh=None) -> dict:
    """Continuous-batching throughput/latency series (repro.serve).

    Drives the paged-KV :class:`ServeEngine` over staggered arrivals with
    shared prompt prefixes on the reduced smollm config and reports
    aggregate tokens/s, per-request TTFT/latency, and a per-step
    cumulative-token series — the request-level counterpart of the
    per-backend GEMM decode series. Lands under ``"serve_engine"`` in
    BENCH_engine.json (``serve_engine.tokens_per_s`` is the trajectory
    key)."""
    from repro.configs import get_reduced
    from repro.core.backend import get_backend
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.serve import ServeEngine

    cfg = serve_config(get_reduced("smollm_135m").replace(
        n_layers=2 if smoke else 4), backend=backend)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = get_backend(backend)
    if b.needs_plan:
        model.precompile_plans(params)
        if b.device_resident:
            params = model.attach_device_plans(params, mesh=mesh)
    rng = np.random.default_rng(3)
    plen, gen, n_req = (8, 4, 4) if smoke else (16, 16, 8)
    base = rng.integers(0, cfg.vocab, size=plen).tolist()
    # every other request extends the shared base prompt — the prefix trie
    # should serve those pages instead of re-prefilling them
    prompts = [list(base) if i % 2 == 0 else
               base[:plen // 2] + rng.integers(
                   0, cfg.vocab, size=plen - plen // 2).tolist()
               for i in range(n_req)]
    page_size = 4
    max_len = -(-(plen + gen) // page_size) * page_size
    eng = ServeEngine(model, params, n_slots=2 if smoke else 4,
                      max_len=max_len, page_size=page_size, mesh=mesh)
    series = []
    submitted = host_step = 0
    arrive_every = 2                        # staggered arrivals
    t0 = time.perf_counter()
    while submitted < n_req or eng.queue or eng.active:
        if submitted < n_req and host_step >= submitted * arrive_every:
            eng.submit(prompts[submitted], gen)
            submitted += 1
        eng.step()
        host_step += 1
        done = (sum(len(r.out) for r in eng.finished)
                + sum(len(r.out) for r in eng.active.values()))
        series.append({"t_s": time.perf_counter() - t0, "tokens": done})
    rep = eng.report()
    emit("serve_engine", rep["wall_s"] * 1e6,
         f"{backend}: {rep['n_requests']} reqs x {gen} tokens "
         f"(prompt {plen}) staggered -> {rep['tokens_per_s']:.1f} tok/s "
         f"(prefix hits={rep['counters']['prefix_hits']} "
         f"pages shared={rep['counters']['pages_shared']} "
         f"prefill skipped={rep['counters']['prefill_skipped']})")
    return {"backend": backend, "prompt_len": plen, "gen": gen,
            "n_requests": rep["n_requests"],
            "total_tokens": rep["total_tokens"],
            "wall_s": rep["wall_s"],
            "tokens_per_s": rep["tokens_per_s"],
            "ttft_s": [r["ttft_s"] for r in rep["requests"]],
            "latency_s": [r["latency_s"] for r in rep["requests"]],
            "series": series,
            "counters": {k: rep["counters"][k] for k in
                         ("prefix_hits", "pages_shared", "prefill_skipped",
                          "prefill_computed", "decode_steps",
                          "admitted", "completed")}}


def serve_fastpath_bench(smoke: bool = False,
                         backend: str = "engine_jit") -> dict:
    """The PR-8 serve fast paths as curves, not points.

    (a) ``paged_kernel``: a ``max_len`` sweep timing one packed decode
    step through the full-extent gather oracle vs the Pallas live-page
    kernel at a FIXED small live-page count — the gather cost grows with
    ``max_len`` while the kernel cost tracks live pages — plus
    engine-level tokens/s for both paths at the largest swept ``max_len``.
    (b) ``prefill_bucketed``: the same staggered workload with bucketing
    on vs off, reporting distinct prefill jit specializations and bucket
    hits. Lands under ``serve_engine.paged_kernel`` /
    ``serve_engine.prefill_bucketed`` in BENCH_engine.json.
    """
    from repro.configs import get_reduced
    from repro.core.backend import get_backend
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.serve import ServeEngine

    cfg = serve_config(get_reduced("smollm_135m").replace(
        n_layers=2 if smoke else 4), backend=backend)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = get_backend(backend)
    if b.needs_plan:
        model.precompile_plans(params)
        if b.device_resident:
            params = model.attach_device_plans(params)

    # page_size 16 and a deep max_len sweep: the gather oracle's per-step
    # K/V materialization is O(max_len) while the kernel touches only the
    # fixed live pages (its residual growth is the full-extent softmax +
    # page-table scan) — the curves separate visibly from ~512 up
    page_size = 16
    n_slots = 4
    live_pages = 2                      # steps fixed -> kernel cost fixed
    sweep = (256, 512) if smoke else (512, 2048, 8192)
    iters = 3 if smoke else 10
    dstep = jax.jit(model.decode_step_paged, static_argnames=("kernel",))
    curve = []
    for max_len in sweep:
        pps = max_len // page_size
        pool = model.init_page_pool(n_slots * pps + 1, page_size)
        table = np.zeros((n_slots, pps), np.int32)
        for s in range(n_slots):
            table[s, :live_pages] = [s * live_pages + 1 + j
                                     for j in range(live_pages)]
        steps = jnp.full((n_slots,), live_pages * page_size - 1, jnp.int32)
        toks = jnp.ones((n_slots, 1), jnp.int32)
        tbl = jnp.asarray(table)
        entry = {"max_len": max_len, "live_pages": live_pages}
        for kern, key in ((False, "gather_decode_us"),
                          (True, "kernel_decode_us")):
            lg, _ = dstep(params, pool, toks, tbl, steps, kernel=kern)
            jax.block_until_ready(lg)   # compile outside the timed loop
            t0 = time.perf_counter()
            for _ in range(iters):
                lg, _ = dstep(params, pool, toks, tbl, steps, kernel=kern)
                jax.block_until_ready(lg)
            entry[key] = (time.perf_counter() - t0) * 1e6 / iters
        curve.append(entry)
        emit("serve_engine.paged_kernel", entry["kernel_decode_us"],
             f"max_len={max_len} live_pages={live_pages}: "
             f"gather={entry['gather_decode_us']:.0f}us "
             f"kernel={entry['kernel_decode_us']:.0f}us "
             f"(x{entry['gather_decode_us']/entry['kernel_decode_us']:.1f})")

    # engine-level throughput at the largest swept max_len, both paths +
    # bucketing on/off for the specialization counts
    max_len = sweep[-1]
    rng = np.random.default_rng(5)
    plen, gen, n_req = (6, 6, 4) if smoke else (8, 24, 6)
    prompts = [rng.integers(0, cfg.vocab, size=3 + (i * 5) % (plen - 2)
                            + 1).tolist() for i in range(n_req)]
    tput = {}
    bucketed = {}
    for kern, bucket_on in ((False, False), (True, True)):
        eng = ServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                          page_size=page_size, paged_kernel=kern,
                          bucket_prefill=bucket_on)
        submitted = host_step = 0
        while submitted < n_req or eng.queue or eng.active:
            if submitted < n_req and host_step >= submitted * 2:
                eng.submit(prompts[submitted], gen)
                submitted += 1
            eng.step()
            host_step += 1
        rep = eng.report()
        st = eng.stats()
        key = "fastpath" if kern else "oracle"
        tput[f"tokens_per_s_{key}"] = rep["tokens_per_s"]
        bucketed["bucketed" if bucket_on else "per_request"] = {
            "prefill_traces": st["prefill_traces"],
            "prefill_calls": st["prefill_calls"],
            "prefill_batched_calls": st["prefill_batched_calls"],
            "bucket_hits": st["bucket_hits"],
            "prefill_pad_rows": st["prefill_pad_rows"]}
    emit("serve_engine.prefill_bucketed", 0.0,
         f"max_len={max_len} {n_req} reqs: "
         f"traces per-request={bucketed['per_request']['prefill_traces']} "
         f"bucketed={bucketed['bucketed']['prefill_traces']} "
         f"bucket_hits={bucketed['bucketed']['bucket_hits']} | tok/s "
         f"oracle={tput['tokens_per_s_oracle']:.1f} "
         f"fastpath={tput['tokens_per_s_fastpath']:.1f}")
    return {"paged_kernel": {"page_size": page_size, "n_slots": n_slots,
                             "sweep": curve, **tput},
            "prefill_bucketed": bucketed}


def serve_hotswap_bench(smoke: bool = False,
                        backend: str = "engine_jit") -> dict:
    """Live-weight swap cost as a timeline, not a point (PR 9).

    Serves the same two-phase workload twice on the reduced smollm
    config: **hot** — the fleet path, where generation 1 is built
    off-path (``repro.fleet.build_generation``) and atomically swapped
    between decode steps — and **drain_restart** — the pre-fleet
    baseline, where the engine drains, the process pays the cold plan
    build inline, and a new engine starts. Both runs record per-step
    decode wall times; the headline is the worst inter-step stall around
    the weight change (``stall_hot_us`` vs ``stall_restart_us`` — the
    hot one should be a normal step, the restart one IS the plan build).
    Also times the bundle pipeline on the same weights:
    ``bundle_write_us`` (planner, amortised once per fleet) vs
    ``bundle_load_us`` (per serve cell, fresh cache, zero plan builds)
    vs ``plan_build_us`` (what the cell pays without bundles). Lands
    under ``serve_engine.hotswap`` in BENCH_engine.json."""
    import shutil
    import tempfile

    from repro.configs import get_reduced
    import repro.core.plancache as PC
    from repro.core.plancache import PlanCache
    from repro.fleet import build_generation, load_bundles, write_bundles
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.serve import ServeEngine

    cfg = serve_config(get_reduced("smollm_135m").replace(
        n_layers=2 if smoke else 4), backend=backend)
    model = Model(cfg)
    raw = {g: model.init(jax.random.PRNGKey(g)) for g in (0, 1)}
    rng = np.random.default_rng(5)
    plen, gen_toks, n_req = (8, 6, 4) if smoke else (16, 16, 8)
    prompts = [rng.integers(0, cfg.vocab, size=plen).tolist()
               for _ in range(n_req)]
    first = n_req // 2
    page_size = 4
    max_len = -(-(plen + gen_toks) // page_size) * page_size

    def _run(eng, reqs, series, swap_to=None, swap_at=2):
        """Drive reqs to completion, appending per-step wall times;
        optionally stage a pre-built generation after ``swap_at`` steps."""
        submitted = 0
        swapped = None
        while submitted < len(reqs) or eng.queue or eng.active:
            if submitted < len(reqs):
                eng.submit(reqs[submitted], gen_toks)
                submitted += 1
            if swap_to is not None and swapped is None \
                    and len(series) >= swap_at:
                swapped = eng.swap_params(swap_to.params, tag="bench")
            t0 = time.perf_counter()
            eng.step()
            series.append({"step_us": (time.perf_counter() - t0) * 1e6,
                           "generation": eng.generation})
        return swapped

    # -- hot: generation 1 built off-path, swapped between steps ----------
    cache = PlanCache(capacity=256)
    prev = PC.set_default_cache(cache)
    try:
        gen0 = build_generation(model, raw[0], gen=0)
        t0 = time.perf_counter()
        gen1 = build_generation(model, raw[1], ref=gen0.params, gen=1)
        plan_build_us = (time.perf_counter() - t0) * 1e6

        hot: list[dict] = []
        eng = ServeEngine(model, gen0.params, n_slots=2, max_len=max_len,
                          page_size=page_size)
        _run(eng, prompts[:first], hot)       # warm the jits on gen 0
        warm = len(hot)
        _run(eng, prompts[first:], hot, swap_to=gen1)
        swap_step = next(i for i, s in enumerate(hot)
                         if s["generation"] > 0)
        stall_hot_us = max(s["step_us"] for s in hot[warm:])
        hot_traces = eng.stats()["decode_jit_traces"]

        # -- drain-and-restart baseline: cold build inline ----------------
        restart: list[dict] = []
        eng = ServeEngine(model, gen0.params, n_slots=2, max_len=max_len,
                          page_size=page_size)
        _run(eng, prompts[:first], restart)   # drains completely
        t0 = time.perf_counter()
        PC.set_default_cache(PlanCache(capacity=256))   # cold process
        gen1_cold = build_generation(model, raw[1], gen=1)
        eng = ServeEngine(model, gen1_cold.params, n_slots=2,
                          max_len=max_len, page_size=page_size)
        stall_restart_us = (time.perf_counter() - t0) * 1e6
        restart.append({"step_us": stall_restart_us, "generation": 1,
                        "restart_gap": True})
        _run(eng, prompts[first:], restart)
    finally:
        PC.set_default_cache(prev)

    # -- bundles: plan once (planner), load on a fresh cell ---------------
    bdir = tempfile.mkdtemp(prefix="hotswap_bundles_")
    try:
        t0 = time.perf_counter()
        write_bundles(raw[1], cfg.quant, bdir)
        bundle_write_us = (time.perf_counter() - t0) * 1e6
        cell_cache = PlanCache(capacity=256)
        prev = PC.set_default_cache(cell_cache)
        try:
            t0 = time.perf_counter()
            load_bundles(raw[1], cfg.quant, bdir)
            bundle_load_us = (time.perf_counter() - t0) * 1e6
        finally:
            PC.set_default_cache(prev)
        if cell_cache.stats()["misses"]:
            raise RuntimeError("bundle load built plans on the serve "
                               f"cell: {cell_cache.stats()}")
    finally:
        shutil.rmtree(bdir, ignore_errors=True)

    emit("serve_engine.hotswap", stall_hot_us,
         f"{backend}: swap stall hot={stall_hot_us:.0f}us vs "
         f"drain+restart={stall_restart_us:.0f}us "
         f"(x{stall_restart_us / max(stall_hot_us, 1):.1f}) | "
         f"decode traces through swap={hot_traces} | plan_build="
         f"{plan_build_us:.0f}us bundle_write={bundle_write_us:.0f}us "
         f"bundle_load={bundle_load_us:.0f}us")
    return {"backend": backend, "n_requests": n_req, "gen": gen_toks,
            "swap_step": swap_step,
            "stall_hot_us": stall_hot_us,
            "stall_restart_us": stall_restart_us,
            "decode_jit_traces_hot": hot_traces,
            "plan_build_us": plan_build_us,
            "bundle_write_us": bundle_write_us,
            "bundle_load_us": bundle_load_us,
            "timeline_hot": hot,
            "timeline_restart": restart}


def serve_bench(smoke: bool = False, out: str = "BENCH_engine.json",
                backends=None):
    """Cached vs uncached serving + a per-backend decode series.

    The headline pair stays what it was: *uncached* is the
    pre-plan-cache serving behaviour (every forward call re-plans the
    weight), *cached* is the plan-cached host engine (plans built once
    offline via PlanCache, decode run-only). Then every registered
    backend (``repro.core.backend`` — or the ``backends`` subset) decodes
    the same weights through its own ``execute`` path under jit, plans
    and DevicePlans prepared offline, and the JSON gains one keyed entry
    per backend under ``"backends"`` — ``engine_jit.device_decode_us``
    next to ``engine.callback_decode_us`` next to ``int_dot.decode_us`` —
    so the CI perf trajectory distinguishes backends instead of
    overwriting one flat dict. Every series is guarded bit-exact against
    the int64 GEMM before its numbers are emitted."""
    from repro.core.backend import EngineConfig, get_backend, list_backends
    import repro.core.plancache as PC
    from repro.core.plancache import PlanCache

    names = list(backends) if backends else [
        nm for nm in list_backends() if get_backend(nm).cpu_ok]
    layers, steps = (4, 8) if smoke else (8, 32)
    n = k = 64 if smoke else 256
    m = 4                                    # decode-like tall-skinny GEMM
    ecfg = EngineConfig(w_bits=8, t=8, groups=1)
    rng = np.random.default_rng(2)
    # int8 like the serving path (the cache canonicalises dtype before
    # fingerprinting, so every series shares one entry per weight either
    # way; the misses guard below would catch a regression)
    ws = [synth_weights(n, k, 8, seed=s).astype(np.int8)
          for s in range(layers)]
    xs = [rng.integers(-128, 128, (k, m)) for _ in range(steps)]
    wants0 = [xs[0].T.astype(np.int64) @ w.astype(np.int64).T
              for w in ws]                   # (M, N) int64 guard truth
    eng = BatchedTransitiveEngine(bits=8, t=8)

    t0 = time.perf_counter()
    for x in xs:
        for w in ws:
            eng(w, x)                        # plan + run, every call
    us_uncached = (time.perf_counter() - t0) * 1e6

    cache = PlanCache(capacity=2 * layers)
    t0 = time.perf_counter()
    for w in ws:                             # offline precompile
        cache.get_or_build(w, ecfg)
    us_plan = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for x in xs:
        for w in ws:                         # hot path: run-only
            cache.run(w, x, ecfg)
    us_cached = (time.perf_counter() - t0) * 1e6

    stats = cache.stats()
    # fail loudly even under python -O: a re-plan in the cached loop would
    # make the emitted numbers meaningless
    if stats["misses"] != layers or stats["hits"] != layers * steps:
        raise RuntimeError(f"plan cache re-planned during the cached loop: "
                           f"{stats} (expected misses={layers}, "
                           f"hits={layers * steps})")
    calls = layers * steps
    result = {
        "shape": {"layers": layers, "decode_steps": steps,
                  "n": n, "k": k, "m": m, "w_bits": 8, "t": 8},
        "uncached_us": us_uncached,
        "plan_build_us": us_plan,
        "cached_decode_us": us_cached,
        "per_call_uncached_us": us_uncached / calls,
        "per_call_cached_us": us_cached / calls,
        "speedup_cached": us_uncached / us_cached,
        "backends": {},
    }

    # per-backend decode series: same weights, each backend's own execute
    # path under jit. The engine host callbacks resolve plans from our warm
    # cache (swapped in as the process default for the duration).
    prev = PC.set_default_cache(cache)
    try:
        xs_row = [jnp.asarray(x.T, jnp.int8) for x in xs]      # (M, K)
        qws = [jnp.asarray(w, jnp.int8) for w in ws]
        for name in names:
            b = get_backend(name)
            entry: dict[str, float] = {}
            plans = [None] * layers
            dplans = [None] * layers
            if b.needs_plan:
                plans = [cache.get_or_build(w, ecfg, backend=name)
                         for w in ws]        # warm: all hits
            if b.needs_plan and b.device_resident:
                t0 = time.perf_counter()
                dplans = [cache.get_or_build_device(w, ecfg, backend=name)
                          for w in ws]
                entry["device_plan_compile_us"] = \
                    (time.perf_counter() - t0) * 1e6
            fns = [jax.jit(lambda a, _b=b, _w=qws[i], _p=plans[i],
                           _d=dplans[i]: _b.execute(a, _w, _p, _d, ecfg))
                   for i in range(layers)]
            # bit-exact guard before timing: int32 ≡ int64 mod 2^32 (smoke
            # magnitudes don't overflow) — a wrong number here would make
            # the emitted series meaningless
            for i, f in enumerate(fns):
                np.testing.assert_array_equal(
                    np.asarray(f(xs_row[0])), wants0[i])
            t0 = time.perf_counter()
            for qx in xs_row:
                for f in fns:
                    jax.block_until_ready(f(qx))
            us_decode = (time.perf_counter() - t0) * 1e6
            decode_key = ("device_decode_us" if b.device_resident
                          and b.needs_plan else
                          "callback_decode_us" if b.needs_plan else
                          "decode_us")
            entry[decode_key] = us_decode
            entry["per_call_us"] = us_decode / calls

            if b.device_resident:
                # the multi-device serve cell's decode: batch sharded
                # P("data") over the widest data extent dividing it, plan
                # leaves placed on the mesh (replicated — the serve-cell
                # default). On a plain 1-device host the extent is 1 (the
                # code path still runs end-to-end); the CI forced-multi-
                # device leg produces the real N-way number.
                from jax.sharding import (Mesh, NamedSharding,
                                          PartitionSpec as P)
                from repro.core.backend import shard_device_plan
                mesh_n = max(d for d in
                             range(1, min(len(jax.devices()), m) + 1)
                             if m % d == 0)
                mesh = Mesh(np.asarray(jax.devices()[:mesh_n]), ("data",))
                mdplans = [shard_device_plan(d, mesh) if d is not None
                           else None for d in dplans]
                xs_mesh = [jax.device_put(
                    qx, NamedSharding(mesh, P("data", None)))
                    for qx in xs_row]
                mfns = [jax.jit(lambda a, _b=b, _w=qws[i], _p=plans[i],
                                _d=mdplans[i]: _b.execute(a, _w, _p, _d,
                                                          ecfg))
                        for i in range(layers)]
                for i, f in enumerate(mfns):
                    np.testing.assert_array_equal(
                        np.asarray(f(xs_mesh[0])), wants0[i])
                t0 = time.perf_counter()
                for qx in xs_mesh:
                    for f in mfns:
                        jax.block_until_ready(f(qx))
                entry["mesh_decode_us"] = (time.perf_counter() - t0) * 1e6
                entry["mesh_devices"] = mesh_n
            result["backends"][name] = entry
    finally:
        PC.set_default_cache(prev)

    # every series must have run against the plans built above — any new
    # miss means a fingerprint diverged and the comparison is meaningless
    if cache.stats()["misses"] != layers:
        raise RuntimeError(
            f"a backend series re-planned: {cache.stats()} "
            f"(expected misses={layers})")
    result["cache"] = cache.stats()

    # continuous-batching engine: request-level throughput next to the
    # GEMM-level decode series (acceptance key: serve_engine.tokens_per_s)
    result["serve_engine"] = serve_engine_bench(smoke=smoke)

    # PR-8 fast paths: live-page kernel max_len sweep + bucketed-prefill
    # specialization counts (serve_engine.paged_kernel.* /
    # serve_engine.prefill_bucketed.*)
    result["serve_engine"].update(serve_fastpath_bench(smoke=smoke))

    # PR-9 live-weight serving: hot-swap stall timeline vs drain-and-
    # restart + the bundle pipeline costs (serve_engine.hotswap.*)
    result["serve_engine"]["hotswap"] = serve_hotswap_bench(smoke=smoke)

    # static-analysis gate overhead (ISSUE 10): verify one plan + its
    # lowering, and cost one decode jaxpr — the work the publish gates
    # add per cold build. Tracked so the gates stay off the hot path
    # (they run once per plan build / bundle load / swap, never per
    # decode step).
    import timeit as _timeit

    from repro.analysis.costcheck import jaxpr_cost
    from repro.analysis.planlint import verify_device_plan, verify_plan
    from repro.core.backend import get_backend as _get_backend
    _plan = cache.get_or_build(ws[0], ecfg)
    _dev = _get_backend("engine_jit").compile(_plan)
    _n = 3
    _lint_s = _timeit.timeit(
        lambda: (verify_plan(_plan), verify_device_plan(_dev, _plan)),
        number=_n) / _n
    _w32 = jnp.asarray(ws[0], jnp.int32)
    _jx = jax.make_jaxpr(
        lambda x: jnp.einsum("bk,nk->bn", x, _w32)
    )(jnp.ones((4, k), jnp.int8))
    _cost_s = _timeit.timeit(lambda: jaxpr_cost(_jx), number=_n) / _n
    result["analysis"] = {"planlint_us": _lint_s * 1e6,
                          "costcheck_us": _cost_s * 1e6}

    # legacy flat aliases for the PR-2/PR-3 trajectory keys
    eng_e = result["backends"].get("engine", {})
    eng_j = result["backends"].get("engine_jit", {})
    if "callback_decode_us" in eng_e:
        result["callback_decode_us"] = eng_e["callback_decode_us"]
        result["per_call_callback_us"] = eng_e["per_call_us"]
    if "device_decode_us" in eng_j:
        result["device_plan_compile_us"] = eng_j["device_plan_compile_us"]
        result["device_decode_us"] = eng_j["device_decode_us"]
        result["per_call_device_us"] = eng_j["per_call_us"]
        result["speedup_device_vs_cached"] = \
            us_cached / eng_j["device_decode_us"]
        if "callback_decode_us" in eng_e:
            result["speedup_device_vs_callback"] = \
                eng_e["callback_decode_us"] / eng_j["device_decode_us"]

    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    per_backend = " ".join(
        f"{nm}={e.get('device_decode_us', e.get('callback_decode_us', e.get('decode_us', 0.0))):.0f}us"
        for nm, e in result["backends"].items())
    emit("serve_plan_cache", us_cached,
         f"{layers} layers x {steps} steps {n}x{k}x{m}: "
         f"uncached={us_uncached:.0f}us plan_once={us_plan:.0f}us "
         f"cached_decode={us_cached:.0f}us "
         f"speedup=x{result['speedup_cached']:.1f} | {per_backend} "
         f"(misses={stats['misses']} hits={stats['hits']}) -> {out}")


def main() -> None:
    from repro.core.backend import list_backends
    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--serve-bench", action="store_true",
                    help="run ONLY the cached-vs-uncached serving benchmark "
                    "(the kernel microbench is its own invocation)")
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="output path for the serving-bench JSON")
    ap.add_argument("--backends", default=None,
                    help="comma-separated registry backend names for the "
                    "serve-bench decode series (default: every CPU-capable "
                    f"registered backend: {','.join(list_backends())})")
    ap.add_argument("--path", default=None,
                    choices=("engine", "engine_jit"),
                    help="DEPRECATED alias: 'engine' = host series only, "
                    "'engine_jit' = host + device series (use --backends)")
    args = ap.parse_args()
    enable_compile_cache()
    backends = args.backends.split(",") if args.backends else None
    if args.path is not None and backends is None:
        warnings.warn("--path is deprecated; use --backends",
                      DeprecationWarning)
        backends = (["engine"] if args.path == "engine"
                    else ["engine", "engine_jit"])
    if args.serve_bench:
        serve_bench(smoke=args.smoke, out=args.json, backends=backends)
    else:
        run(smoke=args.smoke)


if __name__ == "__main__":
    main()
