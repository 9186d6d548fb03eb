"""Serving launcher CLI — batched prefill + greedy decode through the
Transitive-Array path (W4A8 TransitiveLinear + dynamic int8 attention +
KV8 cache).

  PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --reduced \
      --batch 4 --prompt-len 16 --gen 16 [--w-bits 4] [--backend engine]

``--backend`` takes any name from the execution-backend registry
(``repro.core.backend.list_backends()`` — the choice list below is
enumerated from it, not hardcoded). What the launcher does follows the
backend's declared capabilities:

  * ``needs_plan`` backends (the engine family) serve plan-cached: every
    layer's ExecutionPlan is built exactly once (offline precompile over
    the params pytree), decode is run-only, and the report splits
    plan-build time from decode time and prints the cache counters
    (misses == distinct quantized weights, hits == remaining engine
    forward calls) — per backend.
  * ``device_resident`` planned backends additionally get their compiled
    plans embedded into the params pytree (``Model.attach_device_plans``)
    so the block scan slices them alongside the weights — decode runs
    pure JAX with zero host callbacks.

``--mesh data=N`` serves on a device mesh — the multi-device serve cell:
the batch is sharded ``P("data")`` end-to-end through prefill + decode
(``greedy_generate(mesh=)``), and device-resident backends attach their
DevicePlans placed on the mesh (replicated by default — each backend's
``plan_specs`` capability hook decides). Tokens are bit-identical to the
1-device run. On a CPU host, fake the devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the CI smoke).

``--continuous`` switches from the one-shot batched generate to the
continuous-batching serve engine (``repro.serve.ServeEngine``): requests
arrive staggered (``--requests`` of them, one every ``--arrive-every``
host steps), are admitted into ``--slots`` packed decode slots over a
paged KV pool (``--page-size`` tokens per page), and prompts sharing a
prefix share pages through the prefix trie instead of re-prefilling. The
report prints per-request TTFT/latency, aggregate tokens/s, and the
prefix-reuse counters. Tokens stay bit-identical to running each request
alone through the one-shot path.

``--lint`` runs the tracelint preflight (``repro.analysis``) over the
selected backend's serving programs under the selected mesh before any
weight is initialised — plus the plan-IR verifier (``planlint``) over
the backend's plan artifacts — and refuses to serve on any error
finding: the same gate CI runs, one flag away at launch time.

Fleet flags (docs/FLEET.md):

  * ``--role planner --bundle-dir D`` plans + compiles every layer once
    and writes fingerprinted plan bundles to ``D`` (no serving);
    ``--role server --bundle-dir D`` attaches those bundles instead of
    planning — zero plan builds on the serve cell, refusal if the
    bundle's weight fingerprint / config / backend don't match.
  * ``--watch-weights D`` (with ``--continuous``) serves through a live
    weight update: a ``ReplanWorker`` rebuilds plans on a background
    thread when a new checkpoint lands in ``D`` and the engine hot-swaps
    at a step boundary — in-flight requests finish on the weights that
    admitted them, decode is not retraced. The launcher itself stages
    the update (re-init with ``--swap-seed`` written as a checkpoint
    after ``--swap-after`` host steps) so the swap is reproducible;
    ``--assert-swap-identity`` then checks every finished request
    bit-matches the one-shot path on its own generation's weights.

``--path`` is the deprecated spelling of ``--backend``.
"""
from __future__ import annotations

import argparse
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced
from repro.core.backend import get_backend, list_backends
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.launch.specs import mesh_decode_report, serve_config
from repro.models.model import Model
from repro.train.serve_step import greedy_generate


def device_line() -> str:
    """The device every report names: platform, kind and count."""
    devs = jax.devices()
    return (f"[device] platform={devs[0].platform} "
            f"kind={devs[0].device_kind} count={len(devs)}")


def _serve_continuous(model, params, cfg, args, mesh, name,
                      raw_params=None):
    """Continuous-batching serve: staggered arrivals through ServeEngine.

    With ``--watch-weights`` the launcher stages a live weight update mid
    run: half the requests are admitted on generation 0, a fresh
    checkpoint is written after ``--swap-after`` host steps, the
    ``WeightWatcher``/``ReplanWorker`` pair rebuilds plans off-thread
    while the engine keeps stepping, and the remaining requests land on
    generation 1 after the atomic swap.
    """
    from repro.serve import ServeEngine

    ps = args.page_size
    max_len = -(-(args.prompt_len + args.gen) // ps) * ps
    eng = ServeEngine(model, params, n_slots=args.slots, max_len=max_len,
                      page_size=ps, mesh=mesh,
                      paged_kernel=args.paged_kernel,
                      bucket_prefill=not args.no_bucket_prefill)
    rng = np.random.default_rng(1)
    base = rng.integers(0, cfg.vocab, size=args.prompt_len).tolist()
    # arrival pattern with real prefix structure: even requests replay the
    # base prompt (full-prefix hit after the first), odd ones keep only the
    # first half (partial hit at page granularity)
    prompts = [list(base) if i % 2 == 0 else
               base[:args.prompt_len // 2] + rng.integers(
                   0, cfg.vocab,
                   size=args.prompt_len - args.prompt_len // 2).tolist()
               for i in range(args.requests)]

    hot = args.watch_weights
    worker = watcher = None
    gen_raw = {0: raw_params}
    failures = []
    if hot:
        from repro.distributed import checkpoint
        from repro.fleet import ReplanWorker, WeightWatcher

        def _on_ready(g):
            new_gen = eng.swap_params(g.params, tag=g.tag)
            print(f"[hotswap] generation {new_gen} staged "
                  f"(checkpoint step {g.tag}, build {g.build_s:.2f}s, "
                  f"{g.plans_built} plan builds, off-thread)")

        def _on_error(e):
            failures.append(e)
            print(f"[hotswap] replan FAILED — previous generation keeps "
                  f"serving (rollback): {e}")

        worker = ReplanWorker(model, mesh=mesh, reference=params,
                              on_ready=_on_ready, on_error=_on_error)
        watcher = WeightWatcher(hot, raw_params, worker)
        # only react to checkpoints newer than whatever the dir holds now
        watcher.seen_step = checkpoint.latest_step(hot)
        new_raw = model.init(jax.random.PRNGKey(args.swap_seed))
        gen_raw[1] = new_raw
        ckpt_written = False

    # with a staged swap, the second half of the requests waits for gen 1
    first = (args.requests + 1) // 2 if hot else args.requests
    submitted = host_step = 0
    t0 = time.time()
    while (submitted < args.requests or eng.queue or eng.active
           or (hot and eng.generation == 0 and not failures)):
        limit = (first if (hot and eng.generation == 0)
                 else args.requests)
        if (submitted < limit
                and host_step >= submitted * args.arrive_every):
            eng.submit(prompts[submitted], args.gen)
            submitted += 1
        if hot:
            if not ckpt_written and host_step >= args.swap_after:
                step = (watcher.seen_step or 0) + 1
                checkpoint.save(hot, step, new_raw)
                ckpt_written = True
                print(f"[hotswap] new weights written as checkpoint "
                      f"step {step} at host step {host_step}")
            watcher.poll()
        eng.step()
        host_step += 1
    if worker is not None:
        worker.stop()
    dt = time.time() - t0
    rep = eng.report()
    mode = "fp" if args.fp else f"W{args.w_bits}A8+KV8/{name}"
    print(f"[{cfg.name} | {mode} | continuous] {rep['n_requests']} requests "
          f"x {args.gen} tokens (staggered every {args.arrive_every} steps, "
          f"{args.slots} slots, page_size={ps}) in {dt:.2f}s -> "
          f"{rep['tokens_per_s']:.1f} tok/s")
    print(device_line())
    for r in rep["requests"]:
        print(f"  req {r['rid']}: prompt={r['prompt_len']} "
              f"tokens={r['n_tokens']} shared_pages={r['shared_pages']} "
              f"prefill_computed={r['prefill_computed']} "
              f"ttft={r['ttft_s'] * 1e3:.1f}ms "
              f"latency={r['latency_s'] * 1e3:.1f}ms")
    c = rep["counters"]
    print(f"[prefix reuse] hits={c['prefix_hits']} "
          f"pages_shared={c['pages_shared']} "
          f"prefill_skipped={c['prefill_skipped']} "
          f"prefill_computed={c['prefill_computed']} | "
          f"pages={c['pages']} trie={c['trie']}")
    print(f"[fast path] decode={'pallas-kernel' if args.paged_kernel else 'gather'} "
          f"prefill={'per-request' if args.no_bucket_prefill else 'bucketed'} | "
          f"jit traces: prefill={c['prefill_traces']} "
          f"decode={c['decode_traces']} bucket_hits={c['bucket_hits']} "
          f"batched_calls={c['prefill_batched_calls']} "
          f"pad_rows={c['prefill_pad_rows']}")
    for r in eng.finished:
        gen = f" gen={r.gen}" if hot else ""
        print(f"  req {r.rid}:{gen} {r.tokens}")
    if hot:
        _hotswap_report(model, eng, args, failures, gen_raw, worker)
    return eng


def _hotswap_report(model, eng, args, failures, gen_raw, worker):
    """Print the swap outcome; with --assert-swap-identity, bit-compare
    every finished request against the one-shot path on its own
    generation's weights (SystemExit on any mismatch or failed build)."""
    s = eng.stats()
    print(f"[hotswap] generation={s['generation']} "
          f"swaps={eng.counters['swaps']} "
          f"retired={eng.counters['generations_retired']} "
          f"decode_jit_traces={s['decode_jit_traces']} "
          f"prefill_jit_traces={s['prefill_jit_traces']} | "
          f"worker: {worker.stats()}")
    if failures:
        if args.assert_swap_identity:
            raise SystemExit(f"[hotswap] replan failed: {failures[0]}")
        return
    if not args.assert_swap_identity:
        return
    # 1-device references, as in the serve-engine tests: the request
    # alone through greedy_generate on its generation's weights (plans
    # re-attached without the mesh — bit-identical by the mesh contract)
    ps = args.page_size
    max_len = -(-(args.prompt_len + args.gen) // ps) * ps
    ref_params = {g: model.attach_device_plans(raw)
                  for g, raw in gen_raw.items() if raw is not None}
    gens_seen = sorted({r.gen for r in eng.finished})
    bad = 0
    for r in eng.finished:
        if r.gen not in ref_params:
            continue
        batch = {"tokens": jnp.asarray([list(r.prompt)], jnp.int32)}
        want = np.asarray(greedy_generate(
            model, ref_params[r.gen], batch, max_len=max_len,
            n_steps=r.max_new_tokens))[0]
        got = np.asarray(r.tokens)
        if got.shape != want.shape or not np.array_equal(got, want):
            bad += 1
            print(f"[hotswap] MISMATCH req {r.rid} (gen {r.gen}): "
                  f"{got} != {want}")
    if bad or s["generation"] < 1:
        raise SystemExit(
            f"[hotswap] identity check FAILED: {bad} mismatching "
            f"request(s), final generation {s['generation']}")
    print(f"[hotswap] identity OK: {len(eng.finished)} request(s) across "
          f"generations {gens_seen} each bit-match the one-shot path on "
          f"their own weights")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--w-bits", type=int, default=4, choices=(4, 8))
    ap.add_argument("--backend", default=None, choices=list_backends(),
                    help="integer-GEMM execution backend for PTQ linears "
                    "(registry: repro.core.backend)")
    ap.add_argument("--path", default=None, choices=list_backends(),
                    help="DEPRECATED alias for --backend")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="AXIS=N[,AXIS=N]",
                    help="serve on a device mesh, e.g. 'data=4' — batch "
                    "sharded P('data') through prefill+decode, DevicePlans "
                    "attached on the mesh (CPU: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--fp", action="store_true",
                    help="serve unquantized (baseline comparison)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching through the paged-KV serve "
                    "engine: staggered request arrivals, packed decode "
                    "slots, prefix-trie page sharing")
    ap.add_argument("--requests", type=int, default=4,
                    help="(--continuous) number of requests to submit")
    ap.add_argument("--arrive-every", type=int, default=2,
                    help="(--continuous) host steps between arrivals")
    ap.add_argument("--page-size", type=int, default=8,
                    help="(--continuous) tokens per KV page")
    ap.add_argument("--slots", type=int, default=2,
                    help="(--continuous) packed decode batch slots")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="(--continuous) decode attention through the "
                    "Pallas live-page kernel (kernels/paged_attention) "
                    "instead of the full-extent gather oracle")
    ap.add_argument("--no-bucket-prefill", action="store_true",
                    help="(--continuous) disable bucketed batched prefill "
                    "(revert to per-request batch-1 prefills)")
    ap.add_argument("--lint", action="store_true",
                    help="tracelint preflight: before serving, lint the "
                    "selected backend's serving programs (prefill / "
                    "donated decode / paged decode / paged-attention "
                    "kernel / bucketed prefill / forest) under the "
                    "selected mesh and refuse to serve on any error "
                    "finding (rule catalog: docs/ANALYSIS.md)")
    ap.add_argument("--no-precompile", action="store_true",
                    help="skip the offline plan warmup (planned backends "
                    "only; plans then build lazily on first forward per "
                    "weight)")
    ap.add_argument("--bundle-dir", default=None, metavar="DIR",
                    help="plan-bundle directory for --role (docs/FLEET.md)")
    ap.add_argument("--role", default=None, choices=("planner", "server"),
                    help="planner: plan once + write bundles to "
                    "--bundle-dir and exit; server: attach plans from "
                    "--bundle-dir instead of planning (zero plan builds, "
                    "fingerprint-checked)")
    ap.add_argument("--watch-weights", default=None, metavar="DIR",
                    help="(--continuous) hot-swap drill: watch DIR for "
                    "new weight checkpoints, re-plan off-thread and swap "
                    "at a step boundary; the launcher writes the new "
                    "checkpoint itself after --swap-after host steps")
    ap.add_argument("--swap-after", type=int, default=3,
                    help="(--watch-weights) host steps before the new "
                    "weights checkpoint is written")
    ap.add_argument("--swap-seed", type=int, default=1234,
                    help="(--watch-weights) PRNG seed for the new "
                    "weights (re-init; any seed != 0 is a real update)")
    ap.add_argument("--assert-swap-identity", action="store_true",
                    help="(--watch-weights) exit non-zero unless every "
                    "finished request bit-matches the one-shot path on "
                    "its own generation's weights")
    args = ap.parse_args()
    enable_compile_cache()
    if args.role is not None and not args.bundle_dir:
        ap.error(f"--role {args.role} needs --bundle-dir")
    if args.watch_weights and not args.continuous:
        ap.error("--watch-weights needs --continuous (the hot-swap "
                 "protocol lives on the serve engine)")
    if args.role is not None and args.fp:
        ap.error("plan bundles carry quantized-weight plans; drop --fp")

    name = args.backend or "int_dot"
    if args.path is not None:
        warnings.warn("--path is deprecated; use --backend",
                      DeprecationWarning)
        name = args.path if args.backend is None else name
    backend = get_backend(name)

    mesh = make_serve_mesh(args.mesh) if args.mesh else None

    if args.lint:
        # preflight on the reduced arch (same programs, small trace): the
        # invariants are structural, so a violation there is a violation
        # at full size too — and the gate stays cheap enough to be on.
        from repro.analysis.programs import lint_backend
        t0 = time.time()
        _, findings = lint_backend(name, mesh=mesh, arch=args.arch,
                                   batch=args.batch,
                                   w_bits=args.w_bits)
        # plan-IR half of the preflight: the same verifier that gates
        # cache publish / bundle load / swap staging, run proactively
        from repro.analysis.planlint import lint_plans
        _, pfindings = lint_plans([name], mesh=mesh)
        findings = list(findings) + list(pfindings)
        errors = [f for f in findings if f.severity == "error"]
        for f in findings:
            print(f"[tracelint] {f.format()}")
        print(f"[tracelint] preflight {name}: {len(findings)} finding(s) "
              f"({time.time() - t0:.1f}s)")
        if errors:
            ap.error(f"tracelint preflight failed with {len(errors)} "
                     f"error finding(s); serve refused (run python -m "
                     f"repro.analysis.lint --backend {name} to inspect)")

    base = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = base if args.fp else serve_config(base, w_bits=args.w_bits,
                                            backend=name)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    raw_params = params

    planned = not args.fp and backend.needs_plan
    device_path = planned and backend.device_resident

    if args.role == "planner":
        from repro.fleet import write_bundles
        try:
            manifest = write_bundles(params, cfg.quant, args.bundle_dir,
                                     backend=name)
        except ValueError as e:
            ap.error(str(e))
        print(f"[planner] {args.bundle_dir}: {manifest['n_files']} bundle "
              f"file(s) over {manifest['n_layers']} layer(s), backend="
              f"{manifest['backend']}, weights="
              f"{manifest['weights_fingerprint'][:12]} "
              f"({manifest['plan_wall_s']:.2f}s plan+compile)")
        return

    plan_stats, t_plan, t_attach = {}, 0.0, 0.0
    if planned:
        from repro.core import plancache
        cache = plancache.default_cache()
        cache.reset_stats()
    if args.role == "server":
        if not device_path:
            ap.error(f"--role server attaches device plan bundles; "
                     f"backend '{name}' does not execute from them")
        from repro.core.engine import BundleMismatchError
        from repro.fleet import read_manifest, load_bundles
        t0 = time.time()
        try:
            params = load_bundles(params, cfg.quant, args.bundle_dir,
                                  mesh=mesh)
        except (FileNotFoundError, BundleMismatchError) as e:
            raise SystemExit(f"[server] bundle refused: {e}")
        t_attach = time.time() - t0
        s = cache.stats()
        print(f"[server] attached {read_manifest(args.bundle_dir)['n_files']} "
              f"bundle(s) from {args.bundle_dir} in {t_attach:.2f}s | "
              f"plan builds on this cell: {s['misses']}")
        if s["misses"]:
            raise SystemExit("[server] bundle attach built plans locally "
                             "— the planner artifact is incomplete")
    elif planned:
        if not args.no_precompile:
            t0 = time.time()
            plan_stats = model.precompile_plans(params)
            t_plan = time.time() - t0
        if device_path:
            # device-resident backends need plans as traced data inside the
            # block scan; attach builds any still-missing plan through the
            # same cache. With a mesh the plan leaves are placed on it —
            # the backend's plan_specs hook decides the layout (built-ins
            # replicate: every device runs every layer on its batch shard).
            t0 = time.time()
            params = model.attach_device_plans(params, mesh=mesh)
            t_attach = time.time() - t0

    if args.continuous:
        reason = model.supports_paged()
        if reason is not None:
            ap.error(f"--continuous needs the paged serve path: {reason}")
        _serve_continuous(model, params, cfg, args, mesh, name,
                          raw_params=raw_params)
        if planned:
            s = cache.stats()
            print(f"[plan cache] offline plan-build {t_plan:.2f}s | "
                  f"misses={s['misses']} hits={s['hits']}")
        return

    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab, jnp.int32)}
    if cfg.n_context_tokens or cfg.is_encdec:
        batch["context"] = jax.random.normal(
            key, (args.batch, cfg.n_context_tokens, cfg.d_model),
            jnp.float32) * 0.02

    max_len = args.prompt_len + args.gen + 8
    t0 = time.time()
    # n_steps is the number of generated tokens (prefill argmax + gen-1
    # decode steps — the explicit greedy_generate contract)
    toks = greedy_generate(model, params, batch, max_len=max_len,
                           n_steps=args.gen, mesh=mesh)
    dt = time.time() - t0
    mode = "fp" if args.fp else f"W{args.w_bits}A8+KV8/{name}"
    print(f"[{cfg.name} | {mode}] generated {args.batch}x{args.gen} tokens "
          f"in {dt:.2f}s")
    print(device_line())
    if mesh is not None:
        print(mesh_decode_report(mesh, args.batch, args.gen, dt))
    if planned:
        s = cache.stats()
        attach = (f" + device-plan attach {t_attach:.2f}s"
                  if device_path else "")
        decode = ("pure-JAX, zero host callbacks" if device_path
                  else "run-only")
        print(f"[plan cache] offline plan-build {t_plan:.2f}s "
              f"({plan_stats.get('plans', 0)} plans over "
              f"{plan_stats.get('layers', 0)} stacked layer weights)"
              f"{attach} | decode {dt:.2f}s {decode}")
        print(f"[plan cache] misses={s['misses']} hits={s['hits']} "
              f"evictions={s['evictions']} size={s['size']}")
        for bname, bs in sorted(s["backends"].items()):
            print(f"[plan cache]   {bname}: misses={bs['misses']} "
                  f"hits={bs['hits']}")
        if s["misses"] != plan_stats.get("built", s["misses"]):
            print("[plan cache] WARNING: plans were built during decode — "
                  "re-planning leaked back into the hot path")
    print(np.asarray(toks))


if __name__ == "__main__":
    main()
