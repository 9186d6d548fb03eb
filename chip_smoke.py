#!/usr/bin/env python3
"""Chip smoke: serve full-width smollm-135m through ``ServeEngine`` on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data=4 mesh engine vs one device

The model is built the way ``python -m repro.launch.serve`` builds it
(``serve_config(get_config("smollm-135m"), w_bits=4, backend="int_dot")``:
W4A8 linears, int8 attention, int8 KV cache) with weights drawn from a
seed. Sixteen requests, half of them sharing a 128-token prefix, with
prompts of 64-256 tokens and 32 new tokens each, are served to completion
through 8 decode slots over 16-token pages.

The one-chip run fails unless every request returns exactly its new
tokens, all inside the vocabulary; the decode program was traced once;
and engine logits are finite and agree with the dense ``Model.prefill``
and ``Model.decode_step`` on each request alone, at the last prompt
position and 31 teacher-forced decode positions: request 0 through the
engine alone within ``ALONE_TOL`` of the largest reference logit, and
requests 0-7 batched through the bucketed prefill and packed decode
within ``BATCHED_TOL``. It also reports how many requests are
token-identical to per-request ``greedy_generate``; that is not
required.

``--chips 4`` runs only the data-parallel path: the same requests through
``ServeEngine(mesh=make_serve_mesh("data=4"))`` and through an engine on
one device, compared token for token (reported) and on the logits of
requests 0-3 (within ``BATCHED_TOL``: each device holds one row).

Everything runs in this one process. Without a TPU the script exits
non-zero before building anything. The last line of a passing run is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
SEED = 0
SLOTS, PAGE, MAX_LEN = 8, 16, 2048
N_REQUESTS, GEN, SHARED_PREFIX = 16, 32, 128
# Logits are compared as max |diff| over the largest |reference logit| at
# each position. One request alone runs the same arithmetic through the
# engine's programs and the dense ones (bf16 activations, int8 at the
# same points, batch 1), so they may differ only where XLA fuses the two
# programs differently: a bf16 step (2^-8) or two. On the v5e they agree
# bit for bit.
ALONE_TOL = 2.0 ** -7
# A batch compiles with other tiling and fusion than one request alone,
# and XLA may keep a bf16 intermediate in f32 in one program and round it
# in the other. On the v5e the 8 requests batched sit 1.1% off the same
# requests alone at the last prompt position and 6-8.6% at the decode
# positions (0.8% for 7 of 8 with --xla_allow_excess_precision=false).
# What other rows hold moves a row by exactly 0. A wrong page, position
# or row gives 56% or more.
BATCHED_TOL = 2.0 ** -2
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling inside the
    ``with`` block, and how many programs came from the persistent cache."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _duration(self, event, duration_secs, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)
        mon.unregister_event_listener(self._event)


def build(base_cfg):
    """The serving model and its seeded weights."""
    import jax

    from repro.launch.specs import serve_config
    from repro.models.model import Model
    model = Model(serve_config(base_cfg, w_bits=4, backend="int_dot"))
    return model, model.init(jax.random.PRNGKey(SEED))


def make_prompts(vocab: int) -> list[list[int]]:
    """Even requests: a shared 128-token prefix plus a 64- or 128-token
    turn of their own. Odd requests: 64-256 tokens shared with nobody."""
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(0, vocab, SHARED_PREFIX).tolist()
    prompts = []
    for i in range(N_REQUESTS):
        if i % 2 == 0:
            turn = (64, 128)[(i // 2) % 2]
            prompts.append(prefix + rng.integers(0, vocab, turn).tolist())
        else:
            n = (64, 128, 192, 256)[(i // 2) % 4]
            prompts.append(rng.integers(0, vocab, n).tolist())
    return prompts


def serve(model, params, prompts, mesh=None):
    """Serve every prompt to completion; (engine, {rid: tokens}, wall s)."""
    from repro.serve import ServeEngine
    eng = ServeEngine(model, params, n_slots=SLOTS, max_len=MAX_LEN,
                      page_size=PAGE, mesh=mesh)
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p, GEN)
    eng.run()
    wall = time.perf_counter() - t0
    return eng, {r.rid: list(r.tokens) for r in eng.finished}, wall


def check_tokens(out, n_requests: int, vocab: int) -> None:
    if sorted(out) != list(range(n_requests)):
        raise SmokeFailure(f"finished requests {sorted(out)}, expected "
                           f"0..{n_requests - 1}")
    for rid, toks in out.items():
        if len(toks) != GEN:
            raise SmokeFailure(f"request {rid}: {len(toks)} tokens, "
                               f"expected {GEN}")
        if not all(0 <= t < vocab for t in toks):
            raise SmokeFailure(f"request {rid}: token outside [0, {vocab})")


def engine_logits(model, params, prompts, forced, mesh=None):
    """Logits (rows, 1 + n_forced, vocab) from the engine's programs,
    teacher-forced: the bucketed prefill's last position (rows padded to
    the longest prompt, no shared prefix, a fresh pool), then one packed
    decode per column of ``forced`` (rows, n_forced), which feeds row r
    token ``forced[r, i]`` at step i. With ``mesh`` both run under it as
    the ambient mesh, and the model's ``batch`` sharding constraints split
    the rows over its devices."""
    import contextlib

    import jax
    import jax.numpy as jnp
    rows, lb = len(prompts), max(len(p) for p in prompts)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    tokens, wp, wo, wpos = (np.zeros((rows, lb), np.int32) for _ in range(4))
    table = np.zeros((rows, MAX_LEN // PAGE), np.int32)
    n_pages = 1                                     # page 0 is the null page
    for r, p in enumerate(prompts):
        own = -(-(len(p) + forced.shape[1]) // PAGE)
        table[r, :own] = np.arange(n_pages, n_pages + own)
        n_pages += own
        pos = np.arange(len(p))
        tokens[r, :len(p)] = p
        wp[r, :len(p)] = table[r, pos // PAGE]
        wo[r, :len(p)] = pos % PAGE
        wpos[r, :len(p)] = pos
    prefill = jax.jit(model.prefill_paged_batched, donate_argnums=(2,))
    decode = jax.jit(model.decode_step_paged, donate_argnums=(1,))
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        logits, pool = prefill(
            params, jnp.asarray(tokens), model.init_page_pool(n_pages, PAGE),
            prefix_page_ids=jnp.zeros((rows, 0), jnp.int32),
            prefix_lens=jnp.zeros((rows,), jnp.int32),
            suffix_lens=jnp.asarray(lens), write_page_ids=jnp.asarray(wp),
            write_offs=jnp.asarray(wo), write_pos=jnp.asarray(wpos))
        out = [logits[:, -1]]
        for i in range(forced.shape[1]):
            logits, pool = decode(params, pool,
                                  jnp.asarray(forced[:, i:i + 1]),
                                  jnp.asarray(table), jnp.asarray(lens + i))
            out.append(logits[:, -1])
    return np.asarray(jnp.stack(out, axis=1), np.float32)


def dense_logits(model, params, prompts, forced):
    """Logits (rows, 1 + n_forced, vocab) of each prompt alone, from the
    dense ``Model.prefill`` and then ``Model.decode_step``, teacher-forced
    as in ``engine_logits``: the one-shot path the engine replaces."""
    import jax
    import jax.numpy as jnp
    prefill = jax.jit(functools.partial(model.prefill, max_len=MAX_LEN))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    rows = []
    for prompt, toks in zip(prompts, forced):
        logits, caches = prefill(
            params, {"tokens": jnp.asarray([prompt], jnp.int32)})
        out = [logits[0, -1]]
        for i, tok in enumerate(toks):
            logits, caches = decode(params, caches,
                                    jnp.asarray([[tok]], jnp.int32),
                                    jnp.int32(len(prompt) + i))
            out.append(logits[0, -1])
        rows.append(jnp.stack(out))
    return np.asarray(jnp.stack(rows), np.float32)


def check_logits(what: str, got, want, tol: float) -> None:
    """``got`` finite, and at every position within ``tol`` of the
    largest |want| there; the last axis is the vocabulary."""
    if not np.isfinite(got).all():
        raise SmokeFailure(f"{what}: logits are not finite")
    err = np.abs(got - want).max(-1)
    scale = np.abs(want).max(-1)
    ratio = float((err / scale).max())
    same = int((got.argmax(-1) == want.argmax(-1)).sum())
    print(f"[logits] {what}: max |diff| = {float(err.max()):.6g}, "
          f"max |diff| / max |ref| = {ratio:.6g} (limit {tol:.6g}) "
          f"over {err.size} positions; argmax equal at {same}/{err.size}")
    if not ratio <= tol:
        raise SmokeFailure(f"{what}: logits differ by {ratio:.6g} of the "
                           f"largest reference logit > {tol:.6g}")


def first_divergence(got: dict, want: dict) -> dict:
    """{rid: index of the first differing token} for differing requests."""
    return {rid: next(i for i, (a, b) in enumerate(zip(got[rid], want[rid]))
                      if a != b)
            for rid in want if got[rid] != want[rid]}


def greedy_reference(model, params, prompts) -> dict:
    """Each request alone through ``greedy_generate`` with the engine's
    ``max_len``: the CPU tests' bit-identity reference."""
    import jax.numpy as jnp

    from repro.train.serve_step import greedy_generate
    return {rid: np.asarray(greedy_generate(
        model, params, {"tokens": jnp.asarray([p], jnp.int32)},
        max_len=MAX_LEN, n_steps=GEN))[0].tolist()
        for rid, p in enumerate(prompts)}


def one_chip(model, params) -> None:
    vocab = model.cfg.vocab
    prompts = make_prompts(vocab)
    with CompileClock() as clock:
        eng, out, wall = serve(model, params, prompts)
    s = eng.stats()
    n_tokens = sum(len(t) for t in out.values())
    print(f"[serve] {len(out)} requests, {n_tokens} tokens in {wall:.3f} s "
          f"wall (compile included) | compile {clock.seconds:.3f} s, "
          f"persistent-cache hits {clock.cache_hits}")
    print(f"[serve] prefix hits {s['prefix_hits']}, pages shared "
          f"{s['pages_shared']}, batched prefills "
          f"{s['prefill_batched_calls']}, decode steps {s['decode_steps']}, "
          f"jit traces {eng.jit_traces}")
    check_tokens(out, len(prompts), vocab)
    if eng.jit_traces["decode"] != 1:
        raise SmokeFailure(f"decode traced {eng.jit_traces['decode']} "
                           f"times, expected once")
    rows = prompts[:SLOTS]
    forced = np.asarray([out[rid][:-1] for rid in range(SLOTS)], np.int32)
    want = dense_logits(model, params, rows, forced)
    check_logits(f"request 0 alone, engine prefill + {GEN - 1} decode "
                 f"steps vs dense prefill + decode_step",
                 engine_logits(model, params, rows[:1], forced[:1]),
                 want[:1], ALONE_TOL)
    check_logits(f"requests 0-{SLOTS - 1} batched through the engine vs "
                 f"each alone through the dense path",
                 engine_logits(model, params, rows, forced), want,
                 BATCHED_TOL)
    diverged = first_divergence(out, greedy_reference(model, params,
                                                      prompts))
    print(f"[identity] {len(prompts) - len(diverged)}/{len(prompts)} "
          f"requests token-identical to per-request greedy_generate "
          f"(reported, not required); first differing token by request: "
          f"{diverged}")


def four_chips(model, params) -> None:
    import warnings

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import ShardingDropWarning
    from repro.launch.mesh import make_serve_mesh
    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found "
                           f"{len(jax.devices())}")
    vocab = model.cfg.vocab
    prompts = make_prompts(vocab)
    _, single, wall1 = serve(model, params, prompts)
    mesh = make_serve_mesh("data=4")
    replicated = jax.device_put(params, NamedSharding(mesh, P()))
    with warnings.catch_warnings():
        # prefill buckets of 1 or 2 rows cannot split over 4 devices and
        # run replicated; the engine expects that
        warnings.simplefilter("ignore", ShardingDropWarning)
        eng, meshed, wall4 = serve(model, replicated, prompts, mesh=mesh)
    print(f"[mesh] one device: {wall1:.3f} s wall | data=4: {wall4:.3f} s "
          f"wall (compile included in both) | mesh jit traces "
          f"{eng.jit_traces}")
    check_tokens(single, len(prompts), vocab)
    check_tokens(meshed, len(prompts), vocab)
    if eng.jit_traces["decode"] != 1:
        raise SmokeFailure(f"mesh decode traced "
                           f"{eng.jit_traces['decode']} times")
    rows = prompts[:4]
    forced = np.asarray([single[rid][:-1] for rid in range(4)], np.int32)
    check_logits(f"requests 0-3, engine prefill + {GEN - 1} decode steps, "
                 f"data=4 vs one device",
                 engine_logits(model, replicated, rows, forced, mesh=mesh),
                 engine_logits(model, params, rows, forced), BATCHED_TOL)
    diverged = first_divergence(meshed, single)
    print(f"[mesh] {len(prompts) - len(diverged)}/{len(prompts)} requests "
          f"token-identical between data=4 and one device; first differing "
          f"token by request: {diverged}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data=4 mesh comparison")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import device_line
    print(device_line())
    print(f"[compile cache] {enable_compile_cache()}")
    model, params = build(get_config(ARCH))
    try:
        if args.chips == 4:
            four_chips(model, params)
        else:
            one_chip(model, params)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
