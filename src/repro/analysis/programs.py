"""Build the lintable serving programs for one registered backend.

For a backend name from ``repro.core.backend.list_backends()`` this
module constructs the same programs the serve path runs — prefill, the
donated decode step, the paged (continuous-batching) decode step, and the
backend's forest execution — as :class:`~repro.analysis.rules.LintProgram`
objects: traced jaxprs, the decode steps' lowered StableHLO (donation is
only visible there), and, under a mesh, the live KV cache arrays a real
prefill produced (shardings are only visible there).

Program construction is capability-driven off the registry, so the lint
CLI holds for every backend ``list_backends()`` ever returns: a future
``engine_tpu`` gets the same program set the day it registers, and its
``lint_exempt`` tags (core/backend.py) opt it out of exactly the rules
that do not apply to it.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.analysis.rules import Finding, LintProgram, run_rules
from repro.core.backend import EngineConfig, get_backend

__all__ = ["build_programs", "lint_backend", "PROGRAM_RULES"]

# which rules guard which program (minus per-backend lint_exempt tags)
PROGRAM_RULES = {
    "prefill": ("no-host-callback", "static-shapes", "dtype-purity"),
    "decode": ("no-host-callback", "static-shapes", "dtype-purity",
               "kv-donation", "sharding-integrity"),
    "paged-decode": ("no-host-callback", "static-shapes", "dtype-purity",
                     "kv-donation"),
    # the post-hot-swap decode (PR 9): the same paged step on a SECOND
    # weight generation built off-thread by repro.fleet.build_generation
    # and pad-aligned against the first — swap must not cost the serving
    # invariants (kv-donation in particular stays finding-free)
    "paged-decode-swapped": ("no-host-callback", "static-shapes",
                             "dtype-purity", "kv-donation"),
    # the PR-8 fast paths: the Pallas live-page decode kernel and the
    # bucketed batched prefill are held to the same serving invariants as
    # the oracle paths they shadow, from day one
    "paged-attention": ("no-host-callback", "static-shapes", "dtype-purity",
                        "kv-donation"),
    "prefill-bucketed": ("no-host-callback", "static-shapes",
                         "dtype-purity"),
    "forest": ("gather-only-levels", "no-host-callback", "static-shapes"),
}


def _n_leaves(tree) -> int:
    return len(jax.tree_util.tree_leaves(tree))


def _lower_donated(fn, donate_argnums, *args) -> str:
    """Lowered StableHLO text with donation requested and unused args kept
    (pruning would shift the flat argument indices the donation rule
    checks against)."""
    return jax.jit(fn, donate_argnums=donate_argnums,
                   keep_unused=True).lower(*args).as_text()


def build_programs(backend_name: str, *, mesh=None, arch: str = "smollm-135m",
                   n_layers: int = 2, batch: int = 4, prompt_len: int = 8,
                   max_len: int = 16, page_size: int = 4,
                   w_bits: int = 4) -> list[LintProgram]:
    """The lintable program set for ``backend_name``.

    With ``mesh=`` (total size > 1) the decode program is built under the
    ambient mesh on a really-prefilled, batch-placed cache so the
    ``sharding-integrity`` rule sees live shardings; ``batch`` should
    divide the mesh's data extent or the lint will (correctly) report the
    replication drop.
    """
    from repro.configs import get_reduced
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.train.serve_step import (_jit_prefill, _place_batch,
                                        make_decode_step)

    backend = get_backend(backend_name)
    cfg = serve_config(get_reduced(arch).replace(n_layers=n_layers),
                       w_bits=w_bits, backend=backend_name)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = model.attach_device_plans(params, mesh=mesh)
    batch_d = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab,
        jnp.int32)}
    ctx = jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    n_params = _n_leaves(params)
    progs: list[LintProgram] = []

    with ctx:
        # -- prefill -------------------------------------------------------
        prefill_fn = lambda p, b: model.prefill(p, b, max_len)  # noqa: E731
        progs.append(LintProgram(
            name="prefill", backend=backend_name,
            rules=PROGRAM_RULES["prefill"],
            jaxpr=jax.make_jaxpr(prefill_fn)(params, batch_d)))

        # -- decode (donated; under a mesh: on live prefilled caches) ------
        if mesh is not None:
            placed = _place_batch(batch_d, mesh)
            _, caches = _jit_prefill(model, max_len, mesh)(params, placed)
            arrays = {"kv-cache": caches}
        else:
            caches, arrays = model.init_cache(batch, max_len), None
        tok = jnp.zeros((batch, 1), jnp.int32)
        step = jnp.int32(prompt_len)
        decode_fn = make_decode_step(model)
        progs.append(LintProgram(
            name="decode", backend=backend_name,
            rules=PROGRAM_RULES["decode"],
            jaxpr=jax.make_jaxpr(decode_fn)(params, caches, tok, step),
            lowered_text=_lower_donated(decode_fn, (1,), params, caches,
                                        tok, step),
            donate_expect={"kv-cache": (n_params,
                                        n_params + _n_leaves(caches))},
            mesh=mesh, arrays=arrays))

        # -- paged decode (the continuous-batching step) -------------------
        if model.supports_paged() is None:
            pages_per_slot = max_len // page_size
            pool = model.init_page_pool(batch * pages_per_slot + 1,
                                        page_size)
            page_idx = jnp.zeros((batch, pages_per_slot), jnp.int32)
            steps = jnp.zeros((batch,), jnp.int32)
            progs.append(LintProgram(
                name="paged-decode", backend=backend_name,
                rules=PROGRAM_RULES["paged-decode"],
                jaxpr=jax.make_jaxpr(model.decode_step_paged)(
                    params, pool, tok, page_idx, steps),
                lowered_text=_lower_donated(
                    model.decode_step_paged, (1,), params, pool, tok,
                    page_idx, steps),
                donate_expect={"kv-page-pool":
                               (n_params, n_params + _n_leaves(pool))}))

            # -- paged decode after a hot swap (second weight generation) --
            from repro.fleet import build_generation
            gen = build_generation(
                model, model.init(jax.random.PRNGKey(2)), ref=params,
                gen=1, mesh=mesh)
            n_swapped = _n_leaves(gen.params)
            progs.append(LintProgram(
                name="paged-decode-swapped", backend=backend_name,
                rules=PROGRAM_RULES["paged-decode-swapped"],
                jaxpr=jax.make_jaxpr(model.decode_step_paged)(
                    gen.params, pool, tok, page_idx, steps),
                lowered_text=_lower_donated(
                    model.decode_step_paged, (1,), gen.params, pool, tok,
                    page_idx, steps),
                donate_expect={"kv-page-pool":
                               (n_swapped, n_swapped + _n_leaves(pool))}))

            # -- paged decode through the Pallas live-page kernel ----------
            kernel_fn = lambda p, pl, t, pi, st: \
                model.decode_step_paged(p, pl, t, pi, st,
                                        kernel=True)  # noqa: E731
            progs.append(LintProgram(
                name="paged-attention", backend=backend_name,
                rules=PROGRAM_RULES["paged-attention"],
                jaxpr=jax.make_jaxpr(kernel_fn)(
                    params, pool, tok, page_idx, steps),
                lowered_text=_lower_donated(
                    kernel_fn, (1,), params, pool, tok, page_idx, steps),
                donate_expect={"kv-page-pool":
                               (n_params, n_params + _n_leaves(pool))}))

            # -- bucketed batched prefill (one padded bucket shape) --------
            lb = max(page_size, 8)
            b_tokens = jnp.zeros((batch, lb), jnp.int32)
            b_prefix = jnp.zeros((batch, 0), jnp.int32)
            b_plens = jnp.zeros((batch,), jnp.int32)
            b_slens = jnp.full((batch,), lb, jnp.int32)
            b_wp = jnp.zeros((batch, lb), jnp.int32)
            b_wo = jnp.zeros((batch, lb), jnp.int32)
            b_wpos = jnp.zeros((batch, lb), jnp.int32)
            bucketed_fn = lambda p, t, pl, *ix: \
                model.prefill_paged_batched(
                    p, t, pl, prefix_page_ids=ix[0], prefix_lens=ix[1],
                    suffix_lens=ix[2], write_page_ids=ix[3],
                    write_offs=ix[4], write_pos=ix[5])  # noqa: E731
            progs.append(LintProgram(
                name="prefill-bucketed", backend=backend_name,
                rules=PROGRAM_RULES["prefill-bucketed"],
                jaxpr=jax.make_jaxpr(bucketed_fn)(
                    params, b_tokens, pool, b_prefix, b_plens, b_slens,
                    b_wp, b_wo, b_wpos)))

        # -- forest (the DevicePlan level loops, per device backend) -------
        if backend.needs_plan and backend.device_resident:
            import numpy as np
            rng = np.random.default_rng(0)
            w = rng.integers(-8, 8, size=(5, 32))
            ecfg = EngineConfig(w_bits=4, t=8, groups=1)
            plan = backend.plan(w, ecfg)
            dplan = backend.compile(plan)
            qw = jnp.asarray(w, jnp.int8)
            x = jnp.asarray(rng.integers(-128, 128, size=(3, 32)),
                            jnp.int8)
            progs.append(LintProgram(
                name="forest", backend=backend_name,
                rules=PROGRAM_RULES["forest"],
                jaxpr=jax.make_jaxpr(
                    lambda xx: backend.execute(xx, qw, plan, dplan,
                                               ecfg))(x)))
    return progs


def lint_backend(backend_name: str, *, mesh=None,
                 only: tuple[str, ...] | None = None,
                 **build_kw) -> tuple[list[LintProgram], list[Finding]]:
    """Build and lint one backend's program set.

    Returns (programs, findings); the backend's ``lint_exempt`` tags are
    honored, ``only`` restricts to a rule subset (CLI ``--rules``).
    """
    backend = get_backend(backend_name)
    progs = build_programs(backend_name, mesh=mesh, **build_kw)
    findings: list[Finding] = []
    exempt = frozenset(getattr(backend, "lint_exempt", ()))
    for prog in progs:
        findings.extend(run_rules(prog, exempt=exempt, only=only))
    return progs, findings
