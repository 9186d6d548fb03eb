"""First-class execution backends: a pluggable registry for the online half
of the Transitive Array.

The paper splits execution into offline TransRow packing and online
multiplication-free GEMM; this repo grew four online strategies (dense
``int_dot``, the doubling-LUT ``lut``/``pallas`` kernels, and the
Scoreboard-forest ``engine`` family). They used to be selected by string
``if/elif`` chains duplicated across quant/qlinear.py, launch/serve.py and
benchmarks/bench_kernel.py. This module replaces the strings with declared
objects:

  * :class:`TransitiveBackend` — the protocol every execution strategy
    implements: capability flags (``device_resident``, ``supports_groups``,
    ``supports_jit``, ``needs_plan``, ``cpu_ok``) plus a uniform lifecycle
    ``plan(w, cfg) -> ExecutionPlan | None`` (offline, weight-only),
    ``compile(plan, mesh=None, specs=None) -> DevicePlan | None`` (lowering
    + optional sharding), ``execute(x, w, plan, dplan, cfg) -> int32``
    (the online hot path).
  * :class:`EngineConfig` — the engine-side knobs ``(w_bits, t, groups)``
    as one frozen dataclass instead of loose kwargs threaded through the
    stack.
  * a process-level registry (:func:`register_backend`,
    :func:`get_backend`, :func:`list_backends`) so serving, benchmarks and
    tests enumerate backends instead of hardcoding choice lists, and a
    custom backend drops in without touching the dispatch sites.

Two hooks the ROADMAP names next are part of the protocol rather than
bolted on: ``compile(..., mesh=, specs=)`` threads ``PartitionSpec``s onto
the (possibly stacked) :class:`~repro.core.engine.DevicePlan` leaves —
shard-ready plans for multi-device serving (:func:`shard_device_plan`) —
and the device lowering persists across processes tagged with its backend
(``ExecutionPlan.save(..., device=, backend=)`` /
``ExecutionPlan.load_bundle``).

``execute`` contract (all integer, all bit-exact with the ``int_dot``
int32 accumulator):

  * ungrouped (``cfg.groups == 1``): ``x (..., K) × w (N, K) -> (..., N)``
  * grouped   (``cfg.groups == G``): ``x (..., G, g) × w (N, G, g) ->
    (..., G, N)`` per-group partial sums (the caller rescales in the
    epilogue).

Run ``python -m repro.core.backend`` to print the registry; ``--cpu``
restricts to backends the CPU runner can satisfy (the CI serve-smoke loop
uses this).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import (DEVICE_DATA_FIELDS, DevicePlan, ExecutionPlan,
                               compile_plan, compile_plans, run_device_jit)

__all__ = ["EngineConfig", "TransitiveBackend", "register_backend",
           "unregister_backend", "get_backend", "list_backends",
           "shard_device_plan"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The engine-side execution signature as one object.

    Replaces the loose ``(w_bits, T, groups)`` kwargs that used to thread
    through qlinear -> plancache -> engine. ``groups`` is the number of
    quantization groups concatenated along K (1 = per-channel).
    """
    w_bits: int = 8
    t: int = 8                 # TransRow width
    groups: int = 1

    @classmethod
    def from_quant(cls, qcfg: Any, groups: int = 1) -> "EngineConfig":
        """Build from a ``QuantConfig``-shaped object (``w_bits`` +
        ``transrow_t`` attributes)."""
        return cls(w_bits=qcfg.w_bits, t=qcfg.transrow_t, groups=groups)

    def key(self) -> tuple[int, int, int]:
        return (int(self.w_bits), int(self.t), int(self.groups))


CAPABILITY_FLAGS = ("device_resident", "supports_groups", "supports_jit",
                    "needs_plan", "cpu_ok")


class TransitiveBackend:
    """Base class / protocol for one online execution strategy.

    Capability flags (class attributes — declare, don't imply):

    ``device_resident``
        ``execute`` is pure JAX on device data; the lowered jaxpr contains
        no host callback. Device-resident backends that also ``needs_plan``
        consume a :class:`DevicePlan` (the ``dplan`` argument).
    ``supports_groups``
        ``execute`` accepts grouped inputs (``cfg.groups > 1``).
    ``supports_jit``
        ``execute`` composes with ``jax.jit`` (host-callback backends
        qualify via ``pure_callback``).
    ``needs_plan``
        the strategy has an offline weight-only half (:meth:`plan`); serving
        should precompile through :class:`~repro.core.plancache.PlanCache`.
    ``cpu_ok``
        the CPU runner can satisfy this backend (Pallas kernels via
        interpret mode count). CI uses this to skip accelerator-only
        backends.

    ``lint_exempt`` tags which tracelint rules (repro.analysis —
    ``list_rules()`` names) do NOT apply to this backend, with a reason
    per tag in the class docstring. The lint gate runs every other rule
    against the backend's serving programs; an exemption is a declared
    capability, not an escape hatch — e.g. the host ``engine`` oracle is
    exempt from ``no-host-callback`` because being a callback is its
    contract.
    """
    name: str = ""
    device_resident: bool = False
    supports_groups: bool = True
    supports_jit: bool = True
    needs_plan: bool = False
    cpu_ok: bool = True
    lint_exempt: frozenset[str] = frozenset()

    # -- lifecycle ---------------------------------------------------------
    def plan(self, w: np.ndarray, cfg: EngineConfig) -> ExecutionPlan | None:
        """Offline half: weight-only schedule for the full 2-D (N, K)
        weight (grouped layers pass all groups concatenated along K).
        Backends without an offline half return None."""
        return None

    def compile(self, plan, mesh=None, specs=None) -> DevicePlan | None:
        """Lower ``plan`` (one :class:`ExecutionPlan`, or a sequence of
        same-signature plans -> one stacked :class:`DevicePlan`) to
        device-resident index arrays. With ``mesh=`` the leaves are placed
        with the given ``PartitionSpec``s (:func:`shard_device_plan`) —
        shard-ready plans for multi-device serving. Backends without a
        device lowering return None."""
        return None

    def plan_specs(self, mesh):
        """How this backend's DevicePlan leaves are placed on ``mesh``.

        The serve path (``plancache.attach_device_plans`` /
        ``Model.attach_device_plans``) consults this when the caller gives
        a mesh but no explicit ``specs`` — the capability-keyed default
        placement. The base default replicates (``None``): plans are small
        index arrays, and data-parallel decode needs every device to hold
        every layer's plan. A backend whose lowering is sharded (say a TPU
        forest kernel splitting output rows over ``"model"``) overrides
        this to return a single ``PartitionSpec`` or a
        ``{leaf-field: PartitionSpec}`` mapping
        (:func:`shard_device_plan`'s forms)."""
        return None

    def execute(self, x: jnp.ndarray, w: jnp.ndarray,
                plan: ExecutionPlan | None, dplan: DevicePlan | None,
                cfg: EngineConfig) -> jnp.ndarray:
        """Online half — the integer GEMM (see the module docstring for the
        shape contract). Must be bit-exact with ``int_dot``'s int32
        accumulator."""
        raise NotImplementedError

    # -- introspection -----------------------------------------------------
    def capabilities(self) -> dict[str, bool]:
        return {f: bool(getattr(self, f)) for f in CAPABILITY_FLAGS}

    def lint_profile(self) -> dict[str, bool]:
        """rule name -> applies-to-this-backend, over the tracelint rule
        registry (repro.analysis). The lint driver consults
        ``lint_exempt`` directly; this is the introspection twin of
        :meth:`capabilities` for reports and the registry CLI."""
        from repro.analysis import list_rules
        return {r: r not in self.lint_exempt for r in list_rules()}

    def __repr__(self) -> str:
        caps = ", ".join(f for f in CAPABILITY_FLAGS if getattr(self, f))
        return f"{type(self).__name__}(name={self.name!r}, {caps})"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, TransitiveBackend] = {}


def register_backend(backend: TransitiveBackend, *,
                     replace: bool = False) -> TransitiveBackend:
    """Register ``backend`` under ``backend.name``.

    Duplicate names are a loud error unless ``replace=True`` — two backends
    silently shadowing each other is exactly the failure mode string
    dispatch had. Returns the backend (decorator-friendly)."""
    name = getattr(backend, "name", "")
    if not name or not isinstance(name, str):
        raise ValueError(f"backend must declare a non-empty string name, "
                         f"got {name!r}")
    if name in _REGISTRY and not replace:
        raise ValueError(
            f"backend '{name}' is already registered "
            f"({_REGISTRY[name]!r}); pass replace=True to override")
    _REGISTRY[name] = backend
    return backend


def unregister_backend(name: str) -> TransitiveBackend:
    """Remove a backend (tests / plugin teardown). Returns the removed
    backend; KeyError (with the valid names) if absent."""
    if name not in _REGISTRY:
        raise KeyError(_unknown_msg(name))
    return _REGISTRY.pop(name)


def list_backends() -> tuple[str, ...]:
    """Registered backend names, in registration order (stable for
    parametrized tests and CLI choice lists)."""
    return tuple(_REGISTRY)


def _unknown_msg(name) -> str:
    return (f"unknown backend {name!r}; registered backends: "
            f"{', '.join(sorted(_REGISTRY))}")


def get_backend(name) -> TransitiveBackend:
    """Resolve ``name`` to a registered backend.

    Accepts a registry name, a :class:`TransitiveBackend` instance (returned
    as-is), or any object with a ``backend_name()`` method / ``backend``
    attribute (a ``QuantConfig`` works — including its deprecated ``path=``
    shim). Unknown names raise ``KeyError`` listing the valid ones."""
    if isinstance(name, TransitiveBackend):
        return name
    if not isinstance(name, str):
        resolver = getattr(name, "backend_name", None)
        if callable(resolver):
            name = resolver()
        elif isinstance(getattr(name, "backend", None), str):
            name = name.backend
    try:
        return _REGISTRY[name]
    except (KeyError, TypeError):
        raise KeyError(_unknown_msg(name)) from None


# ---------------------------------------------------------------------------
# Sharding hook: PartitionSpecs onto DevicePlan leaves
# ---------------------------------------------------------------------------

def shard_device_plan(dplan: DevicePlan, mesh, specs=None) -> DevicePlan:
    """Place every :class:`DevicePlan` leaf on ``mesh`` under ``specs``.

    ``specs`` is ``None`` (replicate everywhere — the safe default for
    plans, which are small index arrays), a single ``PartitionSpec``
    applied to every leaf (e.g. ``P("data")`` to shard the stacked
    leading axis of scan-stacked plans), or a mapping from leaf field
    name (``level_src`` ...) to spec, missing fields replicated. Leaf
    values are unchanged — only placement — so a sharded plan stays
    bit-exact with its host twin."""
    from jax.sharding import NamedSharding, PartitionSpec

    if specs is None:
        specs = PartitionSpec()
    if isinstance(specs, PartitionSpec):
        specs = {f: specs for f in DEVICE_DATA_FIELDS}
    elif isinstance(specs, Mapping):
        bad = set(specs) - set(DEVICE_DATA_FIELDS)
        if bad:
            raise ValueError(f"unknown DevicePlan leaf fields {sorted(bad)}; "
                             f"valid: {list(DEVICE_DATA_FIELDS)}")
    else:
        raise TypeError("specs must be None, a PartitionSpec, or a "
                        "{leaf-field: PartitionSpec} mapping")
    placed = {
        f: jax.device_put(
            getattr(dplan, f),
            NamedSharding(mesh, specs.get(f, PartitionSpec())))
        for f in DEVICE_DATA_FIELDS}
    return dataclasses.replace(dplan, **placed)


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

class IntDotBackend(TransitiveBackend):
    """Dense int8 ``dot_general`` (int32 accumulation) — the MXU-native
    execution; the bit-exactness reference for every other backend."""
    name = "int_dot"
    device_resident = True

    def execute(self, x, w, plan, dplan, cfg):
        if cfg.groups > 1:
            return jnp.einsum("...gi,ngi->...gn", x, w,
                              preferred_element_type=jnp.int32)
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)


class LutBackend(TransitiveBackend):
    """Pure-jnp dense doubling-LUT transitive execution (kernels/ref.py) —
    the paper's result-reuse dataflow in software, data-independent."""
    name = "lut"
    device_resident = True

    def execute(self, x, w, plan, dplan, cfg):
        from repro.kernels import ref
        if cfg.groups > 1:
            return ref.transitive_matmul_grouped_ref(x, w, cfg.w_bits, cfg.t)
        return ref.transitive_matmul_ref(x, w, cfg.w_bits, cfg.t)


class PallasLutBackend(TransitiveBackend):
    """The doubling-LUT schedule as a Pallas TPU kernel
    (kernels/transitive_gemm.py); interpret mode on CPU."""
    name = "pallas"
    device_resident = True

    def execute(self, x, w, plan, dplan, cfg):
        from repro.kernels import ops
        if cfg.groups > 1:
            return ops.transitive_gemm_grouped(x, w, w_bits=cfg.w_bits,
                                               t=cfg.t)
        return ops.transitive_gemm(x, w, w_bits=cfg.w_bits, t=cfg.t)


class EngineHostBackend(TransitiveBackend):
    """The batched multi-tile Scoreboard engine (core/engine.py) on the
    host via ``pure_callback`` — the faithful forest dataflow, kept as the
    oracle next to core/transitive_ref.py. A plan resolved at dispatch
    time (the protocol's ``plan`` argument) is executed run-only with no
    further cache traffic; with ``plan=None`` (the weight was a tracer)
    the callback resolves it from the process plan cache per call.

    ``lint_exempt``: being a ``pure_callback`` is this backend's contract
    (it exists to differential-test the device paths), so
    ``no-host-callback`` does not apply to its serving programs."""
    name = "engine"
    needs_plan = True
    lint_exempt = frozenset({"no-host-callback"})

    def plan(self, w, cfg):
        from repro.core import plancache
        return plancache.default_cache().get_or_build(
            np.asarray(w), cfg, backend=self.name)

    def _gemm(self, plan, qw2, flat, cfg):
        """flat (B, K) int64 -> the engine's (N, [G,] B) layout."""
        if plan is not None:
            from repro.core.engine import BatchedTransitiveEngine
            return BatchedTransitiveEngine(bits=plan.bits,
                                           t=plan.t).run(plan, flat.T)
        from repro.core import plancache
        return plancache.default_cache().run(qw2, flat.T, cfg,
                                             backend=self.name)

    def execute(self, x, w, plan, dplan, cfg):
        if plan is not None and (plan.bits, plan.t,
                                 plan.groups) != cfg.key():
            raise ValueError(
                f"plan signature (bits, t, groups)="
                f"{(plan.bits, plan.t, plan.groups)} does not match the "
                f"execute config {cfg.key()}")
        if cfg.groups > 1:
            n, n_groups, g = w.shape
            out = jax.ShapeDtypeStruct(x.shape[:-1] + (n,), jnp.int32)

            def host(xg_np, wg_np):
                # shape-agnostic: under vmap the callback sees extra
                # leading axes (size-1 on the unmapped weight with
                # vmap_method="expand_dims")
                qw2 = np.asarray(wg_np).reshape(wg_np.shape[-3],
                                                n_groups * g)
                flat = np.asarray(xg_np, np.int64).reshape(-1, n_groups * g)
                part = self._gemm(plan, qw2, flat, cfg)        # (N, G, M)
                return (part.transpose(2, 1, 0)
                        .reshape(xg_np.shape[:-1] + (n,)).astype(np.int32))

            return jax.pure_callback(host, out, x, w,
                                     vmap_method="expand_dims")

        out = jax.ShapeDtypeStruct(x.shape[:-1] + (w.shape[0],), jnp.int32)

        def host(qx_np, qw_np):
            qw2 = np.asarray(qw_np).reshape(qw_np.shape[-2:])
            flat = np.asarray(qx_np, np.int64).reshape(-1, qx_np.shape[-1])
            y = self._gemm(plan, qw2, flat, cfg).T
            return (y.reshape(qx_np.shape[:-1] + (qw2.shape[0],))
                    .astype(np.int32))

        return jax.pure_callback(host, out, x, w,
                                 vmap_method="expand_dims")


class EngineJitBackend(TransitiveBackend):
    """The planned forest executed device-resident (DevicePlan +
    ``run_device``): pure jnp gathers under jit, zero host callbacks."""
    name = "engine_jit"
    needs_plan = True
    device_resident = True

    def plan(self, w, cfg):
        from repro.core import plancache
        return plancache.default_cache().get_or_build(
            np.asarray(w), cfg, backend=self.name)

    def compile(self, plan, mesh=None, specs=None):
        if isinstance(plan, ExecutionPlan):
            dplan = compile_plan(plan)
        elif isinstance(plan, Sequence):
            dplan = compile_plans(list(plan))
        else:
            raise TypeError(f"plan must be an ExecutionPlan or a sequence "
                            f"of them, got {type(plan).__name__}")
        if mesh is not None:
            dplan = shard_device_plan(dplan, mesh, specs)
        return dplan

    def _forest(self, dplan, flat):
        """flat int32 (K, B) activations -> (N, B) / (N, G, B)."""
        return run_device_jit(dplan, flat)

    def execute(self, x, w, plan, dplan, cfg):
        if dplan is None:
            if plan is None:
                raise ValueError(
                    f"backend '{self.name}' is device-resident: execute "
                    f"needs a compiled DevicePlan (or an ExecutionPlan to "
                    f"lower) — compile with backend.compile(plan) or serve "
                    f"through plancache.attach_device_plans")
            dplan = self.compile(plan)
        if cfg.groups > 1:
            n_groups, g = x.shape[-2], x.shape[-1]
            flat = x.reshape(-1, n_groups * g).astype(jnp.int32).T
            y = self._forest(dplan, flat)                  # (N, G, B)
            return y.transpose(2, 1, 0).reshape(x.shape[:-1] + (dplan.n,))
        flat = x.reshape(-1, x.shape[-1]).astype(jnp.int32).T    # (K, B)
        y = self._forest(dplan, flat)                            # (N, B)
        return y.T.reshape(x.shape[:-1] + (dplan.n,))


class EnginePallasBackend(EngineJitBackend):
    """The same DevicePlan forest as a Pallas kernel
    (kernels/transitive_forest.py; interpret on CPU)."""
    name = "engine_pallas"

    def _forest(self, dplan, flat):
        from repro.kernels import transitive_forest
        return transitive_forest.transitive_forest(dplan, flat)


for _b in (IntDotBackend(), LutBackend(), PallasLutBackend(),
           EngineHostBackend(), EngineJitBackend(), EnginePallasBackend()):
    register_backend(_b)
del _b


if __name__ == "__main__":
    import argparse
    # runpy executes this file as __main__ with its own module globals;
    # consult the canonical module so the registry printed is the one
    # every import site (and any plugin registration) actually uses
    from repro.core import backend as _canonical
    ap = argparse.ArgumentParser(
        description="List registered Transitive Array execution backends")
    ap.add_argument("--cpu", action="store_true",
                    help="only names the CPU runner can satisfy, one per "
                    "line (the CI serve-smoke loop consumes this)")
    args = ap.parse_args()
    for n in _canonical.list_backends():
        b = _canonical.get_backend(n)
        if args.cpu:
            if b.cpu_ok:
                print(n)
        else:
            print(f"{n:16s} {b.capabilities()}")
