"""TransitiveLinear — the paper's technique as a first-class linear layer.

Three operating modes:
  * ``none`` — plain dense matmul in the working dtype (FP baseline).
  * ``qat``  — fake-quantized weights (straight-through), for training the
               models that will later serve through the Transitive Array.
  * ``ptq``  — weights stored as integers + scales; activations quantized
               per-token at runtime; the integer GEMM routes through a
               **registered execution backend** (core/backend.py):
               ``int_dot`` (dense MXU int GEMM), ``lut`` / ``pallas`` (the
               doubling-LUT dataflow, jnp / Pallas kernel), ``engine``
               (host Scoreboard forest via pure_callback — the oracle),
               ``engine_jit`` / ``engine_pallas`` (the planned forest
               device-resident, zero host callbacks). Any backend
               registered via ``repro.core.backend.register_backend``
               is selectable by name — there is no string dispatch here.

All backends share the same quantization, so they agree bit-exactly on the
int32 accumulator (property-tested over ``list_backends()``).

Layers are functional: ``linear_init`` builds a params dict,
``linear_apply`` consumes it. Weight layout is (d_out, d_in) so the
reduction axis is last (TransRows slice along it).

``QuantConfig.backend`` names the registry backend; the legacy
``QuantConfig(path=...)`` spelling still resolves through the same registry
but emits a ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp

import repro.quant.quantize as Q
from repro.core.backend import EngineConfig, get_backend, list_backends

__all__ = ["QuantConfig", "linear_init", "linear_apply"]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    mode: str = "none"        # none | qat | ptq
    w_bits: int = 8
    a_bits: int = 8
    group: int = 128          # group size along d_in (exact paths / qat)
    # integer-GEMM execution backend — any repro.core.backend registry name
    backend: str = "int_dot"
    # DEPRECATED alias for ``backend``; resolves via the shim below
    path: str | None = None
    transrow_t: int = 8       # TransRow width for transitive backends

    def with_(self, **kw) -> "QuantConfig":
        return dataclasses.replace(self, **kw)

    def backend_name(self) -> str:
        """The registry backend this config serves through.

        Legacy ``path=`` strings take precedence (existing configs keep
        their meaning) but warn: the strings were ad-hoc; the registry is
        the API."""
        if self.path is not None:
            warnings.warn(
                "QuantConfig(path=...) is deprecated; use backend=... — "
                "names resolve through repro.core.backend.get_backend",
                DeprecationWarning, stacklevel=2)
            return self.path
        return self.backend


def _effective_group(cfg: QuantConfig, d_in: int) -> int:
    g = cfg.group
    if g <= 0 or d_in % g:
        return d_in               # fall back to per-channel
    return g


def linear_init(key: jax.Array, d_in: int, d_out: int,
                cfg: QuantConfig = QuantConfig(),
                dtype=jnp.bfloat16) -> dict[str, Any]:
    scale = 1.0 / (d_in ** 0.5)
    w = jax.random.normal(key, (d_out, d_in), jnp.float32) * scale
    if cfg.mode != "ptq":
        return {"w": w.astype(dtype)}
    g = _effective_group(cfg, d_in)
    qw, sg = Q.quantize_groupwise(w, cfg.w_bits, g)
    return {"qw": qw, "sg": sg.astype(jnp.float32)}


def _resolve_device_plan(params, backend, qw: jnp.ndarray,
                         ecfg: EngineConfig):
    """Resolve the DevicePlan a device-resident planned backend executes.

    Preference order: a ``"dplan"`` embedded in the params (survives jit /
    vmap / scan — the weight may be a tracer there), else a trace-time
    process-cache lookup, which needs the weight concrete. Backends that
    do not consume device plans resolve to None."""
    if not (backend.needs_plan and backend.device_resident):
        return None
    dplan = params.get("dplan")
    if dplan is not None:
        # consistency of everything checkable under trace. Weight CONTENT
        # cannot be checked here (qw may be a tracer): an embedded plan is
        # only as fresh as the last attach_device_plans — re-attach after
        # any weight update, or the old weights' GEMM comes back silently.
        # Custom backends with their own lowering layout validate inside
        # their execute(); only the standard DevicePlan schema is checked
        # here.
        from repro.core.engine import DevicePlan
        if isinstance(dplan, DevicePlan):
            sig = (dplan.bits, dplan.t, dplan.n, dplan.k, dplan.groups)
            want = (ecfg.w_bits, ecfg.t, qw.shape[-2], qw.shape[-1],
                    ecfg.groups)
            if sig != want:
                raise ValueError(
                    f"attached plan signature (bits, t, n, k, groups)="
                    f"{sig} does not match the layer's {want} — re-attach "
                    f"with the serving QuantConfig")
        return dplan
    if isinstance(qw, jax.core.Tracer):
        fallback = ", ".join(
            n for n in list_backends()
            if not (get_backend(n).needs_plan
                    and get_backend(n).device_resident))
        raise ValueError(
            f"backend '{backend.name}' is device-resident and saw a traced "
            f"weight with no attached DevicePlan. Remedy: embed plans with "
            f"plancache.attach_device_plans(params, cfg) (or "
            f"Model.attach_device_plans) before jit, or close concrete "
            f"params over the jit. Registered backends that handle traced "
            f"weights without attachment: {fallback}.")
    import numpy as np
    from repro.core import plancache
    return plancache.default_cache().get_or_build_device(
        np.asarray(qw), ecfg, backend=backend.name)


def _resolve_plan(backend, qw: jnp.ndarray, ecfg: EngineConfig, dplan):
    """Resolve the host ExecutionPlan for a ``needs_plan`` backend.

    A device plan supersedes it; a traced weight cannot be planned here
    (host backends then resolve plans themselves — the built-in engine
    looks the plan up in the process cache inside its callback)."""
    if not backend.needs_plan or dplan is not None:
        return None
    if isinstance(qw, jax.core.Tracer):
        return None
    import numpy as np
    return backend.plan(np.asarray(qw), ecfg)


def _ptq_apply(params, x: jnp.ndarray, cfg: QuantConfig) -> jnp.ndarray:
    backend = get_backend(cfg.backend_name())
    qw, sg = params["qw"], params["sg"]
    d_out, d_in = qw.shape
    if d_in % sg.shape[-1]:
        # a floor-divided group size would reshape into the wrong groups
        # and silently mis-scale every output channel
        raise ValueError(
            f"grouped PTQ layer mis-shaped: weight ({d_out}, {d_in}) "
            f"carries {sg.shape[-1]} scale groups, but d_in={d_in} is not "
            f"divisible by the group count — requantize with a group size "
            f"that divides d_in")
    g = d_in // sg.shape[-1]
    qx, sx = Q.quantize_per_token(x, cfg.a_bits)
    if sg.shape[-1] == 1:
        # per-channel: one dense int GEMM + epilogue scale
        ecfg = EngineConfig.from_quant(cfg, groups=1)
        dplan = _resolve_device_plan(params, backend, qw, ecfg)
        plan = _resolve_plan(backend, qw, ecfg, dplan)
        y32 = backend.execute(qx, qw, plan, dplan, ecfg)
        y = y32.astype(jnp.float32) * sx * sg[:, 0]
    else:
        # group-wise: per-group int partials rescaled in the epilogue —
        # the VPU "integer scale factor per 128/T tile" of Sec. 4.5.
        n_groups = d_in // g
        if not backend.supports_groups:
            raise ValueError(
                f"backend '{backend.name}' does not support group-wise "
                f"quantization (supports_groups=False); use group=0 "
                f"(per-channel) or a grouped backend")
        ecfg = EngineConfig.from_quant(cfg, groups=n_groups)
        dplan = _resolve_device_plan(params, backend, qw, ecfg)
        plan = _resolve_plan(backend, qw, ecfg, dplan)   # from 2-D qw
        xg = qx.reshape(qx.shape[:-1] + (n_groups, g))
        wg = qw.reshape(d_out, n_groups, g)
        part = backend.execute(xg, wg, plan, dplan, ecfg)   # (..., G, N)
        y = jnp.einsum("...gn,ng->...n", part.astype(jnp.float32), sg) * sx
    return y.astype(x.dtype)


@jax.named_scope("linear")
def linear_apply(params: dict[str, Any], x: jnp.ndarray,
                 cfg: QuantConfig = QuantConfig()) -> jnp.ndarray:
    """y = x @ W^T under the configured quantization mode."""
    if cfg.mode == "ptq":
        return _ptq_apply(params, x, cfg)
    w = params["w"]
    if cfg.mode == "qat":
        g = _effective_group(cfg, w.shape[-1])
        w = Q.fake_quant(w, cfg.w_bits, g)
    return jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())))
