"""Logical-axis sharding rules → PartitionSpecs / constraints.

Logical axes:
  batch   → ("pod", "data")   data parallelism (pod = DCN-level DP)
  heads   → "model"           tensor parallelism over attention heads
  kv_heads→ "model"           (replicated when GQA kv count not divisible)
  ffn     → "model"           tensor parallelism over FFN inner dim
  vocab   → "model"           sharded embedding / logits
  experts → "model"           expert parallelism
  kv_seq  → "model"           sequence parallelism for decode KV caches
  seq     → "model" iff cfg.seq_shard (Megatron-SP activations)
  fsdp    → "data"            ZeRO-3-ish parameter sharding on the DP axis

``shard(x, *logical_axes)`` applies a sharding constraint only when a mesh
with the needed axis names is ambient (jit under ``with mesh:``) and the
dimension is divisible — so the same model code runs on 1 CPU device in
tests and on the 512-chip production mesh in the dry-run. Dropping an axis
for non-divisibility is legal but no longer silent: the first time a given
(logical axis, mesh extent, dim) combination replicates instead of
sharding, :func:`spec` emits a ``ShardingDropWarning`` — a serve cell that
meant to split its batch 4 ways but quietly ran 4 replicated copies is
exactly the failure mode the warning exists for.
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["RULES", "ShardingDropWarning", "spec", "shard",
           "mesh_axis_size", "ambient_mesh"]


class ShardingDropWarning(UserWarning):
    """A sharding rule's mesh axes were dropped (replicated) because the
    mesh extent does not divide the dimension."""


# (logical axis, mesh axes, dim, extent) combinations already warned about —
# spec() runs on every layer of every step, the warning must fire once
_WARNED_DROPS: set[tuple] = set()


def _warn_drop(name: str, mesh_axes: tuple[str, ...], dim: int,
               size: int) -> None:
    key = (name, mesh_axes, dim, size)
    if key in _WARNED_DROPS:
        return
    _WARNED_DROPS.add(key)
    axes = "+".join(mesh_axes)
    product = " (product of present axes)" if len(mesh_axes) > 1 else ""
    warnings.warn(
        f"sharding rule '{name}' -> mesh axes {mesh_axes} dropped: "
        f"dim {dim} is not divisible by the mesh extent {size} of "
        f"{axes}{product}; the dimension is REPLICATED on every device. "
        f"Pad the dimension or resize the mesh to actually shard it.",
        ShardingDropWarning, stacklevel=3)

RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "kv_seq": ("model",),
    "seq_sp": ("model",),
    "fsdp": ("data",),
    "none": (),
}


def ambient_mesh():
    """The ambient mesh, or None when no mesh context is active."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def mesh_axis_size(name: str) -> int:
    m = ambient_mesh()
    if m is None or name not in m.axis_names:
        return 1
    return m.shape[name]


def spec(*logical_axes: str | None, shape: tuple[int, ...] | None = None,
         mesh=None) -> P:
    """PartitionSpec from logical axis names (None → replicated dim).

    When ``shape`` is given, axes whose mesh extent does not divide the dim
    are dropped (replicated) — e.g. 8 GQA kv heads on a 16-way model axis.
    For multi-axis rules (``batch`` → ``("pod", "data")``) the *product* of
    the present axes must divide. A drop emits a ``ShardingDropWarning``
    once per (rule, extent, dim) — replication is a legal fallback, not a
    silent one. ``mesh`` defaults to the ambient mesh.
    """
    m = ambient_mesh() if mesh is None else mesh
    parts = []
    for i, name in enumerate(logical_axes):
        if name is None or name == "none":
            parts.append(None)
            continue
        mesh_axes = tuple(a for a in RULES[name]
                          if m is not None and a in m.axis_names)
        if not mesh_axes:
            parts.append(None)
            continue
        size = 1
        for a in mesh_axes:
            size *= dict(m.shape)[a]
        if shape is not None and shape[i] % size:
            if size > 1:
                _warn_drop(name, mesh_axes, shape[i], size)
            parts.append(None)
            continue
        parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    return P(*parts)


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """with_sharding_constraint under the ambient mesh; no-op without one."""
    if ambient_mesh() is None:
        return x
    assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
    s = spec(*logical_axes, shape=x.shape)
    if all(p is None for p in s):
        return x
    return jax.lax.with_sharding_constraint(x, s)


# --------------------------------------------------------------------------
# Parameter sharding rules (Megatron TP + ZeRO-3 FSDP on the data axis)
# --------------------------------------------------------------------------

# row-parallel linears: contraction (input) dim carries the TP shard
_ROW_PARALLEL = {"wo", "down", "w_out"}
# leaves sharded over experts on "model" (+ FSDP on a wide inner dim)
_EXPERT = {"w_gate", "w_up", "w_down"}


def _leaf_logical(path_keys: list[str], shape) -> tuple[str | None, ...]:
    name = None
    for k in reversed(path_keys):
        if k not in ("w", "qw", "sg"):
            name = k
            break
    ndim = len(shape)
    lead = (None,) * (ndim - 2)                    # scan-stacked axes

    if name in ("embed", "unembed"):
        return ("vocab", "fsdp")
    if name in _EXPERT and ndim >= 3:
        # (R?, E, d_in, d_out): experts on model, last dim ZeRO-3
        logical = [None] * ndim
        logical[ndim - 3] = "experts"
        logical[ndim - 1] = "fsdp"
        return tuple(logical)
    if ndim < 2:
        return (None,) * ndim                      # norms, scalars, lam
    if name == "router":
        return lead + (None, None)
    if name in _ROW_PARALLEL:
        return lead + ("fsdp", "heads")            # (out, in): in = model
    # column-parallel default: (out, in) with out on model, in on data
    return lead + ("heads", "fsdp")


def param_specs(params, fsdp: bool = True) -> object:
    """Pytree of PartitionSpecs for a params/opt-state tree.

    Layout convention: qlinear weights are (d_out, d_in) (possibly with
    leading stacked scan axes). Column-parallel weights shard d_out on
    "model"; row-parallel ({wo, down, w_out}) shard d_in on "model"; the
    other big dim takes ZeRO-3 ("data") where divisible. MoE expert stacks
    shard experts on "model" and their widest dim on "data"; scales/norms
    replicate.

    ``fsdp=False`` drops the ZeRO-3 ("data") axis — the serving layout:
    weights stay TP-resident instead of being all-gathered every step
    (EXPERIMENTS.md §Perf iteration 1).
    """
    def one(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        logical = _leaf_logical(keys, leaf.shape)
        if not fsdp:
            logical = tuple(None if ax == "fsdp" else ax for ax in logical)
        return spec(*logical, shape=leaf.shape)

    return jax.tree_util.tree_map_with_path(one, params)
