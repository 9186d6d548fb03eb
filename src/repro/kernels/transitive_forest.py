"""Pallas kernel: the level-synchronous Scoreboard forest from a DevicePlan.

Where kernels/transitive_gemm.py rebuilds the *complete* subset-sum LUT per
k-subtile (data-independent doubling), this kernel executes the paper's
actual data-dependent schedule — the gather-only per-level source maps plus
the direct-dispatch and APE shift-accumulate passes — straight from the
same :class:`~repro.core.engine.DevicePlan` index arrays the pure-jnp
``run_device`` uses. One grid step owns one block of activation columns;
the plan arrays are broadcast to every step. Each level advances the whole
psum table as ``psum[src] + x[xsrc]`` (identity lanes gather themselves
plus a pinned zero row), identical to the jnp path, so the kernel is
bit-exact with ``run_device`` and with the ``int_dot`` int32 accumulator.

Like the sibling kernels this runs in interpret mode on CPU (the container
validates semantics); the VMEM story on real silicon is the psum table
(J * 2^T, bm) int32 — e.g. K=4096, T=8, bm=64: 32 MiB, so a hardware
lowering would tile K as well and accumulate group partials across a k
grid axis. That step is deliberately left to a TPU-silicon PR.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.engine import DevicePlan

__all__ = ["transitive_forest", "transitive_forest_pallas"]


def _kernel(x_ref, src_ref, xsrc_ref, didx_ref, dxidx_ref,
            dbits_ref, gat_ref, signs_ref, out_ref, *, t, groups, n, k):
    # the schedule itself is engine.forest_body — one shared jnp body, not
    # a hand-synced copy, so kernel and run_device cannot drift apart
    from repro.core.engine import forest_body
    x = x_ref[...].astype(jnp.int32)                       # (K, bm)
    out = forest_body(x, src_ref[...], xsrc_ref[...], didx_ref[...],
                      dxidx_ref[...], dbits_ref[...], gat_ref[...],
                      signs_ref[...], t=t, groups=groups, n=n, k=k)
    out_ref[...] = out.reshape(n * groups, x.shape[1])


@functools.partial(jax.jit, static_argnames=("t", "groups", "n", "k", "bm",
                                             "interpret"))
def transitive_forest_pallas(x, level_src, level_xsrc, direct_idx,
                             direct_x_idx, direct_bits, gather_idx, signs, *,
                             t, groups, n, k, bm, interpret):
    """Raw pallas_call over the plan leaves; x (K, M) with M % bm == 0."""
    m = x.shape[1]
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    return pl.pallas_call(
        functools.partial(_kernel, t=t, groups=groups, n=n, k=k),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((k, bm), lambda i: (0, i)),
            full(level_src), full(level_xsrc),
            full(direct_idx), full(direct_x_idx), full(direct_bits),
            full(gather_idx), full(signs),
        ],
        out_specs=pl.BlockSpec((n * groups, bm), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n * groups, m), jnp.int32),
        interpret=interpret,
    )(x, level_src, level_xsrc, direct_idx, direct_x_idx,
      direct_bits, gather_idx, signs)


def transitive_forest(dplan: DevicePlan, x: jnp.ndarray, *,
                      bm: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Forest execution of ``x`` (K, M) via the Pallas kernel.

    Same contract as :func:`repro.core.engine.run_device`: int32 (N, M)
    ungrouped, (N, G, M) grouped. Pads M up to the block width and slices
    the result back.
    """
    if interpret is None:
        from repro.kernels import ops     # deferred: ops imports this module
        interpret = ops.default_interpret()
    if x.ndim != 2 or x.shape[0] != dplan.k:
        raise ValueError(f"x must be (K={dplan.k}, M), got {x.shape}")
    m = x.shape[1]
    # decode-sized inputs (M < 8, e.g. batch-1 serving) get bm = M: padding
    # them to a fixed block would run the whole forest on thrown-away
    # columns every call
    bm = bm or (128 if m >= 128 else min(8, m))
    pad = (-m) % bm
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    out = transitive_forest_pallas(
        x, dplan.level_src, dplan.level_xsrc,
        dplan.direct_idx, dplan.direct_x_idx, dplan.direct_bits,
        dplan.gather_idx, dplan.signs, t=dplan.t, groups=dplan.groups,
        n=dplan.n, k=dplan.k, bm=bm, interpret=interpret)
    out = out[:, :m].reshape(dplan.n, dplan.groups, m)
    return out[:, 0] if dplan.groups == 1 else out
