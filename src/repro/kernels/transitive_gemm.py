"""Pallas TPU kernel: transitive-reuse (multiplication-free) quantized GEMM.

The faithful TPU mapping of the paper's dataflow (DESIGN.md §2): per T-wide
k-subtile we build the *complete* subset-sum LUT by doubling — every Hasse
node's prefix is its pattern with the lowest set bit cleared, so every
reuse step has distance 1 and the schedule is data-independent. Weight
TransRows (packed outside the kernel) gather their subset sum from the LUT
and shift-accumulate across bit planes with 2's-complement signs.

Beyond-paper optimisation: **split-LUT** — for T=8 we keep two 4-bit LUTs
(hi/lo nibble) instead of one 256-entry LUT: 30 build-adds instead of 255
and a 32x smaller VMEM table, at +1 add per gather (hierarchical transitive
reuse; a DSE point the paper did not explore).

VMEM budget per grid step (defaults bm=128, bn=64, bk=256, T=8, S=8):
  x block   128x256 i8           = 32 KiB
  rows      64*8 x 32 i32        = 64 KiB
  LUT       2 x (128x16) i32     = 16 KiB
  out block 128x64 i32           = 32 KiB            → well under 16 MiB VMEM.
MXU note: the gather is VPU-side; on MXU silicon the one-hot formulation of
a gather costs >= the dense int8 dot, so this kernel is the *adder-optimal*
dataflow (ASIC-faithful), while kernels/w4a8_gemm.py is the MXU-optimal one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bitslice

__all__ = ["transitive_gemm_pallas"]


def _lut4(xt: jnp.ndarray) -> jnp.ndarray:
    """(bm, 4) int32 -> (bm, 16) subset sums via 4 doubling steps."""
    lut = jnp.zeros(xt.shape[:-1] + (1,), jnp.int32)
    for b in range(4):
        lut = jnp.concatenate([lut, lut + xt[:, b:b + 1]], axis=-1)
    return lut


def _lut_full(xt: jnp.ndarray, t: int) -> jnp.ndarray:
    lut = jnp.zeros(xt.shape[:-1] + (1,), jnp.int32)
    for b in range(t):
        lut = jnp.concatenate([lut, lut + xt[:, b:b + 1]], axis=-1)
    return lut


def _kernel(x_ref, rows_ref, out_ref, *, t, w_bits, bk, split_lut):
    bm = x_ref.shape[0]
    bn = rows_ref.shape[0]
    s = w_bits
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)
    # 2's-complement plane weights as python scalars (no captured consts)
    signs = [(-1 if b == s - 1 else 1) * (1 << b) for b in range(s)]
    acc = jnp.zeros((bm, bn), jnp.int32)
    for j in range(bk // t):                              # static unroll
        xt = x[:, j * t:(j + 1) * t]
        p = rows_ref[:, :, j].reshape(bn * s)             # (bn*S,) patterns
        if split_lut and t == 8:
            lo = _lut4(xt[:, :4])
            hi = _lut4(xt[:, 4:])
            g = jnp.take(lo, p & 15, axis=1) + jnp.take(hi, p >> 4, axis=1)
        else:
            lut = _lut_full(xt, t)
            g = jnp.take(lut, p, axis=1)                  # (bm, bn*S)
        gr = g.reshape(bm, bn, s)
        for b in range(s):                                # shift-accumulate
            acc = acc + signs[b] * gr[:, :, b]
    out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("w_bits", "t", "bm", "bn", "bk",
                                             "split_lut", "interpret"))
def transitive_gemm_pallas(qx: jnp.ndarray, qw: jnp.ndarray, *,
                           w_bits: int = 8, t: int = 8,
                           bm: int = 128, bn: int = 64, bk: int = 256,
                           split_lut: bool = True,
                           interpret: bool) -> jnp.ndarray:
    """int32 [qx (M, K) i8] @ [qw (N, K) i8]^T with transitive reuse.

    M, N, K must be divisible by (bm, bn, bk); ops.py handles padding.
    """
    m, k = qx.shape
    n = qw.shape[0]
    if qw.shape[1] != k:
        raise ValueError(f"reduction mismatch: qx {qx.shape} vs qw {qw.shape}")
    if k % bk or bk % t:
        raise ValueError(f"K={k} must tile by bk={bk} and bk by T={t}")
    if m % bm or n % bn:
        raise ValueError(f"M={m}, N={n} must tile by bm={bm}, bn={bn} "
                         "(kernels/ops.py pads non-divisible shapes)")
    # Pre-pack TransRows (offline in the paper; cheap jnp here).
    planes = bitslice.bit_planes_jnp(qw.astype(jnp.int32), w_bits)
    rows = bitslice.pack_transrows_jnp(planes, t)          # (S, N, J)
    rows = jnp.moveaxis(rows, 0, 1).astype(jnp.int32)      # (N, S, J)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, t=t, w_bits=w_bits, bk=bk,
                          split_lut=split_lut),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, w_bits, bk // t), lambda i, j, kk: (j, 0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(qx, rows)
