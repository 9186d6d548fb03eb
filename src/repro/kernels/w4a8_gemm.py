"""Pallas TPU kernel: fused group-dequant W4/W8 x A8 GEMM (MXU hot path).

This is the TPU-native side of the hardware adaptation (DESIGN.md §2): on
TPU the technique's *memory* win (4-bit weights → half the HBM traffic for
decode-bound GEMMs) is what reaches roofline, while the adder-reuse win is
ASIC-specific. The kernel keeps weights quantized in VMEM, runs the int8
MXU dot per quantization group, and applies the per-group scales in the
f32 epilogue — the paper's Sec. 4.5 "integer scale per 128/T tile" folded
into the matmul.

Tiling (defaults bm=128, bn=128, bk=512, group=128):
  x block 128x512 i8 = 64 KiB; w block 128x512 i8 = 64 KiB;
  sg block 128x4 f32; acc/out 128x128 f32 = 64 KiB  → VMEM-friendly,
  MXU dims all multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["w4a8_gemm_pallas"]


def _kernel(x_ref, w_ref, sg_ref, sx_ref, out_ref, *, bk, group, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for gi in range(bk // group):
        xs = x_ref[:, gi * group:(gi + 1) * group]
        ws = w_ref[:, gi * group:(gi + 1) * group]
        part = jax.lax.dot_general(
            xs, ws, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)              # (bm, bn) MXU int8
        acc = acc + part.astype(jnp.float32) * sg_ref[:, gi][None, :]
    out_ref[...] += acc

    @pl.when(kk == nk - 1)
    def _epilogue():
        out_ref[...] *= sx_ref[...]


@functools.partial(jax.jit, static_argnames=("group", "bm", "bn", "bk",
                                             "interpret"))
def w4a8_gemm_pallas(qx: jnp.ndarray, sx: jnp.ndarray, qw: jnp.ndarray,
                     sg: jnp.ndarray, *, group: int = 128,
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     interpret: bool) -> jnp.ndarray:
    """f32 (M, N) = dequant(qw, sg) @ qx^T-style fused GEMM.

    qx (M, K) i8, sx (M, 1) f32 per-token scales,
    qw (N, K) i8 (int4 values stored in i8 for W4), sg (N, K//group) f32.
    """
    m, k = qx.shape
    n = qw.shape[0]
    bk = min(bk, k)
    assert k % bk == 0 and bk % group == 0, (k, bk, group)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    assert sg.shape == (n, k // group)
    nk = k // bk
    gpb = bk // group
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, group=group, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, gpb), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(qx, qw, sg, sx)
