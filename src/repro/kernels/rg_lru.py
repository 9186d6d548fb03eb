"""Pallas TPU kernel: blocked linear recurrence (RG-LRU / SSM scan).

h_t = a_t * h_{t-1} + x_t, computed per sequence block with an in-block
doubling (Blelloch-style) scan — log2(bs) shifted multiply-adds on the VPU —
and a VMEM carry across blocks. The sequence grid axis is sequential
("arbitrary"); batch and feature axes are parallel.

This serves the long_500k decode/prefill path of the recurrent archs
(recurrentgemma, xlstm), where attention-free state makes 500k context
sub-quadratic (DESIGN.md §5).

VMEM per step (bb=8, bs=256, bd=256): 3 blocks x 8x256x256 f32 = 6 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rg_lru_pallas"]


def _kernel(x_ref, a_ref, h0_ref, out_ref, carry_ref, *, bs):
    sk = pl.program_id(2)

    @pl.when(sk == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)      # (bb, bs, bd)
    h = x_ref[...].astype(jnp.float32)
    # In-block inclusive scan by doubling: after step o,
    # h_t = sum_{t-2o < u <= t} (prod a) x_u, a_t = prod of 2o coefficients.
    off = 1
    while off < bs:
        h_shift = jnp.pad(h, ((0, 0), (off, 0), (0, 0)))[:, :bs, :]
        a_shift = jnp.pad(a, ((0, 0), (off, 0), (0, 0)),
                          constant_values=1.0)[:, :bs, :]
        h = h + a * h_shift
        a = a * a_shift
        off *= 2
    h = h + a * carry_ref[...][:, None, :]
    out_ref[...] = h.astype(out_ref.dtype)
    carry_ref[...] = h[:, -1, :]


@functools.partial(jax.jit, static_argnames=("bb", "bs", "bd", "interpret"))
def rg_lru_pallas(x: jnp.ndarray, a: jnp.ndarray, h0: jnp.ndarray, *,
                  bb: int = 8, bs: int = 256, bd: int = 256,
                  interpret: bool) -> jnp.ndarray:
    """x, a: (B, S, D); h0: (B, D) -> h: (B, S, D)."""
    b, s, d = x.shape
    bb, bs, bd = min(bb, b), min(bs, s), min(bd, d)
    assert b % bb == 0 and s % bs == 0 and d % bd == 0, (x.shape, bb, bs, bd)
    grid = (b // bb, d // bd, s // bs)      # sequence axis last → sequential
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bs, bd), lambda i, j, sk: (i, sk, j)),
            pl.BlockSpec((bb, bs, bd), lambda i, j, sk: (i, sk, j)),
            pl.BlockSpec((bb, bd), lambda i, j, sk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, bs, bd), lambda i, j, sk: (i, sk, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bd), jnp.float32)],
        interpret=interpret,
    )(x, a, h0)
