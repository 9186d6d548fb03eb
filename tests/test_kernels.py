"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,n,k", [(8, 8, 16), (16, 24, 32), (128, 64, 256),
                                   (130, 70, 512), (1, 8, 64)])
@pytest.mark.parametrize("wbits,t", [(8, 8), (4, 8), (8, 4), (2, 8)])
def test_transitive_gemm_sweep(m, n, k, wbits, t, rng):
    qx = rng.integers(-128, 128, (m, k)).astype(np.int8)
    qw = rng.integers(-(1 << (wbits - 1)), 1 << (wbits - 1),
                      (n, k)).astype(np.int8)
    want = qx.astype(np.int64) @ qw.astype(np.int64).T
    got = np.asarray(ops.transitive_gemm(jnp.asarray(qx), jnp.asarray(qw),
                                         w_bits=wbits, t=t))
    np.testing.assert_array_equal(got, want)


def test_transitive_gemm_split_vs_full_lut(rng):
    """Beyond-paper split-LUT must agree with the monolithic 2^T LUT."""
    from repro.kernels.transitive_gemm import transitive_gemm_pallas
    qx = rng.integers(-128, 128, (16, 64)).astype(np.int8)
    qw = rng.integers(-8, 8, (16, 64)).astype(np.int8)
    interp = ops.default_interpret()
    a = transitive_gemm_pallas(jnp.asarray(qx), jnp.asarray(qw), w_bits=4,
                               t=8, bm=8, bn=8, bk=8, split_lut=True,
                               interpret=interp)
    b = transitive_gemm_pallas(jnp.asarray(qx), jnp.asarray(qw), w_bits=4,
                               t=8, bm=8, bn=8, bk=8, split_lut=False,
                               interpret=interp)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transitive_gemm_batched(rng):
    qx = rng.integers(-128, 128, (2, 5, 32)).astype(np.int8)
    qw = rng.integers(-8, 8, (12, 32)).astype(np.int8)
    got = np.asarray(ops.transitive_gemm(jnp.asarray(qx), jnp.asarray(qw),
                                         w_bits=4, t=8))
    want = np.einsum("bsk,nk->bsn", qx.astype(np.int64), qw.astype(np.int64))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,n,k,g", [(128, 128, 512, 128), (8, 16, 256, 64),
                                     (130, 200, 384, 128)])
def test_w4a8_gemm_sweep(m, n, k, g, rng):
    qx = rng.integers(-128, 128, (m, k)).astype(np.int8)
    sx = rng.uniform(0.5, 2.0, (m, 1)).astype(np.float32)
    qw = rng.integers(-8, 8, (n, k)).astype(np.int8)
    sg = rng.uniform(0.5, 2.0, (n, k // g)).astype(np.float32)
    want = np.asarray(ref.w4a8_matmul_ref(*map(jnp.asarray,
                                               (qx, sx, qw, sg))))
    got = np.asarray(ops.w4a8_gemm(*map(jnp.asarray, (qx, sx, qw, sg)),
                                   group=g))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-2)


@pytest.mark.parametrize("b,s,d", [(8, 512, 256), (1, 64, 32), (2, 256, 512)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_rg_lru_sweep(b, s, d, dtype, rng):
    x = rng.standard_normal((b, s, d)).astype(np.float32)
    a = rng.uniform(0.8, 0.999, (b, s, d)).astype(np.float32)
    h0 = rng.standard_normal((b, d)).astype(np.float32)
    xs, as_, h0s = (jnp.asarray(x, dtype), jnp.asarray(a, dtype),
                    jnp.asarray(h0, dtype))
    want = np.asarray(ref.rg_lru_ref(xs, as_, h0s), np.float32)
    got = np.asarray(ops.rg_lru(xs, as_, h0s), np.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_lut_build_matches_subset_sums(rng):
    xt = jnp.asarray(rng.integers(-50, 50, (5, 8)), jnp.int32)
    lut = np.asarray(ref.lut_build_ref(xt))
    x = np.asarray(xt)
    for p in [0, 1, 5, 128, 255, 170]:
        bits = [b for b in range(8) if (p >> b) & 1]
        np.testing.assert_array_equal(lut[:, p], x[:, bits].sum(-1))


@pytest.mark.parametrize("module,builder", [
    ("transitive_gemm", "transitive_gemm_pallas"),
    ("transitive_forest", "transitive_forest_pallas"),
    ("w4a8_gemm", "w4a8_gemm_pallas"),
    ("rg_lru", "rg_lru_pallas"),
])
def test_raw_builder_requires_interpret(module, builder):
    """The raw pallas_call builders take no interpret default: only
    ops.default_interpret() decides, so nothing lands in interpret mode
    on a TPU because a caller forgot the argument."""
    import importlib
    import inspect
    fn = getattr(importlib.import_module(f"repro.kernels.{module}"), builder)
    param = inspect.signature(fn).parameters["interpret"]
    assert param.kind is inspect.Parameter.KEYWORD_ONLY
    assert param.default is inspect.Parameter.empty
