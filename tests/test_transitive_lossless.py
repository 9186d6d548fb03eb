"""THE paper claim: transitive execution is lossless (bit-exact vs int GEMM).

Property-tested across bit widths, TransRow widths, shapes and data
distributions — including adversarial all-ones/all-zeros/duplicate-heavy
matrices.
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import transitive


@given(bits=st.sampled_from([2, 4, 8]), t=st.sampled_from([4, 8]),
       n=st.integers(1, 20), kt=st.integers(1, 5), m=st.integers(1, 9),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=40, deadline=None)
def test_lossless_random(bits, t, n, kt, m, seed):
    rng = np.random.default_rng(seed)
    k = kt * t
    w = rng.integers(-(1 << (bits - 1)), 1 << (bits - 1), size=(n, k))
    x = rng.integers(-128, 128, size=(k, m))
    want = w.astype(np.int64) @ x.astype(np.int64)
    got = transitive.transitive_gemm(w, x, bits, t)
    np.testing.assert_array_equal(got, want)


@given(fill=st.sampled_from([-8, -1, 0, 1, 7]), seed=st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_lossless_degenerate(fill, seed):
    rng = np.random.default_rng(seed)
    w = np.full((7, 16), fill)
    x = rng.integers(-128, 128, size=(16, 3))
    want = w.astype(np.int64) @ x.astype(np.int64)
    np.testing.assert_array_equal(
        transitive.transitive_gemm(w, x, 4, 8), want)


def test_lossless_duplicate_heavy(rng):
    """FR-dominated tiles (few unique patterns) stay exact."""
    pats = rng.integers(-8, 8, size=(3, 16))
    w = pats[rng.integers(0, 3, size=64)]
    x = rng.integers(-128, 128, size=(16, 5))
    want = w.astype(np.int64) @ x.astype(np.int64)
    got, totals = transitive.transitive_gemm_stats(w, x, 4, 8)
    np.testing.assert_array_equal(got, want)
    assert totals["density"] < 0.30      # heavy reuse visible in ops


def test_stats_density_sane(rng):
    w = rng.integers(-128, 128, size=(64, 64))
    x = rng.integers(-128, 128, size=(64, 4))
    got, totals = transitive.transitive_gemm_stats(w, x, 8, 8)
    np.testing.assert_array_equal(got, w.astype(np.int64) @ x.astype(np.int64))
    assert 1 / 8 - 0.02 <= totals["density"] <= 0.75
    assert totals["bit_ops"] <= totals["dense_ops"]
