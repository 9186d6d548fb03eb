"""Bit-slicing properties (paper Sec. 2.1-2.2): exact roundtrips."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import bitslice

BITS = st.sampled_from([2, 4, 8])


@given(bits=BITS, n=st.integers(1, 12), k=st.integers(1, 6),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=40, deadline=None)
def test_plane_roundtrip(bits, n, k, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(-(1 << (bits - 1)), 1 << (bits - 1), size=(n, k * 8))
    planes = bitslice.bit_planes(w, bits)
    assert planes.shape == (bits, n, k * 8)
    assert set(np.unique(planes)) <= {0, 1}
    back = bitslice.reconstruct_from_planes(planes, bits)
    np.testing.assert_array_equal(back, w)


@given(bits=BITS, t=st.sampled_from([4, 8]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_transrow_pack_unpack(bits, t, seed):
    rng = np.random.default_rng(seed)
    w = rng.integers(-(1 << (bits - 1)), 1 << (bits - 1), size=(9, 4 * t))
    planes = bitslice.bit_planes(w, bits)
    rows = bitslice.pack_transrows(planes, t)
    assert rows.max() < (1 << t)
    back = bitslice.unpack_transrows(rows, t)
    np.testing.assert_array_equal(back, planes)


def test_plane_signs_msb_negative():
    s = bitslice.plane_signs(8)
    assert s[-1] == -128 and s[0] == 1 and (s[:-1] > 0).all()


def test_jnp_matches_numpy(rng):
    import jax.numpy as jnp
    w = rng.integers(-8, 8, size=(5, 16))
    np_rows = bitslice.pack_transrows(bitslice.bit_planes(w, 4), 8)
    j_rows = bitslice.pack_transrows_jnp(
        bitslice.bit_planes_jnp(jnp.asarray(w), 4), 8)
    np.testing.assert_array_equal(np.asarray(j_rows), np_rows)
