"""Quantization substrate + TransitiveLinear path equivalence."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

import repro.quant.quantize as Q
from repro.quant import QuantConfig, linear_init, linear_apply


@given(bits=st.sampled_from([4, 8]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_groupwise_roundtrip_error(bits, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    q, s = Q.quantize_groupwise(w, bits, 128)
    back = Q.dequantize_groupwise(q, s, 128)
    # max error bounded by half an LSB per group
    lsb = np.asarray(s).repeat(128, -1) * 1.0
    err = np.abs(np.asarray(back - w))
    assert (err <= 0.5 * lsb + 1e-6).all()


def test_per_token_scale_shape():
    x = jnp.ones((2, 3, 64))
    q, s = Q.quantize_per_token(x)
    assert q.shape == x.shape and s.shape == (2, 3, 1)
    assert q.dtype == jnp.int8


@pytest.mark.parametrize("group", [64, 128, 0])
@pytest.mark.parametrize("w_bits", [4, 8])
def test_linear_paths_agree(group, w_bits):
    cfg = QuantConfig(mode="ptq", w_bits=w_bits, a_bits=8, group=group)
    p = linear_init(jax.random.PRNGKey(0), 256, 96, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 256), jnp.float32)
    y_int = linear_apply(p, x, cfg.with_(backend="int_dot"))
    y_lut = linear_apply(p, x, cfg.with_(backend="lut"))
    y_pal = linear_apply(p, x, cfg.with_(backend="pallas"))
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_lut),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_pal),
                               rtol=1e-4, atol=1e-4)


def test_ptq_close_to_fp():
    cfg_fp = QuantConfig(mode="none")
    cfg_q = QuantConfig(mode="ptq", w_bits=8, a_bits=8, group=128)
    key = jax.random.PRNGKey(0)
    p_fp = linear_init(key, 256, 128, cfg_fp, dtype=jnp.float32)
    p_q = linear_init(key, 256, 128, cfg_q)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256), jnp.float32)
    y_fp = np.asarray(linear_apply(p_fp, x, cfg_fp))
    y_q = np.asarray(linear_apply(p_q, x, cfg_q))
    rel = np.abs(y_q - y_fp).mean() / (np.abs(y_fp).mean() + 1e-9)
    assert rel < 0.02, rel           # W8A8 is near-lossless


def test_qat_ste_grads():
    cfg = QuantConfig(mode="qat", w_bits=4, group=64)
    p = linear_init(jax.random.PRNGKey(0), 64, 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32)
    g = jax.grad(lambda pp: (linear_apply(pp, x, cfg) ** 2).mean())(p)
    gw = np.asarray(g["w"])
    assert np.isfinite(gw).all() and np.abs(gw).sum() > 0


def test_grouped_ptq_shape_mismatch_is_loud():
    """A PTQ layer whose d_in is not divisible by its scale-group count
    must raise, not floor-divide into wrong groups and silently mis-scale
    every output channel (e.g. a weight sliced after quantization)."""
    cfg = QuantConfig(mode="ptq", w_bits=4, a_bits=8, group=64)
    p = linear_init(jax.random.PRNGKey(0), 192, 16, cfg)   # sg: (16, 3)
    bad = {"qw": p["qw"][:, :100], "sg": p["sg"]}          # 100 % 3 != 0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 100), jnp.float32)
    with pytest.raises(ValueError, match=r"\(16, 100\).*3 scale groups"):
        linear_apply(bad, x, cfg)
    # divisible slices still pass the guard (3 groups of 32)
    ok = {"qw": p["qw"][:, :96], "sg": p["sg"]}
    y = linear_apply(ok, x[:, :96], cfg)
    assert np.isfinite(np.asarray(y)).all()
