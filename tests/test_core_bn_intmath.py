"""BN deployment strategies (paper §3.4) + integer math primitives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.bn import (
    apply_integer_bn, apply_thresholds, bn_apply_float, fold_bn,
    make_bn_act_thresholds, make_integer_bn,
)
from repro.core.intmath import (
    apply_lut, avgpool_requant_params, build_lut, int_avgpool_combine,
    int_isqrt, int_reciprocal_q, select_lut,
)

RNG = np.random.default_rng(0)


def _bn_params(c):
    gamma = RNG.uniform(0.5, 2.0, c)
    beta = RNG.uniform(-1.0, 1.0, c)
    mu = RNG.uniform(-1.0, 1.0, c)
    sigma = RNG.uniform(0.5, 2.0, c)
    return gamma, beta, mu, sigma


def test_bn_fold_exact():
    """Eq. 18 is an identity: folded linear == linear followed by BN."""
    c_in, c_out = 8, 5
    w = RNG.normal(size=(c_in, c_out))
    b = RNG.normal(size=(c_out,))
    gamma, beta, mu, sigma = _bn_params(c_out)
    x = RNG.normal(size=(16, c_in))
    ref = np.asarray(
        bn_apply_float(jnp.asarray(x @ w + b), gamma, beta, mu, sigma)
    )
    w_f, b_f = fold_bn(w, b, gamma, beta, mu, sigma, channel_axis=-1)
    np.testing.assert_allclose(x @ w_f + b_f, ref, rtol=1e-6, atol=1e-6)


def test_integer_bn_matches_float():
    """Eq. 21-22: integer BN approximates FP BN within its quantizer error."""
    c = 16
    gamma, beta, mu, sigma = _bn_params(c)
    eps_phi = 1e-3
    q_phi = RNG.integers(-(1 << 14), 1 << 14, size=(64, c)).astype(np.int32)
    phi = q_phi * eps_phi
    ibn = make_integer_bn(gamma, beta, mu, sigma, eps_phi, acc_bound=1 << 14)
    q_out = np.asarray(apply_integer_bn(jnp.asarray(q_phi), ibn))
    got = q_out * ibn.eps_out[None, :]
    ref = np.asarray(bn_apply_float(jnp.asarray(phi), gamma, beta, mu, sigma))
    # error sources: kappa quantization (<= eps_k/|kappa| rel) + lambda round
    kappa = gamma / sigma
    eps_k = 2 * np.max(np.abs(kappa)) / 255
    tol = eps_k * np.abs(phi).max() + 2 * ibn.eps_out.max()
    assert np.max(np.abs(got - ref)) <= tol


@pytest.mark.parametrize("rounded", [False, True])
def test_threshold_merge_exact_vs_quantized_act(rounded):
    """Eq. 19-20 absorbs BN+LQ with NO approximation: compare against the
    float pipeline BN -> clip -> quantize for a 4-bit output space.
    rounded=False is Eq. 10's floor; rounded=True shifts every threshold
    by half a quantum, absorbing a round-to-nearest quantizer instead —
    exactness must hold for both."""
    c, n_bits = 8, 4
    gamma, beta, mu, sigma = _bn_params(c)
    eps_phi = 7.3e-4
    beta_y = 4.0
    n_levels = 2 ** n_bits
    eps_y = beta_y / (n_levels - 1)
    q_phi = RNG.integers(-(1 << 15), 1 << 15, size=(256, c)).astype(np.int64)
    phi_real = q_phi * eps_phi
    # float reference: BN then linear quantization (Eq. 10 / round)
    bn = np.asarray(bn_apply_float(jnp.asarray(phi_real), gamma, beta, mu, sigma))
    shift = 0.5 if rounded else 0.0
    ref_img = np.clip(np.floor(bn / eps_y + shift), 0, n_levels - 1)
    th = make_bn_act_thresholds(gamma, beta, mu, sigma, eps_phi, eps_y,
                                n_levels, rounded=rounded)
    got = np.asarray(apply_thresholds(jnp.asarray(q_phi.astype(np.int32)), th))
    np.testing.assert_array_equal(got, ref_img)


@settings(max_examples=300, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_int_isqrt(n):
    got = int(int_isqrt(jnp.int32(n)))
    assert got == int(np.floor(np.sqrt(n)))


def test_int_isqrt_vectorized():
    n = jnp.asarray(RNG.integers(0, 2**31 - 1, size=4096), jnp.int32)
    got = np.asarray(int_isqrt(n))
    ref = np.floor(np.sqrt(np.asarray(n, np.float64))).astype(np.int64)
    np.testing.assert_array_equal(got, ref)


@settings(max_examples=200, deadline=None)
@given(st.integers(1, 2**15), st.integers(8, 24))
def test_int_reciprocal(r, d):
    got = int(int_reciprocal_q(jnp.int32(r), d))
    assert got == (1 << d) // r


def test_lut_matches_fn():
    """256-entry LUT == the staircase quantization of SiLU (Eq. 8/9)."""
    eps_in, zp_in = 0.05, -10
    eps_out, zp_out = 0.021, -128
    silu = lambda v: v / (1.0 + np.exp(-v))
    table = build_lut(silu, eps_in, zp_in, eps_out, zp_out)
    s = jnp.arange(-128, 128, dtype=jnp.int8)
    out = np.asarray(apply_lut(s, table))
    real_in = (np.arange(-128, 128) - zp_in) * eps_in
    expect = np.clip(np.round(silu(real_in) / eps_out) + zp_out, -128, 127)
    np.testing.assert_array_equal(out, expect)


def _lut_tables():
    silu = lambda v: v / (1.0 + np.exp(-v))
    gelu = lambda v: 0.5 * v * (
        1.0 + np.tanh(0.7978845608 * (v + 0.044715 * v**3)))
    k = np.arange(256)
    rng = np.random.default_rng(7)
    return {
        "silu": build_lut(silu, 0.05, -10, 0.021, -128),
        "gelu": build_lut(gelu, 0.04, 0, 0.03, -100),
        "random_a": rng.integers(-128, 128, 256).astype(np.int8),
        "random_b": rng.integers(-128, 128, 256).astype(np.int8),
        "non_monotone": np.round(100 * np.sin(k / 9.0)).astype(np.int8),
        "constant": np.full(256, 37, np.int8),
        "jumps": np.where(k % 2 == 0, -128, 127).astype(np.int8),
    }


LUT_TABLES = _lut_tables()


def _scan_lookup(stored, table):
    """The table as a traced slice of a per-layer stack under lax.scan."""
    stack = jnp.stack([table, table[::-1]])

    def layer(carry, t):
        return carry, select_lut(stored, t)

    return jax.lax.scan(layer, 0, stack)[1]


@pytest.mark.parametrize("mode", ["int8", "int32", "multidim", "jit", "scan"])
@pytest.mark.parametrize("name", sorted(LUT_TABLES))
def test_select_lut_equals_gather(name, mode):
    """The gather-free lookup is table[stored - qmin] for all 256 inputs."""
    table = LUT_TABLES[name]
    stored = np.arange(-128, 128)
    np.random.default_rng(1).shuffle(stored)
    if mode == "int32":
        got = select_lut(jnp.asarray(stored, jnp.int32), table)
    elif mode == "multidim":
        stored = stored.reshape(4, 8, 8)
        got = select_lut(jnp.asarray(stored, jnp.int8), table)
    elif mode == "jit":
        got = jax.jit(select_lut)(jnp.asarray(stored, jnp.int8), table)
    elif mode == "scan":
        got, got_rev = jax.jit(_scan_lookup)(
            jnp.asarray(stored, jnp.int8), table)
        np.testing.assert_array_equal(
            np.asarray(got_rev), table[::-1][stored + 128])
    else:
        got = select_lut(jnp.asarray(stored, jnp.int8), table)
    assert got.dtype == jnp.int8 and got.shape == stored.shape
    np.testing.assert_array_equal(np.asarray(got), table[stored + 128])
    np.testing.assert_array_equal(
        np.asarray(apply_lut(jnp.asarray(stored, jnp.int8), table)),
        table[stored + 128],
    )


def test_integer_avgpool():
    """Eq. 25 within 1/2^d of exact division."""
    k1 = k2 = 3
    m, d = avgpool_requant_params(k1 * k2)
    acc = jnp.asarray(RNG.integers(0, 9 * 127, size=128), jnp.int32)
    got = np.asarray(int_avgpool_combine(acc, m, d))
    ref = np.asarray(acc) / 9.0
    assert np.all(np.abs(got - ref) <= np.abs(ref) * (9 / (1 << d)) + 1)
