"""Pallas kernel validation vs pure-jnp oracles.  On the CPU every kernel
runs in the Pallas interpreter (kernels/platform.py picks the mode).

Integer kernels demand EXACT equality (tolerance 0) against ref.py;
shape/dtype sweeps cover the model's real call sites (head dims 64..192,
ragged M, per-channel tables).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.requant import RequantParams
from repro.kernels import ops, ref
from repro.kernels.int8_matmul import int8_matmul_requant_pallas
from repro.kernels.quant_attention import quant_flash_attention_pallas
from repro.kernels.requant_kernel import requant_pallas

def _rng(seed=21):
    return np.random.default_rng(seed)


RNG = _rng()


def _rand_i8(*shape, rng=None):
    return jnp.asarray((rng or RNG).integers(-127, 128, size=shape),
                       jnp.int8)


def _tables(N, eps_out=0.05, acc_bound=2.0 ** 20):
    eps_in = RNG.uniform(1e-5, 5e-4, size=N)
    rp = RequantParams.make(eps_in, eps_out, acc_bound=acc_bound)
    return (jnp.asarray(np.broadcast_to(rp.m, (N,)), jnp.int32),
            jnp.asarray(np.broadcast_to(rp.s0, (N,)), jnp.int32),
            rp.d)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 256)])
def test_int8_matmul_exact(M, K, N):
    x = _rand_i8(M, K)
    w = _rand_i8(K, N)
    bias = jnp.asarray(RNG.integers(-1000, 1000, size=N), jnp.int32)
    mul, s0, d = _tables(N)
    got = int8_matmul_requant_pallas(x, w, bias, mul, s0, d=d, zp=-3)
    want = ref.int8_matmul_requant_ref(x, w, bias, mul, s0, d=d, zp=-3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(5, 7, 100), (3, 130)])
def test_int8_matmul_ragged_wrapper(shape):
    K, N = 96, 72
    x = _rand_i8(*shape, K)
    w = _rand_i8(K, N)
    bias = jnp.asarray(RNG.integers(-100, 100, size=N), jnp.int32)
    mul, s0, d = _tables(N)
    got = ops.int8_matmul_requant(x, w, bias, mul, s0, d=d)
    want = ref.int8_matmul_requant_ref(
        x.reshape(-1, K), w, bias, mul, s0, d=d)
    np.testing.assert_array_equal(
        np.asarray(got).reshape(-1, N), np.asarray(want))


def test_int8_matmul_matches_model_linear():
    """Kernel == QLinear.apply_id + apply_rqt on a real deploy table."""
    from repro.core.requant import apply_rqt, make_rqt
    from repro.layers.linear import QLinear

    lin = QLinear(96, 64, use_bias=True)
    p = jax.tree.map(np.asarray, lin.init(jax.random.PRNGKey(0)))
    p["b"] = RNG.normal(size=64).astype(np.float32) * 0.1
    eps_x = 0.03
    ip, eps_acc = lin.deploy(p, eps_x, 0)
    rqt = make_rqt(eps_acc, 0.05, zp_out=-5, acc_bound=lin.acc_bound())
    s_x = _rand_i8(32, 96)
    want = apply_rqt(lin.apply_id(jax.tree.map(jnp.asarray, ip), s_x),
                     jax.tree.map(jnp.asarray, rqt))
    got = ops.linear_rqt_kernel(s_x, jax.tree.map(jnp.asarray, ip), rqt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype_bits", [4, 8])
def test_requant_kernel_exact(dtype_bits):
    M, N = 256, 64
    hi = 2 ** (dtype_bits * 3)
    q = jnp.asarray(RNG.integers(-hi, hi, size=(M, N)), jnp.int32)
    mul, s0, d = _tables(N)
    lo_t = jnp.full((N,), -(2 ** 26), jnp.int32)
    hi_t = jnp.full((N,), 2 ** 26, jnp.int32)
    got = requant_pallas(q, mul, s0, lo_t, hi_t, d=d, zp=1)
    want = ref.requant_ref(q, mul, s0, lo_t, hi_t, d=d, zp=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("hd,S_q,S_kv,causal", [
    (64, 128, 128, True),
    (128, 128, 256, True),
    (192, 128, 128, False),
    (64, 256, 384, True),
])
def test_quant_attention_exact_vs_blockwise_ref(hd, S_q, S_kv, causal):
    BH = 2
    q = _rand_i8(BH, S_q, hd)
    k = _rand_i8(BH, S_kv, hd)
    v = _rand_i8(BH, S_kv, hd)
    scale = 1e-4
    got = quant_flash_attention_pallas(
        q, k, v, score_scale=scale, eps_ctx=0.01, causal=causal,
        bq=128, bkv=128)
    want = ref.quant_flash_attention_ref(
        q, k, v, score_scale=scale, eps_ctx=0.01, causal=causal,
        bq=128, bkv=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quant_attention_close_to_unfused():
    """Blockwise (max-relative) probability quantization vs the model's
    global one: both must land within a few quanta of TRUE float
    attention on a calibrated ctx range.  (The fused kernel is in fact
    the more accurate of the two — it keeps precision on low-prob keys.)"""
    rng = _rng(101)
    BH, S, hd = 2, 256, 64
    q = _rand_i8(BH, 128, hd, rng=rng)
    k = _rand_i8(BH, S, hd, rng=rng)
    v = _rand_i8(BH, S, hd, rng=rng)
    scale = 5e-5
    # calibrated ctx range: |ctx| <= ~weighted |v| -> eps = 2*amax/255
    eps_ctx = 2.0 * 100.0 / 255.0
    kw = dict(score_scale=scale, eps_ctx=eps_ctx, causal=True)
    got = np.asarray(
        quant_flash_attention_pallas(q, k, v, bq=128, bkv=128, **kw),
        np.int64)
    # true float attention, quantized on the same grid
    s = np.einsum("bqd,bkd->bqk", np.asarray(q, np.int64),
                  np.asarray(k, np.int64)).astype(np.float64) * scale
    mask = np.arange(S)[None, None, :] > np.arange(128)[None, :, None]
    s = np.where(mask, -1e9, s)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    true_ctx = np.einsum("bqk,bkd->bqd", p, np.asarray(v, np.float64))
    true_q = np.clip(np.round(true_ctx / eps_ctx), -128, 127)
    assert np.abs(got - true_q).max() <= 6, np.abs(got - true_q).max()
    # the unfused path is also within a few quanta
    want = np.asarray(ref.attention_unfused_ref(q, k, v, **kw), np.int64)
    assert np.abs(want - true_q).max() <= 8
    assert np.abs(got - true_q).mean() <= np.abs(want - true_q).mean() + 0.1


def test_quant_attention_gqa_wrapper():
    rng = _rng(102)
    B, H, K, S, hd = 2, 8, 2, 128, 64
    q = _rand_i8(B, H, 128, hd, rng=rng)
    k = _rand_i8(B, K, S, hd, rng=rng)
    v = _rand_i8(B, K, S, hd, rng=rng)
    out = ops.quant_flash_attention(
        q, k, v, score_scale=1e-4, eps_ctx=0.01, n_rep=H // K)
    assert out.shape == (B, H, 128, hd) and out.dtype == jnp.int8
    # equals per-head call with repeated kv
    kr = jnp.repeat(k, H // K, axis=1).reshape(B * H, S, hd)
    vr = jnp.repeat(v, H // K, axis=1).reshape(B * H, S, hd)
    want = ref.quant_flash_attention_ref(
        q.reshape(B * H, 128, hd), kr, vr, score_scale=1e-4, eps_ctx=0.01)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(B * H, 128, hd), np.asarray(want))
