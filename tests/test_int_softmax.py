"""Integer-only softmax (core/intsoftmax.py): accuracy vs float oracle +
the attention island swap (int_softmax=True leaves NO float ops)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.intsoftmax import (
    int_softmax, int_softmax_ref_float, make_int_softmax_tables,
)

RNG = np.random.default_rng(5)


@settings(max_examples=40, deadline=None)
@given(st.floats(min_value=1e-5, max_value=1e-2),
       st.integers(8, 512))
def test_int_softmax_within_quanta(eps_s, n):
    rng = np.random.default_rng(1234)  # deterministic per example
    t = jax.tree.map(jnp.asarray, make_int_softmax_tables(eps_s))
    lim = min(int(8.0 / eps_s), 2 ** 24)  # logits within +-8.0
    s = jnp.asarray(rng.integers(-lim, lim, size=(4, n)), jnp.int32)
    got = np.asarray(int_softmax(s, t), np.int64)
    ref = np.asarray(int_softmax_ref_float(s, eps_s), np.int64)
    assert np.abs(got - ref).max() <= 3
    # probability mass unbiased vs the float oracle (both paths round;
    # a floor-division implementation fails this at ~15% deficit)
    assert np.abs(got.sum(-1) - ref.sum(-1)).max() <= 6


def test_int_softmax_masked():
    eps_s = 4e-4
    t = jax.tree.map(jnp.asarray, make_int_softmax_tables(eps_s))
    s = jnp.asarray(RNG.integers(-10000, 10000, size=(8, 64)), jnp.int32)
    mask = jnp.asarray(RNG.random((8, 64)) > 0.4)
    got = np.asarray(int_softmax(s, t, mask=mask), np.int64)
    ref = np.asarray(int_softmax_ref_float(s, eps_s, mask=mask), np.int64)
    assert np.abs(got - ref).max() <= 2
    assert (got[~np.asarray(mask)] == 0).all()


def test_int_softmax_is_integer_only():
    eps_s = 4e-4
    t = jax.tree.map(jnp.asarray, make_int_softmax_tables(eps_s))
    s = jnp.zeros((2, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda s: int_softmax(s, t))(s)
    float_ops = [e.primitive.name for e in jaxpr.jaxpr.eqns
                 if any(jnp.issubdtype(v.aval.dtype, jnp.floating)
                        for v in list(e.outvars) + list(e.invars)
                        if hasattr(v, "aval"))]
    assert not float_ops, float_ops


def test_attention_island_swap():
    """int_softmax=True: ID attention runs with ZERO float ops."""
    from repro.core.calibrate import Calibrator
    from repro.core.rep import Rep
    from repro.layers.attention import QAttention

    attn = QAttention(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      max_seq=64)
    p = attn.init(jax.random.PRNGKey(2))
    x = jnp.asarray(RNG.normal(size=(2, 32, 64)), jnp.float32)
    calib = Calibrator()
    y_fp, _ = attn.apply_float(p, x, Rep.FP, calib=calib, scope="")
    from repro.layers.common import DeployCtx

    t, eps_acc_o = attn.deploy(DeployCtx(calib=calib), "",
                               jax.tree.map(np.asarray, p), 2 * 4.0 / 255, 0)
    t_j = jax.tree.map(jnp.asarray, t)
    s_x = jnp.asarray(np.clip(np.floor(np.asarray(x) / (2 * 4.0 / 255)),
                              -128, 127), jnp.int8)
    acc_int, _ = attn.apply_id(t_j, s_x, int_softmax=True)
    jaxpr = jax.make_jaxpr(
        lambda s: attn.apply_id(t_j, s, int_softmax=True)[0])(s_x)
    # no float-typed outputs anywhere in the attention jaxpr
    bad = [e.primitive.name for e in jaxpr.jaxpr.eqns
           if any(jnp.issubdtype(ov.aval.dtype, jnp.floating)
                  for ov in e.outvars)]
    assert not bad, bad
    # and it still matches the float-island path within a few quanta
    acc_float, _ = attn.apply_id(t_j, s_x)
    got = np.asarray(acc_int, np.float64)
    ref = np.asarray(acc_float, np.float64)
    cc = np.corrcoef(got.ravel(), ref.ravel())[0, 1]
    assert cc > 0.999, cc
