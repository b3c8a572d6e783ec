"""Sparse experts and sliding-window attention on the served path
(mellum2_12b's block): the windowed paged kernel against the gather
oracle, the grouped expert kernel against a plain integer mirror,
dropless routing's row independence, chunked serving against
exact-length serving, and the residual Add's rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibrate import Calibrator
from repro.core.rep import Rep
from repro.core.requant import apply_rqt
from repro.kernels.moe_experts import moe_experts
from repro.kernels.paged_attention import paged_attention_pallas
from repro.launch.serve import deploy_model
from repro.layers.add import QAdd, shift_round
from repro.layers.attention import INACTIVE_POS, _paged_kv_view
from repro.layers.common import DeployCtx
from repro.layers.moe import QMoE
from repro.serving import SchedulerConfig, ServingConfig, ServingEngine

MAX_LEN = 48


@pytest.fixture(scope="module")
def deployed():
    """Reduced mellum2_12b: 4 layers (sliding x 3 at window 8, full
    with YaRN), 4 experts top-2."""
    # max_seq 65: the 4 x 64-token calibration batch fills whole
    # capacity groups of the float path
    return deploy_model("mellum2_12b", reduced=True, max_seq=65)


# ---------------------------------------------------------------------
# windowed paged kernel == gather oracle, bit for bit
# ---------------------------------------------------------------------
def _windowed_oracle(q, k_pool, v_pool, table, pos, *, score_scale, group,
                     window):
    """The gather path's math (layers/attention.apply_id with
    paged_decode="gather") with the window in its mask."""
    kh = jnp.repeat(_paged_kv_view(k_pool, table), group, axis=1)
    vh = jnp.repeat(_paged_kv_view(v_pool, table), group, axis=1)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, kh,
                        preferred_element_type=jnp.int32)
    S, T = q.shape[2], kh.shape[2]
    i = pos[:, None, None, None] + jnp.arange(S)[None, None, :, None]
    j = jnp.arange(T)[None, None, None, :]
    keep = (j <= i) & (j > i - window)
    logits = (scores.astype(jnp.float32) * jnp.float32(score_scale)
              + jnp.where(keep, 0.0, -1e9).astype(jnp.float32))
    s_p = jnp.round(jax.nn.softmax(logits, axis=-1) * 127.0).astype(
        jnp.int8)
    return jnp.einsum("bhst,bhtd->bhsd", s_p, vh,
                      preferred_element_type=jnp.int32)


@pytest.mark.parametrize("s_q,start", [
    # decode rows: inside the window, on its edge, one past, far past,
    # and a parked row
    (1, [3, 9, 10, 21, INACTIVE_POS]),
    # chunk rows whose queries straddle the window edge, start on a
    # page boundary past it, and start at 0
    (6, [5, 8, 13, 0, 17]),
])
def test_windowed_kernel_matches_gather(s_q, start):
    rng = np.random.default_rng(41)
    B, H, K, hd, ps, pps, window = 5, 4, 2, 8, 4, 6, 10
    n_pages = B * pps + 2
    kp, vp = (jnp.asarray(rng.integers(-127, 128,
                                       size=(n_pages + 1, K, hd, ps)),
                          jnp.int8) for _ in range(2))
    q = jnp.asarray(rng.integers(-127, 128, size=(B, H, s_q, hd)),
                    jnp.int8)
    perm = 1 + rng.permutation(n_pages)[:B * pps]
    table = jnp.asarray(perm.reshape(B, pps), jnp.int32)
    pos = jnp.asarray(start, jnp.int32)
    kw = dict(score_scale=3e-4, group=H // K)
    got = paged_attention_pallas(q, kp, vp, table, pos, window=window,
                                 **kw)
    want = _windowed_oracle(q, kp, vp, table, pos, window=window, **kw)
    real = np.asarray(start) < INACTIVE_POS
    np.testing.assert_array_equal(np.asarray(got)[real],
                                  np.asarray(want)[real])
    # a window longer than every row's history is no window
    wide = paged_attention_pallas(q, kp, vp, table, pos,
                                  window=pps * ps + s_q, **kw)
    plain = paged_attention_pallas(q, kp, vp, table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(wide)[real],
                                  np.asarray(plain)[real])


# ---------------------------------------------------------------------
# grouped expert kernel == plain integer mirror
# ---------------------------------------------------------------------
E, D, F, TOP = 8, 32, 64, 2


@pytest.fixture(scope="module")
def moe_tables():
    moe = QMoE(d_model=D, d_ff=F, n_experts=E, top_k=TOP, group_size=64)
    p = moe.init(jax.random.PRNGKey(3))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(128, D)),
                    jnp.float32)
    calib = Calibrator()
    moe.apply_float(p, x, Rep.FP, calib=calib, scope="")
    t, _ = moe.deploy(DeployCtx(calib=calib), "",
                      jax.tree.map(np.asarray, p), 2 * 4.0 / 255, 0)
    return moe, jax.tree.map(jnp.asarray, t)


def _expert_mirror(t, x, e):
    """One expert's integer SwiGLU over rows x (R, D), with the XLA ops
    of the capacity path it replaced."""
    def rows(rqt):
        return {k: (v[e] if getattr(v, "ndim", 0) == 2 else v)
                for k, v in rqt.items()}

    def dot(a, w):
        return jnp.dot(a.astype(jnp.int32), w.astype(jnp.int32))

    s_pre = apply_rqt(dot(x, t["wg_q"][e]), rows(t["g_rqt"]))
    s_g = jnp.take(t["g_lut"], s_pre.astype(jnp.int32) + 128)
    s_u = apply_rqt(dot(x, t["wu_q"][e]), rows(t["u_rqt"]))
    prod = (s_g.astype(jnp.int32) - t["zp_g"]) * s_u.astype(jnp.int32)
    s_h = apply_rqt(prod, t["h_rqt"])
    return apply_rqt(dot(s_h, t["wd_q"][e]), rows(t["o_rqt"]))


@pytest.mark.parametrize("T", [3, 40], ids=["pairs_lt_experts",
                                            "tiles_span_experts"])
# 3 x 2 pairs reach at most 6 of the 8 experts; 80 pairs fill three
# 32-row tiles, so tiles hold several experts' rows and runs cross tiles
def test_moe_experts_kernel_matches_mirror(moe_tables, T):
    _, t = moe_tables
    rng = np.random.default_rng(T)
    x = jnp.asarray(rng.integers(-128, 128, size=(T, D)), jnp.int8)
    experts = np.stack([rng.choice(E, TOP, replace=False)
                        for _ in range(T)]).astype(np.int32)
    experts[T // 2] = E  # a token routed nowhere
    got = np.asarray(moe_experts(x, jnp.asarray(experts), t))
    for tok in range(T):
        for i, e in enumerate(experts[tok]):
            if e == E:
                continue
            want = _expert_mirror(t, x[tok:tok + 1], int(e))
            np.testing.assert_array_equal(got[tok, i], np.asarray(want)[0])


def test_dropless_moe_rows_are_independent(moe_tables):
    """A token's output is the same whatever the other tokens are, and
    whether or not they are marked invalid (routed nowhere)."""
    moe, t = moe_tables
    rng = np.random.default_rng(7)
    x = rng.integers(-128, 128, size=(24, D)).astype(np.int8)
    base = np.asarray(moe.apply_id(t, jnp.asarray(x)))
    x2 = x.copy()
    x2[1:] = rng.integers(-128, 128, size=(23, D))
    valid = np.zeros(24, bool)
    valid[0] = True
    for other, v in ((x2, None), (x2, valid)):
        got = np.asarray(moe.apply_id(t, jnp.asarray(other),
                                      None if v is None else jnp.asarray(v)))
        np.testing.assert_array_equal(got[0], base[0])
    masked = np.asarray(moe.apply_id(t, jnp.asarray(x), jnp.asarray(valid)))
    assert not masked[1:].any()


# ---------------------------------------------------------------------
# the served path: chunked == exact-length, garbage rows, kernel==gather
# ---------------------------------------------------------------------
def _prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, 256, size=n).astype(np.int32)
            for n in (5, 14, 21, 9)]


def _engine_tokens(lm, tables, *, chunk, paged_kernel=True, n_slots=3):
    eng = ServingEngine(lm, tables, ServingConfig(
        n_slots=n_slots, max_len=MAX_LEN, paged=True, page_size=4,
        n_pages=40, paged_kernel=paged_kernel,
        scheduler=SchedulerConfig(prefill_chunk=chunk)))
    for p in _prompts():
        eng.submit(p, max_new_tokens=16)
    eng.run_until_drained()
    return {c.req_id: list(c.tokens) for c in eng.completed}


def _exact_tokens(lm, tables, prompt, n):
    """Greedy decode of one request alone: prefill at its exact length
    into a contiguous cache, then one token at a time."""
    caches = lm.init_caches(1, MAX_LEN, Rep.ID)
    logits, caches = jax.jit(lm.prefill)(tables, jnp.asarray(prompt)[None],
                                         caches)
    out = [int(jnp.argmax(logits[0, -1]))]
    step = jax.jit(lm.decode_step)
    for i in range(n - 1):
        logits, caches = step(
            tables, jnp.asarray([[out[-1]]], jnp.int32), caches,
            jnp.int32(len(prompt) + i))
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def test_moe_chunked_serving_matches_exact_length(deployed):
    """Chunks of 8 through the unified step and the paged kernel, with
    rows routed nowhere for padding and parked slots, give each
    request's exact-length greedy tokens; so do the whole-prompt
    (bucketed) engine and the gather oracle."""
    lm, tables = deployed
    assert lm.cfg.family == "moe"
    assert [t.window for _, t, _ in lm.plan()] == [8, 0]
    chunked = _engine_tokens(lm, tables, chunk=8)
    for rid, p in enumerate(_prompts()):
        assert chunked[rid] == _exact_tokens(lm, tables, p, 16), rid
    assert _engine_tokens(lm, tables, chunk=0) == chunked
    assert _engine_tokens(lm, tables, chunk=8, paged_kernel=False) == chunked


def test_moe_real_row_ignores_garbage_rows(deployed):
    """One unified step: a real row's logits do not move when every
    other slot carries garbage tokens at live positions instead of
    being parked."""
    lm, tables = deployed
    arena = ServingEngine(lm, tables, ServingConfig(
        n_slots=4, max_len=MAX_LEN, paged=True, page_size=4,
        n_pages=60)).arena
    pps = arena.pages_per_slot
    arena.page_table[:] = 1 + np.arange(4 * pps).reshape(4, pps)
    rng = np.random.default_rng(9)
    toks = rng.integers(0, 256, size=(4, 8)).astype(np.int32)
    last = jnp.asarray([7, 7, 7, 7], jnp.int32)
    outs = []
    for start in ([0, INACTIVE_POS, INACTIVE_POS, INACTIVE_POS],
                  [0, 0, 4, 12]):
        garbage = toks.copy()
        garbage[1:] = rng.integers(0, 256, size=(3, 8))
        logits, _ = jax.jit(lm.prefill_chunk)(
            tables, jnp.asarray(garbage), arena.decode_view(),
            jnp.asarray(start, jnp.int32), last)
        outs.append(np.asarray(logits[0]))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------
# residual Add: both shifts round to nearest
# ---------------------------------------------------------------------
@pytest.mark.parametrize("x,k,want", [
    (5, 1, 3), (4, 1, 2), (-5, 1, -2), (-6, 1, -3), (7, 2, 2), (6, 2, 2),
    (-7, 2, -2), (-6, 2, -1), (9, 0, 9), (-9, 0, -9), (2 ** 30 + 1, 1,
                                                         2 ** 29 + 1),
])
def test_shift_round_halves_up(x, k, want):
    """floor(x / 2^k + 1/2): a half goes up, where the floor shift
    would drop it."""
    got = shift_round(jnp.asarray([x], jnp.int32), jnp.asarray(k, jnp.int32))
    assert int(got[0]) == want


def test_add_rounds_both_shifts():
    """QAdd.apply_id on hand-picked tables: branch a through m=3, s0=1,
    d=3 (3/8 after a halving pre-shift), branch b through m=1, d=1."""
    big = np.int32(1 << 24)

    def rqt(m, d, s0):
        return {"m": np.int32(m), "d": np.int32(d), "s0": np.int32(s0),
                "lo": -big, "hi": big, "zp": np.int32(0)}

    t = jax.tree.map(jnp.asarray, {"rq_a": rqt(3, 3, 1), "rq_b": rqt(1, 1, 0),
                                   "zp_a": np.int32(0), "zp_b": np.int32(0)})
    a = jnp.asarray([7, 5, -5, 1], jnp.int8)
    b = jnp.asarray([1, 3, -3, 0], jnp.int8)
    got = np.asarray(QAdd().apply_id(t, a, b))
    # a: round(7/2)=4 -> round(12/4)=3; 5 -> 3 -> round(9/4)=2;
    #    -5 -> -2 -> round(-6/4)=-1; 1 -> 1 -> round(3/4)=1
    # b: round(1/2)=1, round(3/2)=2, round(-3/2)=-1, 0
    np.testing.assert_array_equal(got, [4, 4, -2, 1])
