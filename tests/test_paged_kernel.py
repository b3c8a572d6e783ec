"""Fused paged-attention kernel: decode (ISSUE 4) and the unified
multi-token generalization (ISSUE 9).

Acceptance: paged ID decode AND chunked prefill run through
kernels/paged_attention.py without materializing the dense logical KV
view — engine-wide, a mixed prefill+decode step on the default paged
path performs ZERO dense gathers — with
kernel == gather-dense oracle == SlotArena pinned token-for-token, and
page-table edge cases (single-page requests, decode landing exactly on
a page boundary, last partial page, recycled slots with reassigned
table rows, multi-token query rows crossing page boundaries
mid-chunk) pinned bit-exact against the pure-jnp mirror and the
gather-dense math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.requant import make_rqt
from repro.kernels import ref
from repro.kernels.paged_attention import (
    page_walk,
    paged_attention_decode_pallas,
    paged_attention_pallas,
)
from repro.launch import variants
from repro.launch.serve import deploy_model, serve_batch
from repro.layers.attention import (
    INACTIVE_POS,
    PAGE_NULL,
    _kv4_operand,
    _paged_kv_view,
)
from repro.serving import SchedulerConfig, ServingConfig, ServingEngine


def make_engine(lm, tables, **kw):
    """Every test engine goes through the typed ServingConfig surface
    (the legacy kwarg shim has its own dedicated tests in
    tests/test_policy.py)."""
    on_token = kw.pop("on_token", None)
    return ServingEngine(
        lm, tables, ServingConfig(**kw), on_token=on_token)


MAX_LEN = 40


@pytest.fixture(scope="module")
def deployed():
    return deploy_model("granite_3_2b", reduced=True, max_seq=MAX_LEN)


def _rand_pools(rng, n_pages, K, ps, hd):
    """Position-minor pools (n_pages + 1, K, hd, ps), the arena layout."""
    kp = jnp.asarray(
        rng.integers(-127, 128, size=(n_pages + 1, K, hd, ps)), jnp.int8
    )
    vp = jnp.asarray(
        rng.integers(-127, 128, size=(n_pages + 1, K, hd, ps)), jnp.int8
    )
    return kp, vp


def _gather_dense_acc_st(
    q, k_pool, v_pool, table, pos, *, score_scale, group, window=None
):
    """The model's write-then-gather attention math for (S, T) query
    rows (the flagged oracle path of layers/attention.apply_id): dense
    logical view + global causal softmax + one global int8 probability
    image -> int32 P.V acc.  `pos` is each row's START position; query
    row i sits at pos + i and, with a `window`, sees only its last
    `window` positions."""
    kv = _paged_kv_view(k_pool, table)
    vv = _paged_kv_view(v_pool, table)
    kh = jnp.repeat(kv, group, axis=1)
    vh = jnp.repeat(vv, group, axis=1)
    scores = jnp.einsum(
        "bhsd,bhtd->bhst", q, kh, preferred_element_type=jnp.int32,
    )
    S, T = q.shape[2], kh.shape[2]
    q_pos = pos[:, None, None, None] + jnp.arange(S)[None, None, :, None]
    k_pos = jnp.arange(T)[None, None, None, :]
    keep = k_pos <= q_pos
    if window is not None:
        keep = keep & (k_pos > q_pos - window)
    mask = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)
    logits = scores.astype(jnp.float32) * jnp.float32(score_scale) + mask
    probs = jax.nn.softmax(logits, axis=-1)
    s_p = jnp.round(probs * 127.0).astype(jnp.int8)
    return jnp.einsum(
        "bhst,bhtd->bhsd", s_p, vh, preferred_element_type=jnp.int32
    )


def _gather_dense_acc(q, k_pool, v_pool, table, pos, *, score_scale, group):
    """Single-token decode view of the oracle above."""
    return _gather_dense_acc_st(
        q[:, :, None, :], k_pool, v_pool, table, pos,
        score_scale=score_scale, group=group,
    )[:, :, 0, :]


# ---------------------------------------------------------------------
# kernel primitive: bit-exact vs the jnp mirror AND the gather oracle
# ---------------------------------------------------------------------
@pytest.mark.parametrize(
    "name,pps,ps,pos",
    [
        # every request's whole history inside one page
        ("single_page", 1, 8, [0, 3, 7]),
        # decode position exactly on a page boundary (first slot of a
        # fresh page) and exactly on the last slot of a page
        ("page_boundary", 4, 4, [4, 8, 7]),
        # last page only partially filled
        ("partial_last_page", 3, 4, [9, 5, 10]),
    ],
)
def test_kernel_exact_page_shapes(name, pps, ps, pos):
    rng = np.random.default_rng(11)
    B, H, K, hd = 3, 4, 2, 8
    n_pages = B * pps + 2
    kp, vp = _rand_pools(rng, n_pages, K, ps, hd)
    q = jnp.asarray(rng.integers(-127, 128, size=(B, H, hd)), jnp.int8)
    # each slot owns a disjoint shuffled set of physical pages
    perm = 1 + rng.permutation(n_pages)[: B * pps]
    table = jnp.asarray(perm.reshape(B, pps), jnp.int32)
    pos_v = jnp.asarray(pos, jnp.int32)
    kw = dict(score_scale=2e-4, group=H // K)
    got = paged_attention_decode_pallas(q, kp, vp, table, pos_v, **kw)
    mirror = ref.paged_attention_decode_ref(q, kp, vp, table, pos_v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(mirror))
    oracle = _gather_dense_acc(q, kp, vp, table, pos_v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_kernel_exact_recycled_and_inactive_rows():
    """A recycled slot whose table rows were reassigned (pages swapped
    between slots, PAGE_NULL tails) and rows parked at INACTIVE_POS:
    the kernel must agree with the mirror and the gather oracle on
    every row, garbage rows included (deterministic trash)."""
    rng = np.random.default_rng(12)
    B, H, K, hd, ps, pps = 4, 2, 2, 8, 4, 3
    n_pages = 6
    kp, vp = _rand_pools(rng, n_pages, K, ps, hd)
    table = jnp.asarray(
        [
            # slot 0: recycled — now owns pages a released slot used,
            # in a different order, with an unallocated tail
            [3, 1, PAGE_NULL],
            # slot 1: the other tenant of those physical pages
            [2, 5, 4],
            # slot 2: freshly admitted, single page allocated
            [6, PAGE_NULL, PAGE_NULL],
            # slot 3: free row parked at INACTIVE_POS (all trash)
            [PAGE_NULL, PAGE_NULL, PAGE_NULL],
        ],
        jnp.int32,
    )
    pos = jnp.asarray([6, 11, 0, INACTIVE_POS], jnp.int32)
    q = jnp.asarray(rng.integers(-127, 128, size=(B, H, hd)), jnp.int8)
    kw = dict(score_scale=5e-4, group=H // K)
    got = paged_attention_decode_pallas(q, kp, vp, table, pos, **kw)
    mirror = ref.paged_attention_decode_ref(q, kp, vp, table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(mirror))
    oracle = _gather_dense_acc(q, kp, vp, table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_kernel_traced_scale_under_scan():
    """score_scale arrives as a traced per-layer scalar under lax.scan
    (layer-stacked tables) — the kernel must accept it and stay exact."""
    rng = np.random.default_rng(13)
    B, H, K, hd, ps, pps = 2, 2, 1, 8, 4, 2
    kp, vp = _rand_pools(rng, 4, K, ps, hd)
    q = jnp.asarray(rng.integers(-127, 128, size=(B, H, hd)), jnp.int8)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 6], jnp.int32)
    scales = jnp.asarray([1e-3, 2e-3], jnp.float32)

    def body(carry, sc):
        out = paged_attention_decode_pallas(
            q, kp, vp, table, pos, score_scale=sc, group=H // K
        )
        return carry, out

    _, got = jax.jit(lambda s: jax.lax.scan(body, 0, s))(scales)
    for i, sc in enumerate(np.asarray(scales)):
        want = ref.paged_attention_decode_ref(
            q, kp, vp, table, pos, score_scale=float(sc), group=H // K
        )
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


# ---------------------------------------------------------------------
# the page walk ends at each row's last visible page
# ---------------------------------------------------------------------
WALK_PS, WALK_PPS = 4, 5


@pytest.mark.parametrize(
    "pos,s_q,window,want",
    [
        # decode on the last slot of page 1, and on the first of page 2
        (7, 1, None, (0, 2)),
        (8, 1, None, (0, 3)),
        # query rows 6..9 cross the edge of pages 1 and 2
        (6, 4, None, (0, 3)),
        # rows 9, 10 see positions 4..10 under a window of 6
        (9, 2, 6, (1, 3)),
        # the row's last slot, and parked rows: the whole row
        (19, 1, None, (0, 5)),
        (INACTIVE_POS, 1, None, (0, 5)),
        (INACTIVE_POS, 4, 6, (4, 5)),
    ],
)
def test_page_walk_bounds(pos, s_q, window, want):
    """`page_walk`'s (lo, hi) for one slot in the kernel's scalar form
    and in the engine's host form over a vector of slots."""
    lo, hi = page_walk(jnp.int32(pos), s_q, WALK_PS, WALK_PPS, window)
    assert (int(lo), int(hi)) == want
    lo, hi = page_walk(np.asarray([pos, 0], np.int32), s_q, WALK_PS,
                       WALK_PPS, window, xp=np)
    assert (int(np.broadcast_to(lo, (2,))[0]), int(hi[0])) == want


def _unpack_pool(pool, rq):
    """An int4-packed pool (n, K, hd/2, ps) -> its int8 image
    (n, K, hd, ps), page by page through the mirror's unpack."""
    n, K = pool.shape[:2]
    return jnp.stack([
        jnp.stack([ref.kv4_unpack_page_ref(pool[p, kh].T, rq, kh).T
                   for kh in range(K)])
        for p in range(n)
    ])


@pytest.mark.parametrize(
    "name,s_q,pos,window,kv_bits",
    [
        # short decode rows after a whole-row walk (row 0); their
        # tails hold a stale real page of extreme K/V (all 127)
        ("stale_tail", 1, [19, 2, 9, 6], None, 8),
        # decode on the last slot of a page and on the first of the next
        ("page_edges", 1, [19, 3, 4, 7], None, 8),
        # query rows that cross a page edge
        ("chunk_crosses_edge", 3, [17, 2, 6, 3], None, 8),
        # a sliding window cuts the walk at both ends
        ("window_both_ends", 2, [18, 9, 13, 5], 6, 8),
        # int4-packed pools with unallocated (PAGE_NULL) tails
        ("int4_null_tail", 1, [19, 5, 2, 10], None, 4),
        # a parked row ahead of short rows
        ("inactive_row", 2, [INACTIVE_POS, 5, 0, 11], None, 8),
    ],
)
def test_kernel_exact_walk_ends(name, s_q, pos, window, kv_bits):
    """Each row's walk stops at its last visible page (`page_walk`):
    neither the pages of its table tail nor the logits a longer row
    left in the kernel's scratch may reach its softmax.  Kernel ==
    gather oracle, and == mirror where the mirror has the mask (no
    window), at tolerance 0 on every row, parked rows included."""
    rng = np.random.default_rng(61)
    B, H, K, hd, ps, pps = 4, 4, 2, 8, WALK_PS, WALK_PPS
    n_live = B * pps
    hd_store = hd // 2 if kv_bits == 4 else hd
    stale = n_live + 1  # a page another tenant wrote last
    pools = []
    for _ in range(2):
        p = rng.integers(-127, 128, size=(n_live + 2, K, hd_store, ps))
        p[stale] = 127
        pools.append(jnp.asarray(p, jnp.int8))
    kp, vp = pools
    pos_v = np.asarray(pos, np.int32)
    _, hi = page_walk(pos_v, s_q, ps, pps, window, xp=np)
    table = np.where(np.arange(pps)[None, :] < hi[:, None],
                     1 + rng.permutation(n_live).reshape(B, pps),
                     PAGE_NULL if kv_bits == 4 else stale)
    table[pos_v == INACTIVE_POS] = PAGE_NULL
    table = jnp.asarray(table, jnp.int32)
    q = jnp.asarray(
        rng.integers(-127, 128, size=(B, H, s_q, hd)), jnp.int8
    )
    kw = dict(score_scale=4e-4, group=H // K)
    if kv_bits == 4:
        eps4 = rng.uniform(1.0, 20.0, size=K)
        kw["k_rq"] = _kv4_operand(make_rqt(eps4, 1.0, acc_bound=8.0), K)
        kw["v_rq"] = _kv4_operand(
            make_rqt(np.roll(eps4, 1), 1.0, acc_bound=8.0), K)
    pos_v = jnp.asarray(pos_v)
    got = paged_attention_pallas(q, kp, vp, table, pos_v, window=window,
                                 **kw)
    if window is None:
        mirror = ref.paged_attention_ref(q, kp, vp, table, pos_v, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(mirror))
    if kv_bits == 4:
        kp = _unpack_pool(kp, kw.pop("k_rq"))
        vp = _unpack_pool(vp, kw.pop("v_rq"))
    oracle = _gather_dense_acc_st(q, kp, vp, table, pos_v, window=window,
                                  **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


# ---------------------------------------------------------------------
# unified (S, T) kernel primitive (ISSUE 9): multi-token query rows
# ---------------------------------------------------------------------
@pytest.mark.parametrize(
    "name,pps,ps,s_q,start",
    [
        # a whole chunk inside one page
        ("chunk_in_page", 2, 8, 4, [0, 2, 4]),
        # chunk straddling a page boundary mid-row-range
        ("chunk_crosses_page", 3, 4, 6, [2, 0, 5]),
        # chunk starting exactly on a page boundary
        ("chunk_on_boundary", 3, 4, 4, [4, 8, 0]),
        # S == page_size: rows tile pages exactly
        ("chunk_is_page", 3, 4, 4, [0, 4, 4]),
    ],
)
def test_kernel_exact_multi_token_rows(name, pps, ps, s_q, start):
    """The unified kernel's (S, T) causal path: every query row i of
    every slot attends to positions <= start + i, one global softmax
    per row (no per-block requant), bit-exact vs the jnp mirror and
    the dense gather oracle."""
    rng = np.random.default_rng(31)
    B, H, K, hd = 3, 4, 2, 8
    n_pages = B * pps + 2
    kp, vp = _rand_pools(rng, n_pages, K, ps, hd)
    q = jnp.asarray(
        rng.integers(-127, 128, size=(B, H, s_q, hd)), jnp.int8
    )
    perm = 1 + rng.permutation(n_pages)[: B * pps]
    table = jnp.asarray(perm.reshape(B, pps), jnp.int32)
    pos_v = jnp.asarray(start, jnp.int32)
    kw = dict(score_scale=2e-4, group=H // K)
    got = paged_attention_pallas(q, kp, vp, table, pos_v, **kw)
    mirror = ref.paged_attention_ref(q, kp, vp, table, pos_v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(mirror))
    oracle = _gather_dense_acc_st(q, kp, vp, table, pos_v, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_kernel_exact_mixed_ragged_batch():
    """The unified dispatch's ragged row mix in one (B, S) batch:
    a chunk row mid-prompt, a decode-like row (only row 0
    meaningful, starting at its last position), a fresh row at start
    0, and a free row parked at INACTIVE_POS — every row bit-exact vs
    mirror and oracle, garbage rows included (deterministic trash)."""
    rng = np.random.default_rng(32)
    B, H, K, hd, ps, pps, s_q = 4, 2, 2, 8, 4, 3, 4
    n_pages = 6
    kp, vp = _rand_pools(rng, n_pages, K, ps, hd)
    table = jnp.asarray(
        [
            [3, 1, PAGE_NULL],
            [2, 5, 4],
            [6, PAGE_NULL, PAGE_NULL],
            [PAGE_NULL, PAGE_NULL, PAGE_NULL],
        ],
        jnp.int32,
    )
    # slot 0: chunk at offset 4; slot 1: decode-like at position 7;
    # slot 2: first chunk of a fresh prompt; slot 3: parked
    pos = jnp.asarray([4, 7, 0, INACTIVE_POS], jnp.int32)
    q = jnp.asarray(
        rng.integers(-127, 128, size=(B, H, s_q, hd)), jnp.int8
    )
    kw = dict(score_scale=5e-4, group=H // K)
    got = paged_attention_pallas(q, kp, vp, table, pos, **kw)
    mirror = ref.paged_attention_ref(q, kp, vp, table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(mirror))
    oracle = _gather_dense_acc_st(q, kp, vp, table, pos, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
    # the S = 1 decode wrapper is literally the S-wide kernel's row 0
    dec = paged_attention_decode_pallas(q[:, :, 0], kp, vp, table, pos,
                                        **kw)
    np.testing.assert_array_equal(
        np.asarray(dec), np.asarray(got[:, :, 0])
    )


# ---------------------------------------------------------------------
# engine-level: kernel == gather oracle == SlotArena, token for token
# ---------------------------------------------------------------------
def _run(lm, tables, specs, prompts, *, paged, paged_kernel=None,
         page_size=8, n_slots=3, max_len=MAX_LEN):
    eng = make_engine(
        lm, tables, n_slots=n_slots, max_len=max_len, paged=paged,
        page_size=page_size, paged_kernel=paged_kernel,
        scheduler=SchedulerConfig(
            max_prefills_per_step=2, prefill_bucket=8
        ),
    )
    ids = []
    for (p, g), prompt in zip(specs, prompts):
        ids.append(eng.submit(prompt, max_new_tokens=g))
        eng.step()
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert len(done) == len(specs)
    return [done[rid].tokens for rid in ids], eng


def test_engine_kernel_vs_gather_vs_slot_tokens(deployed):
    """Ragged staggered workload engineered to cross page boundaries
    mid-decode, finish inside partial pages, fit single pages, and
    recycle slots (9 requests on 3 slots): the fused-kernel engine,
    the gather-oracle engine, and the contiguous SlotArena engine must
    agree token for token."""
    lm, tables = deployed
    # page_size 8: prompts of 8/16 land decode on page boundaries;
    # P + G inside one page for the (3, 3) request; partial last pages
    # for the rest; 9 requests on 3 slots force recycling
    specs = [(5, 7), (8, 6), (16, 8), (3, 3), (20, 6), (12, 9),
             (7, 2), (15, 5), (9, 12)]
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    kernel_tokens, eng = _run(lm, tables, specs, prompts, paged=True)
    assert eng.paged_kernel
    gather_tokens, eng2 = _run(lm, tables, specs, prompts, paged=True,
                               paged_kernel=False)
    assert not eng2.paged_kernel
    slot_tokens, _ = _run(lm, tables, specs, prompts, paged=False)
    assert kernel_tokens == gather_tokens
    assert kernel_tokens == slot_tokens


def test_engine_kernel_vs_lockstep_single_page(deployed):
    """Single-page requests (P + G <= page_size): kernel engine ==
    lockstep serve_batch token for token."""
    lm, tables = deployed
    rng = np.random.default_rng(22)
    P, G, B = 4, 4, 3
    prompts = rng.integers(0, lm.cfg.vocab, size=(B, P))
    ref_toks = np.asarray(
        serve_batch(lm, tables, jnp.asarray(prompts, jnp.int32), G)
    )
    eng = make_engine(
        lm, tables, n_slots=B, max_len=P + G, paged=True, page_size=8,
        scheduler=SchedulerConfig(max_prefills_per_step=B,
                                  prefill_bucket=8),
    )
    ids = [eng.submit(prompts[i], max_new_tokens=G) for i in range(B)]
    got = {c.req_id: c.tokens for c in eng.run_until_drained()}
    for i, rid in enumerate(ids):
        assert got[rid] == list(ref_toks[i]), f"slot {i} diverged"


def test_no_dense_gather_in_kernel_decode(deployed):
    """The fused decode must never call _paged_kv_view (the dense
    logical gather) — only the flagged oracle path may.  Prefill runs
    whole-prompt (prefill_chunk=0) so the only traced paged-cache
    consumer is the decode step itself; jit traces once, and the spy
    records every trace-time gather."""
    import repro.layers.attention as attn_mod

    lm, tables = deployed
    calls = []
    orig = attn_mod._paged_kv_view

    def spy(pool, table):
        calls.append(pool.shape)
        return orig(pool, table)

    def serve_one(paged_kernel):
        eng = make_engine(
            lm, tables, n_slots=2, max_len=16, paged=True, page_size=8,
            paged_kernel=paged_kernel,
            scheduler=SchedulerConfig(prefill_bucket=8,
                                      prefill_chunk=0),
        )
        calls.clear()
        attn_mod._paged_kv_view = spy
        try:
            eng.submit(np.arange(1, 5), max_new_tokens=3)
            eng.run_until_drained()
        finally:
            attn_mod._paged_kv_view = orig
        return list(calls)

    assert serve_one(True) == [], (
        "kernel decode materialized the dense KV view"
    )
    # the oracle engine DOES gather (the flag keeps the path alive)
    assert serve_one(False), "gather oracle path no longer gathers"


def test_no_dense_gather_engine_wide_mixed(deployed):
    """ISSUE 9 engine-wide invariant: with chunked prefill ON (the
    default), a mixed prefill+decode step is ONE unified kernel
    dispatch — no dense logical KV gather ANYWHERE on the default
    paged path, prefill chunks included, sync and async alike.  The
    staggered workload (4 requests on 2 slots, submit interleaved
    with steps) forces steps where one slot decodes while the other
    chunks its prompt.  The spy records every trace-time gather; the
    flagged oracle engine must still gather, and must still agree
    token for token."""
    import repro.layers.attention as attn_mod

    lm, tables = deployed
    rng = np.random.default_rng(23)
    specs = [(18, 6), (5, 9), (12, 4), (9, 7)]
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    calls = []
    orig = attn_mod._paged_kv_view

    def spy(pool, table):
        calls.append(pool.shape)
        return orig(pool, table)

    def serve(paged_kernel, depth):
        eng = make_engine(
            lm, tables, n_slots=2, max_len=MAX_LEN, paged=True,
            page_size=8, paged_kernel=paged_kernel,
            dispatch_depth=depth,
            scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=8,
                                      max_prefills_per_step=2),
        )
        calls.clear()
        attn_mod._paged_kv_view = spy
        try:
            ids = []
            for (p, g), prompt in zip(specs, prompts):
                ids.append(eng.submit(prompt, max_new_tokens=g))
                eng.step()
            done = {c.req_id: c.tokens for c in eng.run_until_drained()}
        finally:
            attn_mod._paged_kv_view = orig
        return [done[r] for r in ids], list(calls)

    kernel_toks, kernel_calls = serve(True, depth=0)
    assert kernel_calls == [], (
        "default paged path materialized the dense KV view in a mixed "
        f"prefill+decode run: {kernel_calls}"
    )
    async_toks, async_calls = serve(True, depth=1)
    assert async_calls == [], (
        "async dispatch materialized the dense KV view"
    )
    gather_toks, gather_calls = serve(False, depth=0)
    assert gather_calls, "gather oracle path no longer gathers"
    assert kernel_toks == gather_toks
    assert kernel_toks == async_toks


def test_engine_refuses_unaligned_pages_on_tpu(deployed, monkeypatch):
    """On a TPU the kernel addresses logits at lane offset j * page_size,
    so the engine refuses a page size that is not a multiple of 128 —
    it never switches to the gather path instead.  The gather path
    itself compiles at any page size."""
    from repro.kernels.paged_attention import TPU_PAGE_ALIGN

    class Chip:
        platform = "tpu"

    lm, tables = deployed
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(ValueError, match=f"multiple of {TPU_PAGE_ALIGN}"):
        make_engine(lm, tables, n_slots=2, max_len=MAX_LEN, paged=True,
                    page_size=16)
    monkeypatch.undo()
    make_engine(lm, tables, n_slots=2, max_len=MAX_LEN, paged=True,
                page_size=16)  # the CPU interpreter takes any page size
