"""Continuous-batching serving engine (repro.serving) tests.

Acceptance (ISSUE 1): lockstep parity token-for-token; a ragged
workload (>= 8 requests, >= 3 distinct prompt lengths, staggered
arrivals, slot reuse) drains completely in the ID representation with
zero float tensors in caches or logits.

Acceptance (ISSUE 2, paged KV arena): the paged engine matches the
lockstep oracle AND the contiguous SlotArena engine token-for-token on
a ragged workload; admission is gated on the page budget (not free
slots); freed pages are recycled without stale-token leakage; the
integer-only invariant holds on every page.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rep import Rep
from repro.launch.serve import deploy_model, serve_batch
from repro.serving import (
    PAGE_NULL, PagedArena, SchedulerConfig, ServingConfig,
    ServingEngine, SlotArena,
    assert_integer_caches, float_cache_leaves,
)

MAX_LEN = 40


def make_engine(lm, tables, **kw):
    """Every test engine goes through the typed ServingConfig surface."""
    on_token = kw.pop("on_token", None)
    return ServingEngine(
        lm, tables, ServingConfig(**kw), on_token=on_token)


@pytest.fixture(scope="module")
def deployed():
    return deploy_model("granite_3_2b", reduced=True, max_seq=MAX_LEN)


@pytest.fixture(scope="module")
def deployed_ssm():
    return deploy_model("falcon_mamba_7b", reduced=True, max_seq=12)


# ---------------------------------------------------------------------
# per-slot position primitives (no model needed)
# ---------------------------------------------------------------------
def test_mask_vector_matches_scalar_rows():
    from repro.layers.attention import _bool_mask, _mask

    T = 12
    pos = jnp.asarray([0, 3, 7, 11])
    mv = _mask(1, T, pos)                      # (B,1,1,T)
    bv = _bool_mask(1, T, pos)
    assert mv.shape == (4, 1, 1, T)
    for b, p in enumerate([0, 3, 7, 11]):
        ms = _mask(1, T, p)                    # (1,T)
        assert np.array_equal(np.asarray(mv[b, 0]), np.asarray(ms))
        assert np.array_equal(np.asarray(bv[b, 0]),
                              np.asarray(_bool_mask(1, T, p)))


def test_cache_write_per_slot_offsets():
    from repro.layers.attention import _cache_write

    B, K, T, hd = 4, 2, 10, 3
    cache = jnp.zeros((B, K, T, hd), jnp.int8)
    new = jnp.arange(1, B + 1, dtype=jnp.int8).reshape(B, 1, 1, 1)
    new = jnp.broadcast_to(new, (B, K, 1, hd))
    pos = jnp.asarray([0, 2, 5, 9])
    out = np.asarray(_cache_write(cache, new, pos))
    for b, p in enumerate([0, 2, 5, 9]):
        assert (out[b, :, p] == b + 1).all()
        rest = np.delete(out[b], p, axis=1)
        assert (rest == 0).all()


def test_rope_vector_positions_match_scalar():
    from repro.layers.rope import apply_rope_int, rope_tables_int

    hd, B, H = 8, 3, 2
    rot, cos_q, sin_q = rope_tables_int(hd, 32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, size=(B, H, 1, hd)), jnp.int8)
    pos = jnp.asarray([1, 9, 30])
    yv = np.asarray(apply_rope_int(x, cos_q, sin_q, pos[:, None], rot))
    for b, p in enumerate([1, 9, 30]):
        ys = apply_rope_int(x[b:b + 1], cos_q, sin_q,
                            jnp.asarray([p]), rot)
        assert np.array_equal(yv[b], np.asarray(ys)[0])


# ---------------------------------------------------------------------
# slot arena lifecycle
# ---------------------------------------------------------------------
def test_slot_arena_lifecycle(deployed):
    lm, _ = deployed
    arena = SlotArena(lm, n_slots=3, max_len=16)
    assert arena.n_free == 3
    s0 = arena.alloc(req_id=10, prompt_len=4)
    s1 = arena.alloc(req_id=11, prompt_len=7)
    assert arena.n_free == 1 and s0 != s1
    assert arena.owner[s0] == 10 and arena.lengths[s1] == 7
    arena.release(s0)
    assert arena.n_free == 2 and arena.owner[s0] is None
    s2 = arena.alloc(req_id=12, prompt_len=2)   # slot reuse
    assert s2 == s0
    arena.release(s1)
    with pytest.raises(RuntimeError):
        arena.release(s1)                        # double release
    arena.alloc(13, 1), arena.alloc(14, 1)
    with pytest.raises(RuntimeError):
        arena.alloc(15, 1)                       # exhausted


def test_integer_cache_invariant(deployed):
    lm, tables = deployed
    arena = SlotArena(lm, n_slots=2, max_len=16)
    assert float_cache_leaves(arena.caches) == []
    assert_integer_caches(arena.caches)          # must not raise
    # ID logits are int32 end-to-end (no dequantization anywhere)
    prompts = jnp.zeros((2, 4), jnp.int32)
    logits, caches = lm.prefill(tables, prompts,
                                lm.init_caches(2, 16, Rep.ID))
    assert logits.dtype == jnp.int32
    assert float_cache_leaves(caches) == []
    # FP caches would trip the assertion
    with pytest.raises(AssertionError):
        assert_integer_caches(lm.init_caches(1, 8, Rep.FP))


# ---------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------
def test_parity_with_lockstep_serve_batch(deployed):
    """Simultaneous same-length requests == old lockstep serve_batch,
    token for token (including a prompt length that exercises the
    bucket-padded prefill gather)."""
    lm, tables = deployed
    rng = np.random.default_rng(1)
    for P in (8, 6):  # 6: padded to the 8-bucket; 8: exact bucket
        G, B = 6, 4
        prompts = rng.integers(0, lm.cfg.vocab, size=(B, P))
        ref = np.asarray(serve_batch(
            lm, tables, jnp.asarray(prompts, jnp.int32), G))
        eng = make_engine(
            lm, tables, n_slots=B, max_len=P + G,
            scheduler=SchedulerConfig(max_prefills_per_step=B,
                                      prefill_bucket=8))
        ids = [eng.submit(prompts[i], max_new_tokens=G) for i in range(B)]
        got = {c.req_id: c.tokens for c in eng.run_until_drained()}
        for i, rid in enumerate(ids):
            assert got[rid] == list(ref[i]), f"P={P} slot {i} diverged"


@pytest.mark.parametrize("paged", [False, True])
def test_parity_ssm_family_exact_prefill(deployed_ssm, paged):
    """SSM recurrent state integrates every prefilled position, so the
    engine must prefill at exact prompt length (no bucket padding) —
    parity with lockstep pins it, at a length that WOULD be padded.
    The paged arena keeps the (sequence-axis-free) SSM state
    slot-resident and only pages attention-style KV leaves; parity
    must hold either way."""
    lm, tables = deployed_ssm
    rng = np.random.default_rng(4)
    P, G, B = 5, 4, 2   # P=5 would pad to 8 under the dense bucketing
    prompts = rng.integers(0, lm.cfg.vocab, size=(B, P))
    ref = np.asarray(serve_batch(
        lm, tables, jnp.asarray(prompts, jnp.int32), G))
    eng = make_engine(
        lm, tables, n_slots=B, max_len=P + G, paged=paged, page_size=4,
        scheduler=SchedulerConfig(max_prefills_per_step=B,
                                  prefill_bucket=8))
    assert not eng._bucketed_prefill
    ids = [eng.submit(prompts[i], max_new_tokens=G) for i in range(B)]
    got = {c.req_id: c.tokens for c in eng.run_until_drained()}
    for i, rid in enumerate(ids):
        assert got[rid] == list(ref[i]), f"ssm slot {i} diverged"


def test_ragged_arrivals_drain(deployed):
    """>= 8 requests, >= 3 distinct prompt lengths, staggered arrivals,
    fewer slots than requests (forced queueing + slot reuse): every
    request completes with exactly its requested token budget."""
    lm, tables = deployed
    rng = np.random.default_rng(2)
    streamed = {}
    eng = make_engine(
        lm, tables, n_slots=3, max_len=MAX_LEN,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8),
        on_token=lambda rid, t: streamed.setdefault(rid, []).append(t))
    specs = [(5, 7), (12, 4), (9, 10), (3, 3), (20, 6), (12, 9),
             (5, 2), (17, 5), (9, 12)]
    assert len(specs) >= 8
    assert len({p for p, _ in specs}) >= 3
    ids = []
    for p, g in specs:
        ids.append(eng.submit(rng.integers(0, lm.cfg.vocab, size=(p,)),
                              max_new_tokens=g))
        eng.step()                      # staggered arrival
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert len(done) == len(specs)
    for rid, (p, g) in zip(ids, specs):
        c = done[rid]
        assert c.prompt_len == p
        assert c.n_generated == g and c.finish_reason == "length"
        assert streamed[rid] == c.tokens          # streaming == record
        assert c.ttft >= 0.0 and c.latency >= c.ttft
    s = eng.stats()
    assert s["n_completed"] == len(specs)
    assert s["n_generated"] == sum(g for _, g in specs)
    assert 0.0 < s["mean_occupancy"] <= 1.0
    # integer-only invariant held for the whole run
    assert float_cache_leaves(eng.arena.caches) == []


def test_stop_token_finishes_early(deployed):
    lm, tables = deployed
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, lm.cfg.vocab, size=(6,))
    eng = make_engine(lm, tables, n_slots=1, max_len=24,
                        scheduler=SchedulerConfig(prefill_bucket=8))
    rid = eng.submit(prompt, max_new_tokens=10)
    (full,) = eng.run_until_drained()
    assert full.n_generated == 10
    stop = full.tokens[3]
    eng2 = make_engine(lm, tables, n_slots=1, max_len=24,
                         scheduler=SchedulerConfig(prefill_bucket=8))
    eng2.submit(prompt, max_new_tokens=10, stop_token=stop)
    (early,) = eng2.run_until_drained()
    assert early.finish_reason == "stop"
    assert early.tokens == full.tokens[:early.n_generated]
    assert early.tokens[-1] == stop
    assert early.n_generated <= 4  # greedy is deterministic


def test_submit_validation(deployed):
    lm, tables = deployed
    eng = make_engine(lm, tables, n_slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(12, np.int32), max_new_tokens=8)  # 20 > 16
    with pytest.raises(ValueError):
        eng.submit(np.zeros(0, np.int32), max_new_tokens=1)   # empty


# ---------------------------------------------------------------------
# paged KV arena (ISSUE 2)
# ---------------------------------------------------------------------
def test_paged_write_gather_matches_contiguous():
    """Primitive equivalence: a column written through a page table and
    gathered back == the contiguous per-slot one-hot write, at every
    position the table owns."""
    from repro.layers.attention import (
        _cache_write, _paged_column_write, _paged_kv_view,
    )

    B, K, hd, ps, pps = 3, 2, 4, 4, 3
    T = pps * ps
    rng = np.random.default_rng(0)
    dense = jnp.asarray(
        rng.integers(-128, 128, size=(B, K, T, hd)), jnp.int8)
    # slot b owns pages [1 + b*pps, ...); rebuild the pool from dense
    table = jnp.asarray(
        1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    pool = jnp.zeros((B * pps + 1, K, hd, ps), jnp.int8)
    pool = pool.at[table.reshape(-1)].set(
        jnp.transpose(dense.reshape(B, K, pps, ps, hd), (0, 2, 1, 4, 3))
        .reshape(B * pps, K, hd, ps))
    np.testing.assert_array_equal(
        np.asarray(_paged_kv_view(pool, table)), np.asarray(dense))

    new = jnp.asarray(rng.integers(-128, 128, size=(B, K, 1, hd)), jnp.int8)
    pos = jnp.asarray([0, 5, 11])
    ref = np.asarray(_cache_write(dense, new, pos))
    got_pool = _paged_column_write(pool, new, pos, table)
    np.testing.assert_array_equal(
        np.asarray(_paged_kv_view(got_pool, table)), ref)


def test_paged_arena_lifecycle(deployed):
    """Budget commitment, on-demand allocation, wholesale recycling."""
    lm, _ = deployed
    arena = PagedArena(lm, n_slots=3, max_len=16, page_size=4, n_pages=6)
    assert arena.pages_per_slot == 4
    # P=5, G=6 -> writes [0, 10): commits ceil(10/4) = 3 pages, but
    # only ceil(5/4) = 2 are allocated at admission
    assert arena.can_admit(5, 11)
    s0 = arena.alloc(10, 5, 11)
    assert arena.committed_pages == 3 and arena.pages_in_use == 2
    assert int(arena.page_table[s0, 2]) == PAGE_NULL
    arena.touch(s0, 5)          # still inside page 1: no-op
    assert arena.pages_in_use == 2
    arena.touch(s0, 8)          # crosses into block 2: allocates
    assert arena.pages_in_use == 3
    assert int(arena.page_table[s0, 2]) != PAGE_NULL
    # remaining budget: 3 of 6 pages committed -> a 4-page request
    # must wait even though 2 slots are free
    assert not arena.can_admit(9, 16)
    assert arena.can_admit(5, 11)
    s1 = arena.alloc(11, 5, 11)
    assert arena.committed_pages == 6
    assert not arena.can_admit(1, 2)    # budget exhausted, slot free
    assert arena.n_free == 1
    arena.release(s0)
    assert arena.committed_pages == 3 and arena.pages_in_use == 2
    assert all(p == PAGE_NULL for p in arena.page_table[s0])
    with pytest.raises(RuntimeError):
        arena.release(s0)               # double release
    arena.release(s1)
    assert arena.pages_in_use == 0 and arena.committed_pages == 0
    # a single request larger than the whole pool can never be admitted
    # (ceil((30 - 1) / 4) = 8 pages > the 6-page pool)
    with pytest.raises(ValueError):
        arena.check_request(9, 30)


def test_paged_parity_with_lockstep(deployed):
    """Simultaneous same-length requests through the paged engine ==
    lockstep serve_batch, token for token (acceptance: ISSUE 2)."""
    lm, tables = deployed
    rng = np.random.default_rng(1)
    P, G, B = 8, 6, 4
    prompts = rng.integers(0, lm.cfg.vocab, size=(B, P))
    ref = np.asarray(serve_batch(
        lm, tables, jnp.asarray(prompts, jnp.int32), G))
    eng = make_engine(
        lm, tables, n_slots=B, max_len=P + G, paged=True, page_size=4,
        scheduler=SchedulerConfig(max_prefills_per_step=B,
                                  prefill_bucket=8))
    ids = [eng.submit(prompts[i], max_new_tokens=G) for i in range(B)]
    got = {c.req_id: c.tokens for c in eng.run_until_drained()}
    for i, rid in enumerate(ids):
        assert got[rid] == list(ref[i]), f"slot {i} diverged"
    assert float_cache_leaves(eng.arena.caches) == []
    assert_integer_caches(eng.arena.decode_view())  # incl. page tables


def test_paged_parity_with_slot_engine_ragged(deployed):
    """The paged engine must reproduce the contiguous SlotArena engine
    token-for-token on a ragged prompt/budget workload with staggered
    arrivals (acceptance: ISSUE 2), with the integer-only invariant
    holding on every page."""
    lm, tables = deployed
    specs = [(5, 7), (12, 4), (9, 10), (3, 3), (20, 6), (12, 9),
             (5, 2), (17, 5), (9, 12)]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]

    def run(paged):
        eng = make_engine(
            lm, tables, n_slots=3, max_len=MAX_LEN, paged=paged,
            page_size=8,
            scheduler=SchedulerConfig(max_prefills_per_step=2,
                                      prefill_bucket=8))
        ids = []
        for (p, g), prompt in zip(specs, prompts):
            ids.append(eng.submit(prompt, max_new_tokens=g))
            eng.step()                  # staggered arrival
        done = {c.req_id: c for c in eng.run_until_drained()}
        return [done[rid].tokens for rid in ids], eng

    slot_tokens, _ = run(paged=False)
    paged_tokens, eng = run(paged=True)
    assert paged_tokens == slot_tokens
    assert float_cache_leaves(eng.arena.caches) == []
    assert_integer_caches(eng.arena.decode_view())
    s = eng.stats()
    assert s["arena"] == "paged"
    # short requests never materialized the worst case
    assert 0 < s["max_pages_in_use"] <= s["n_pages"]
    assert s["max_pages_in_use"] < 3 * (MAX_LEN // s["page_size"])


def test_page_exhaustion_backpressure(deployed):
    """Admission is gated on the page budget, not free slots: with a
    2-page pool and 6 free slots, three 2-page requests must run
    strictly one at a time — and all still complete (preemption-free
    backpressure, FCFS head-of-line)."""
    lm, tables = deployed
    rng = np.random.default_rng(5)
    eng = make_engine(
        lm, tables, n_slots=6, max_len=32, paged=True, page_size=8,
        n_pages=2,
        scheduler=SchedulerConfig(max_prefills_per_step=4,
                                  prefill_bucket=8))
    ids = [eng.submit(rng.integers(0, lm.cfg.vocab, size=(6,)),
                      max_new_tokens=8) for _ in range(3)]
    eng.step()
    assert len(eng.active) == 1         # pages, not slots, gate entry
    assert eng.sched.n_pending == 2
    assert eng.arena.n_free == 5        # slots were never the limit
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert len(done) == 3
    assert eng.stats()["max_active"] == 1
    for rid in ids:
        assert done[rid].n_generated == 8
        assert done[rid].finish_reason == "length"


def test_page_recycling_no_stale_leakage(deployed):
    """Pages freed by a completed request are reused by the next one,
    and the recycled contents never leak: the tenant's tokens match a
    fresh engine serving the same request on untouched pages."""
    lm, tables = deployed
    rng = np.random.default_rng(6)
    prompt_a = rng.integers(0, lm.cfg.vocab, size=(11,))
    prompt_b = rng.integers(0, lm.cfg.vocab, size=(7,))

    def run_tracking_pages(eng, prompt, gen):
        rid = eng.submit(prompt, max_new_tokens=gen)
        pages = set()
        while eng.sched.n_pending or eng.active:
            eng.step()
            pages |= {int(p) for p in np.unique(eng.arena.page_table)}
        (c,) = [c for c in eng.completed if c.req_id == rid]
        return c.tokens, pages - {PAGE_NULL}

    eng = make_engine(
        lm, tables, n_slots=1, max_len=24, paged=True, page_size=4,
        n_pages=6, scheduler=SchedulerConfig(prefill_bucket=8))
    tokens_a, pages_a = run_tracking_pages(eng, prompt_a, 8)
    assert pages_a
    assert eng.arena.pages_in_use == 0          # all recycled
    tokens_b, pages_b = run_tracking_pages(eng, prompt_b, 9)
    assert pages_a & pages_b                    # physical reuse happened

    fresh = make_engine(
        lm, tables, n_slots=1, max_len=24, paged=True, page_size=4,
        n_pages=6, scheduler=SchedulerConfig(prefill_bucket=8))
    tokens_b_fresh, _ = run_tracking_pages(fresh, prompt_b, 9)
    assert tokens_b == tokens_b_fresh           # no stale-token leakage
    assert tokens_a != tokens_b                 # the workloads differ


# ---------------------------------------------------------------------
# batched + chunked prefill (ISSUE 3)
# ---------------------------------------------------------------------
def _run_engine(lm, tables, specs, prompts, *, chunk, paged,
                max_len=MAX_LEN, n_slots=3, stagger=True,
                max_chunks=None):
    eng = make_engine(
        lm, tables, n_slots=n_slots, max_len=max_len, paged=paged,
        page_size=8,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8, prefill_chunk=chunk,
                                  max_chunks_per_step=max_chunks))
    assert eng._prefill_mode == ("chunked" if chunk else "bucketed")
    ids = []
    for (p, g), prompt in zip(specs, prompts):
        ids.append(eng.submit(prompt, max_new_tokens=g))
        if stagger:
            eng.step()
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert len(done) == len(specs)
    return [done[rid].tokens for rid in ids], eng


@pytest.mark.parametrize("paged", [False, True])
def test_chunked_matches_whole_and_lockstep(deployed, paged):
    """Chunked prefill must be token-for-token identical to the
    whole-prompt (bucketed) path AND the lockstep serve_batch oracle —
    dense family, slot and paged arenas (acceptance: ISSUE 3).  Chunk
    size 4 forces multi-chunk prefills on every prompt length here,
    including one exactly on the chunk boundary (8) and one 1-token
    prompt."""
    lm, tables = deployed
    specs = [(8, 6), (7, 4), (1, 5), (12, 6), (8, 3), (16, 8), (1, 2),
             (9, 7)]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    whole_tokens, _ = _run_engine(lm, tables, specs, prompts, chunk=0,
                                  paged=paged)
    chunk_tokens, eng = _run_engine(lm, tables, specs, prompts, chunk=4,
                                    paged=paged)
    assert chunk_tokens == whole_tokens
    assert float_cache_leaves(eng.arena.caches) == []
    assert_integer_caches(eng.arena.decode_view())
    # simultaneous same-length subset == lockstep serve_batch
    P, G, B = 8, 6, 3
    batch = np.stack([rng.integers(0, lm.cfg.vocab, size=(P,))
                      for _ in range(B)])
    ref = np.asarray(serve_batch(
        lm, tables, jnp.asarray(batch, jnp.int32), G))
    eng2 = make_engine(
        lm, tables, n_slots=B, max_len=P + G, paged=paged, page_size=4,
        scheduler=SchedulerConfig(max_prefills_per_step=B,
                                  prefill_bucket=8, prefill_chunk=4))
    ids = [eng2.submit(batch[i], max_new_tokens=G) for i in range(B)]
    got = {c.req_id: c.tokens for c in eng2.run_until_drained()}
    for i, rid in enumerate(ids):
        assert got[rid] == list(ref[i]), f"paged={paged} slot {i}"


def test_chunked_boundary_and_one_token_prompts(deployed):
    """Prompt lengths exactly on the chunk boundary (P == k*C) and
    1-token prompts: the final chunk's last-index gather must pick the
    true last prompt token in both the full-chunk and the maximally
    padded case."""
    lm, tables = deployed
    rng = np.random.default_rng(8)
    for P in (4, 8, 1):                      # C=4: full, 2-chunk, padded
        prompt = rng.integers(0, lm.cfg.vocab, size=(P,))
        ref = np.asarray(serve_batch(
            lm, tables, jnp.asarray(prompt[None], jnp.int32), 5))[0]
        (tokens,), eng = _run_engine(
            lm, tables, [(P, 5)], [prompt], chunk=4, paged=False,
            n_slots=1, stagger=False)
        assert tokens == list(ref), f"P={P} diverged"
        # the arena's written-length bookkeeping advanced chunk by chunk
        assert eng.arena.n_free == 1


def test_long_prompt_does_not_starve_decode(deployed):
    """A long prompt admitted while other slots decode must stream in
    chunk by chunk, with every decoding slot advancing one token per
    engine step throughout (the whole point of chunked prefill)."""
    lm, tables = deployed
    rng = np.random.default_rng(9)
    eng = make_engine(
        lm, tables, n_slots=3, max_len=MAX_LEN,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8, prefill_chunk=4))
    a = eng.submit(rng.integers(0, lm.cfg.vocab, size=(3,)),
                   max_new_tokens=30)
    b = eng.submit(rng.integers(0, lm.cfg.vocab, size=(4,)),
                   max_new_tokens=30)
    eng.step()                              # both short prompts decoding
    assert len(eng.active) == 2 and not eng.prefilling
    long_req = eng.submit(rng.integers(0, lm.cfg.vocab, size=(24,)),
                          max_new_tokens=4)
    n_chunk_steps = -(-24 // 4)
    before = {s.request.req_id: len(s.tokens)
              for s in eng.active.values()}
    for i in range(n_chunk_steps):
        eng.step()                          # long prompt still arriving
        assert long_req in [s.request.req_id
                            for s in eng.prefilling.values()] or i \
            == n_chunk_steps - 1
        for s in eng.active.values():
            if s.request.req_id in before:
                # decode advanced EVERY step while the chunk streamed
                assert len(s.tokens) == before[s.request.req_id] + i + 1
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert done[long_req].n_generated == 4
    for rid in (a, b):
        assert done[rid].n_generated == 30


def test_chunk_packing_fairness_cap(deployed):
    """plan_chunks packs FIFO and honors max_chunks_per_step; capped
    rows resume in later dispatches and every request still drains."""
    from repro.serving import PrefillState, Request, Scheduler

    sched = Scheduler(SchedulerConfig(prefill_chunk=4,
                                      max_chunks_per_step=2), 64)
    reqs = [Request(np.arange(1, 1 + p), 4) for p in (10, 4, 7)]
    states = [PrefillState(request=r, slot=i)
              for i, r in enumerate(reqs)]
    plan = sched.plan_chunks(states)
    assert [(st.slot, off, n) for st, off, n in plan] == \
        [(0, 0, 4), (1, 0, 4)]              # FIFO, capped at 2 rows
    states[0].offset = 8                    # mid-prefill: partial tail
    plan = sched.plan_chunks(states)
    assert plan[0][1:] == (8, 2)            # final chunk is partial
    # engine-level: the cap stretches prefill over more steps but every
    # request still completes with the same tokens
    lm, tables = deployed
    rng = np.random.default_rng(10)
    specs = [(12, 4), (9, 4), (16, 4)]
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    uncapped, _ = _run_engine(lm, tables, specs, prompts, chunk=4,
                              paged=False, stagger=False)
    capped, _ = _run_engine(lm, tables, specs, prompts, chunk=4,
                            paged=False, stagger=False, max_chunks=1)
    assert capped == uncapped


def test_paged_submit_validation(deployed):
    """A request whose own worst case exceeds the whole pool can never
    be admitted — reject at submit instead of deadlocking the queue."""
    lm, tables = deployed
    eng = make_engine(lm, tables, n_slots=2, max_len=32, paged=True,
                        page_size=8, n_pages=2)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(12, np.int32), max_new_tokens=12)  # 3 pages
    eng.submit(np.zeros(8, np.int32), max_new_tokens=8)        # 2 pages
    (c,) = eng.run_until_drained()
    assert c.n_generated == 8
