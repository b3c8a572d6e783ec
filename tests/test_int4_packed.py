"""Int4-packed KV arena tests (ISSUE 10, DESIGN.md §Serving
¶Sub-8-bit KV).

Pinned here:
  - pack/unpack roundtrip is EXACT over the full [-8, 7] range
    (exhaustively over all nibble pairs, and property-fuzzed over
    random shapes);
  - the packed `_paged_column_write` equals pack(unpacked write) on
    random ragged chunks including rows parked at INACTIVE_POS — the
    positional scatter is packing-oblivious because both nibbles of a
    cell belong to one token;
  - the packed fused kernel is bit-exact against its (S, T) jnp
    mirror (`kernels.ref.paged_attention_ref` with k_rq/v_rq),
    tolerance 0;
  - engine-level: fused kernel == write-then-gather oracle
    token-for-token at kv_bits=4 (lossy only vs the int8-KV run,
    never across read paths at fixed kv-bits);
  - the requant images bound the packed-cell reconstruction error by
    one int4 quantum;
  - config/engine/arena validation: kv_bits gating and packed pool
    geometry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.core.intmath import pack_int4, unpack_int4
from repro.core.requant import apply_rqt, make_rqt
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ref import paged_attention_ref
from repro.layers.attention import (
    INACTIVE_POS,
    _kv4_operand,
    _kv4_pack_image,
    _paged_column_write,
)
from repro.launch.serve import deploy_model
from repro.serving import (
    PagedArena,
    SchedulerConfig,
    ServingConfig,
    ServingEngine,
)

MAX_LEN = 40
PS = 8


@pytest.fixture(scope="module")
def deployed():
    return deploy_model("granite_3_2b", reduced=True, max_seq=MAX_LEN)


# ---------------------------------------------------------------------
# pack/unpack primitives
# ---------------------------------------------------------------------
def test_pack_unpack_roundtrip_exhaustive():
    """Every (lo, hi) nibble pair in [-8, 7]^2 — all 256 packed cells
    — roundtrips exactly."""
    lo, hi = np.meshgrid(np.arange(-8, 8), np.arange(-8, 8))
    x = np.stack([lo.ravel(), hi.ravel()], axis=-1).astype(np.int8)
    p = pack_int4(jnp.asarray(x))
    assert p.shape == (256, 1) and p.dtype == jnp.int8
    assert np.array_equal(np.asarray(unpack_int4(p)), x)


@settings(max_examples=20, deadline=None)
@given(
    shape=st.tuples(
        st.integers(1, 4), st.integers(1, 3), st.integers(1, 6),
        st.integers(1, 8),
    ),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_unpack_roundtrip_random(shape, seed):
    rng = np.random.default_rng(seed)
    shape = shape[:-1] + (2 * shape[-1],)  # even trailing axis
    x = rng.integers(-8, 8, size=shape).astype(np.int8)
    assert np.array_equal(
        np.asarray(unpack_int4(pack_int4(jnp.asarray(x)))), x
    )


def test_pack_rejects_odd_axis():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((2, 3), jnp.int8))


# ---------------------------------------------------------------------
# packed column write == pack(unpacked write)
# ---------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_packed_column_write_matches_pack_of_unpacked(seed):
    """The positional scatter commutes with nibble packing: writing
    packed values into a packed pool leaves exactly the packed image
    of the unpacked pool — for random ragged chunks, PAGE_NULL table
    entries, and rows parked at INACTIVE_POS."""
    rng = np.random.default_rng(seed)
    n_pages, K, ps, hd = 5, 2, 4, 8
    B, S = 3, int(rng.integers(1, 6))
    # position-minor pools (n_pages + 1, K, hd, ps): nibbles pack along hd
    pool8 = rng.integers(-8, 8, size=(n_pages + 1, K, hd, ps))
    pool8 = jnp.asarray(pool8.astype(np.int8))

    def pack_pool(p):
        return jnp.swapaxes(pack_int4(jnp.swapaxes(p, -1, -2)), -1, -2)

    pool4 = pack_pool(pool8)
    table = jnp.asarray(
        rng.integers(0, n_pages + 1, size=(B, 3)).astype(np.int32))
    pos = rng.integers(0, 3 * ps, size=(B,)).astype(np.int32)
    # park a random subset of rows
    parked = rng.random(B) < 0.4
    pos = jnp.asarray(np.where(parked, INACTIVE_POS, pos))
    new = rng.integers(-8, 8, size=(B, K, S, hd)).astype(np.int8)
    new = jnp.asarray(new)
    out8 = _paged_column_write(pool8, new, pos, table)
    out4 = _paged_column_write(pool4, pack_int4(new), pos, table)
    assert np.array_equal(np.asarray(out4), np.asarray(pack_pool(out8)))


# ---------------------------------------------------------------------
# requant image bounds
# ---------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_kv4_requant_roundtrip_error_bound(seed):
    """pack -> store -> unpack reconstructs every int8-image cell to
    within one int4 quantum (eps4), for random per-head quanta."""
    rng = np.random.default_rng(seed)
    K = int(rng.integers(1, 5))
    eps4 = np.maximum(rng.uniform(0.5, 25.0, size=K), 1.0)
    pack = make_rqt(1.0 / eps4, 1.0, qmin=-8, qmax=7, acc_bound=127.0)
    unpack = make_rqt(eps4, 1.0, acc_bound=8.0)
    x = rng.integers(-127, 128, size=(2, K, 3, 8)).astype(np.int64)
    # stay inside each head's calibrated range (|x| <= 7 * eps4):
    # beyond it the int4 grid saturates by design, like any
    # calibrated activation quantizer
    lim = np.minimum(np.floor(7.0 * eps4), 127.0).reshape(1, K, 1, 1)
    x = np.clip(x, -lim, lim).astype(np.int8)
    q4 = _kv4_pack_image(jnp.asarray(x), pack)
    assert int(jnp.min(q4)) >= -8 and int(jnp.max(q4)) <= 7
    r = apply_rqt(
        unpack_int4(pack_int4(q4)), unpack, channel_axis=1)
    err = np.abs(np.asarray(r).astype(np.int64) - x.astype(np.int64))
    # round-to-nearest pack (<= eps4/2) + floor-shift unpack (< 1
    # quantum) + the Eq. 14 scale error: one eps4 plus slack
    bound = eps4.reshape(1, K, 1, 1) + 2.0
    assert np.all(err <= bound), (err.max(), eps4)


def test_kv4_operand_shape():
    rqt = make_rqt(np.array([2.0, 3.0, 4.0]), 1.0, acc_bound=8.0)
    op = _kv4_operand(rqt, 3)
    assert op.shape == (6, 3) and op.dtype == jnp.int32
    # scalar-leaf tree (single head after squeeze) broadcasts
    rqt1 = make_rqt(2.0, 1.0, acc_bound=8.0)
    op1 = _kv4_operand(rqt1, 4)
    assert op1.shape == (6, 4)
    assert np.all(np.asarray(op1) == np.asarray(op1)[:, :1])


# ---------------------------------------------------------------------
# packed kernel == (S, T) mirror, tolerance 0
# ---------------------------------------------------------------------
@pytest.mark.parametrize("s_q,group", [(1, 1), (4, 2)])
def test_packed_kernel_matches_ref(s_q, group):
    rng = np.random.default_rng(7)
    n_pages, K, ps, hd = 4, 2, 4, 8
    H = K * group
    B, pps = 3, 3
    eps4 = np.maximum(rng.uniform(1.0, 20.0, size=K), 1.0)
    unpack = make_rqt(eps4, 1.0, acc_bound=8.0)
    k_rq = _kv4_operand(unpack, K)
    v_rq = _kv4_operand(
        make_rqt(np.roll(eps4, 1), 1.0, acc_bound=8.0), K)
    q = jnp.asarray(
        rng.integers(-127, 128, size=(B, H, s_q, hd)).astype(np.int8))
    k_pool = jnp.asarray(rng.integers(
        -128, 128, size=(n_pages + 1, K, hd // 2, ps)).astype(np.int8))
    v_pool = jnp.asarray(rng.integers(
        -128, 128, size=(n_pages + 1, K, hd // 2, ps)).astype(np.int8))
    table = jnp.asarray(
        rng.integers(0, n_pages + 1, size=(B, pps)).astype(np.int32))
    pos = jnp.asarray(np.array([0, 5, INACTIVE_POS], np.int32))
    got = paged_attention_pallas(
        q, k_pool, v_pool, table, pos, score_scale=0.02, group=group,
        k_rq=k_rq, v_rq=v_rq)
    want = paged_attention_ref(
        q, k_pool, v_pool, table, pos, score_scale=0.02, group=group,
        k_rq=k_rq, v_rq=v_rq)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_packed_kernel_requires_operands():
    q = jnp.zeros((1, 1, 1, 8), jnp.int8)
    pool = jnp.zeros((2, 1, 4, 4), jnp.int8)  # hd/2 = 4: packed
    table = jnp.zeros((1, 1), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="k_rq/v_rq"):
        paged_attention_pallas(
            q, pool, pool, table, pos, score_scale=0.02)


# ---------------------------------------------------------------------
# engine-level parity and geometry
# ---------------------------------------------------------------------
def _tokens(eng, prompts, gens):
    for p, g in zip(prompts, gens):
        eng.submit(p, max_new_tokens=g)
    return {
        c.req_id: list(map(int, c.tokens))
        for c in eng.run_until_drained()
    }


def test_engine_kernel_vs_gather_kv4(deployed):
    """At kv_bits=4 both read paths (fused kernel with in-kernel
    unpack, write-then-gather with jnp unpack) decode the SAME packed
    bytes through the SAME requant formula — token-for-token."""
    lm, tables = deployed
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, lm.cfg.vocab, size=(int(n),))
        for n in rng.integers(4, 14, size=4)
    ]
    gens = [6] * len(prompts)
    outs = {}
    for kern in (False, True):
        eng = ServingEngine(lm, tables, ServingConfig(
            n_slots=2, max_len=MAX_LEN, paged=True, page_size=PS,
            paged_kernel=kern, kv_bits=4,
            scheduler=SchedulerConfig(prefill_bucket=PS,
                                      prefill_chunk=4)))
        outs[kern] = _tokens(eng, prompts, gens)
    assert outs[True] == outs[False]


def test_engine_kv4_deterministic(deployed):
    """Packed decode is deterministic: two independent kv_bits=4
    engines produce identical tokens (integer determinism makes
    packed pages byte-identical at fixed kv-bits — the prefix-cache
    exactness precondition)."""
    lm, tables = deployed
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, lm.cfg.vocab, size=(9,))]

    def once():
        eng = ServingEngine(lm, tables, ServingConfig(
            n_slots=2, max_len=MAX_LEN, paged=True, page_size=PS,
            kv_bits=4,
            scheduler=SchedulerConfig(prefill_bucket=PS,
                                      prefill_chunk=4)))
        return _tokens(eng, prompts, [8])

    assert once() == once()


def test_arena_packed_geometry(deployed):
    lm, _ = deployed
    a8 = PagedArena(lm, n_slots=2, max_len=MAX_LEN, page_size=PS,
                    n_pages=6)
    a4 = PagedArena(lm, n_slots=2, max_len=MAX_LEN, page_size=PS,
                    n_pages=6, kv_bits=4)
    assert a4.stats()["kv_bits"] == 4
    assert a8.stats()["kv_bits"] == 8
    l8 = jax.tree.leaves(a8.caches)
    l4 = jax.tree.leaves(a4.caches)
    halved = [
        (x8.shape, x4.shape)
        for x8, x4 in zip(l8, l4) if x8.shape != x4.shape
    ]
    assert halved, "kv_bits=4 arena halved no leaf"
    for s8, s4 in halved:  # position-minor pools: (..., hd, ps)
        assert s4 == s8[:-2] + (s8[-2] // 2, s8[-1])


def test_kv_bits_validation(deployed):
    lm, tables = deployed
    with pytest.raises(ValueError, match="kv_bits"):
        ServingConfig(kv_bits=5, paged=True)
    with pytest.raises(ValueError, match="paged"):
        ServingConfig(kv_bits=4)
    with pytest.raises(ValueError, match="kv_bits"):
        PagedArena(lm, n_slots=2, max_len=MAX_LEN, page_size=PS,
                   kv_bits=3)
    # kv_bits=4 off the chunked prefill path is rejected up front
    with pytest.raises(ValueError, match="chunked"):
        ServingEngine(lm, tables, ServingConfig(
            n_slots=2, max_len=MAX_LEN, paged=True, page_size=PS,
            kv_bits=4,
            scheduler=SchedulerConfig(prefill_bucket=PS,
                                      prefill_chunk=0)))
