"""Multi-device serving: mesh-sharded KV arenas + async dispatch.

Acceptance (ISSUE 5): on a forced 8-device host mesh
(tests/conftest.py), ID decode + chunked prefill with `kv_shard`
produce token-for-token identical output to the single-device engine
for BOTH arenas; the async dispatch queue changes no tokens (queue
depth 1 == synchronous); every KV leaf of both arenas gets a sharding
rule hit (no silent replication of the KV pools); and
`assert_integer_caches` still holds on the sharded arena.

The mesh is (data=4, model=2): the model axis matches the reduced
configs' n_kv_heads=2, so KV leaves genuinely split; 8 total devices
exercise a multi-axis mesh, not just a 1-D one.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import deploy_model
from repro.models.lm import DecoderLM
from repro.serving import (
    DispatchQueue, PagedArena, SchedulerConfig, ServingConfig,
    ServingEngine, SlotArena,
    assert_integer_caches, float_cache_leaves,
)
from repro.sharding.rules import arena_leaf_spec, kv_head_axis


def make_engine(lm, tables, **kw):
    """Every test engine goes through the typed ServingConfig surface."""
    on_token = kw.pop("on_token", None)
    return ServingEngine(
        lm, tables, ServingConfig(**kw), on_token=on_token)


pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs the 8-device forced host platform (tests/conftest.py)",
)

MAX_LEN = 28


@pytest.fixture(scope="module")
def mesh():
    return make_serving_mesh(2, n_data=4)


@pytest.fixture(scope="module")
def deployed():
    return deploy_model("granite_3_2b", reduced=True, max_seq=MAX_LEN)


def _specs_of(arena):
    """(leaf, spec) pairs for an arena's cache leaves."""
    leaves = jax.tree.leaves(arena.caches)
    return [(x, x.sharding.spec) for x in leaves]


# ---------------------------------------------------------------------
# sharding-rule coverage on serving cache pytrees
# ---------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["granite_3_2b", "zamba2_1_2b"])
@pytest.mark.parametrize("paged", [False, True])
def test_arena_rules_hit_every_kv_leaf(mesh, arch, paged):
    """Every KV leaf of both arenas shards on the model axis (no silent
    replication of the KV pools); sequence-axis-free leaves (SSM
    recurrent state) and the injected page tables replicate — the
    documented layout contract, checked structurally so a new cache
    layout cannot slip in unsharded.  zamba2 (hybrid) covers mixed
    KV + recurrent-state trees."""
    lm = DecoderLM(get_config(arch).reduced(), max_seq=16)
    if paged:
        arena = PagedArena(lm, n_slots=2, max_len=16, page_size=4,
                           n_pages=8, mesh=mesh, kv_shard=True)
    else:
        arena = SlotArena(lm, 2, 16, mesh=mesh, kv_shard=True)
    n_kv = 0
    for (leaf, spec), b_ax, s_ax in zip(
        _specs_of(arena), arena._batch_axes, arena._seq_axes
    ):
        h_ax = kv_head_axis(b_ax, s_ax)
        if h_ax is None:
            assert spec == P(), f"non-KV leaf {leaf.shape} not replicated"
            continue
        n_kv += 1
        assert spec[h_ax] == "model", (
            f"KV leaf {leaf.shape} silently replicated: {spec}"
        )
        assert leaf.shape[h_ax] % 2 == 0  # the split is real
    assert n_kv > 0  # the check exercised actual KV pools
    # the rule helper agrees leaf-for-leaf with what was placed
    for (leaf, spec), b_ax, s_ax in zip(
        _specs_of(arena), arena._batch_axes, arena._seq_axes
    ):
        assert spec == arena_leaf_spec(leaf.shape, b_ax, s_ax, mesh)
    # integer-only invariant holds on the sharded arena and its decode
    # view (page tables included)
    assert_integer_caches(
        arena.caches, allow_ssm_state=lm.cfg.family in ("ssm", "hybrid")
    )
    assert_integer_caches(
        arena.decode_view(),
        allow_ssm_state=lm.cfg.family in ("ssm", "hybrid"),
    )
    if paged:
        # the injected tables are replicated in the sharding views
        tabs = [
            s for s in jax.tree.leaves(arena.decode_shardings())
        ]
        assert any(sh.spec == P() for sh in tabs)


def test_indivisible_heads_degrade_to_replication(mesh):
    """A mesh model axis wider than n_kv_heads must NOT split a head:
    the GQA-aware fallback replicates instead (sanitize_spec)."""
    assert arena_leaf_spec((2, 4, 2, 16, 8), 1, 3, mesh)[2] == "model"
    wide = make_serving_mesh(8, n_data=1)  # model=8 > n_kv_heads=2
    spec = arena_leaf_spec((2, 4, 2, 16, 8), 1, 3, wide)
    assert all(ax is None for ax in spec)  # fully replicated


def test_serving_mesh_raises_on_too_few_devices():
    """No silent 1-device fallback: a mesh wider than the platform's
    device count is an error naming both counts."""
    n = jax.device_count()
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        make_serving_mesh(n + 1)
    with pytest.raises(ValueError, match="needs"):
        make_serving_mesh(2, n_data=n)


# ---------------------------------------------------------------------
# sharded == single-device, token for token (tentpole acceptance)
# ---------------------------------------------------------------------
def _run(lm, tables, specs, prompts, *, paged, mesh=None, kv_shard=False,
         dispatch_depth=0, chunk=4):
    eng = make_engine(
        lm, tables, n_slots=3, max_len=MAX_LEN, paged=paged, page_size=8,
        mesh=mesh, kv_shard=kv_shard, dispatch_depth=dispatch_depth,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8, prefill_chunk=chunk))
    ids = []
    for (p, g), prompt in zip(specs, prompts):
        ids.append(eng.submit(prompt, max_new_tokens=g))
        eng.step()  # staggered arrivals
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert len(done) == len(specs)
    return [done[rid].tokens for rid in ids], eng


WORKLOAD = [(5, 6), (12, 4), (9, 8), (3, 3), (16, 6), (12, 7), (5, 2)]


@pytest.fixture(scope="module")
def workload_prompts(deployed):
    lm, _ = deployed
    rng = np.random.default_rng(11)
    return [
        rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in WORKLOAD
    ]


@pytest.mark.parametrize("paged", [False, True])
def test_sharded_parity_both_arenas(deployed, mesh, workload_prompts,
                                    paged):
    """kv_shard over the (4, 2) host mesh == single-device engine,
    token for token, on a ragged staggered workload exercising chunked
    prefill AND fused decode (the paged default runs the
    paged-attention kernel under its per-shard head range)."""
    lm, tables = deployed
    ref, _ = _run(lm, tables, WORKLOAD, workload_prompts, paged=paged)
    got, eng = _run(lm, tables, WORKLOAD, workload_prompts, paged=paged,
                    mesh=mesh, kv_shard=True)
    assert got == ref
    # the arena really was sharded (not silently replicated)
    assert any(
        any(ax == "model" for ax in spec)
        for _, spec in _specs_of(eng.arena)
    )
    # invariant after a full sharded run
    assert float_cache_leaves(eng.arena.caches) == []
    assert_integer_caches(eng.arena.decode_view())
    s = eng.stats()
    assert s["kv_shard"] and s["mesh_devices"] == 8


def test_sharded_whole_prompt_oracle_path(deployed, mesh,
                                          workload_prompts):
    """chunk=0 (bucketed whole-prompt prefill, the parity oracle path)
    also survives sharding: prefill scatters a replicated B=1 result
    into the sharded arena through the pinned-layout scatter."""
    lm, tables = deployed
    ref, _ = _run(lm, tables, WORKLOAD, workload_prompts, paged=False,
                  chunk=0)
    got, _ = _run(lm, tables, WORKLOAD, workload_prompts, paged=False,
                  chunk=0, mesh=mesh, kv_shard=True)
    assert got == ref


# ---------------------------------------------------------------------
# async dispatch queue (tentpole acceptance: depth 1 == synchronous)
# ---------------------------------------------------------------------
def test_dispatch_queue_contract():
    q = DispatchQueue(0)
    assert q.pending == 0
    with pytest.raises(ValueError):
        DispatchQueue(2)  # token feedback bounds the pipeline at 1
    # depth-1 queue accepts exactly one in-flight record
    q1 = DispatchQueue(1)
    q1.push("rec")
    with pytest.raises(RuntimeError):
        q1.push("rec2")
    got = []
    q1.drain(got.append)
    assert got == ["rec"] and q1.pending == 0


@pytest.mark.parametrize("paged", [False, True])
def test_async_depth1_matches_sync(deployed, workload_prompts, paged):
    """The async dispatch queue changes no tokens: depth 1 ==
    synchronous, both arenas, ragged staggered workload."""
    lm, tables = deployed
    ref, _ = _run(lm, tables, WORKLOAD, workload_prompts, paged=paged)
    got, eng = _run(lm, tables, WORKLOAD, workload_prompts, paged=paged,
                    dispatch_depth=1)
    assert got == ref
    assert eng.queue.pending == 0  # fully drained
    assert eng.stats()["dispatch_depth"] == 1


def test_async_plus_sharded_full_stack(deployed, mesh, workload_prompts):
    """The full multi-device engine — sharded paged arena, fused
    kernel, async dispatch — still reproduces the plain single-device
    engine token for token."""
    lm, tables = deployed
    ref, _ = _run(lm, tables, WORKLOAD, workload_prompts, paged=True)
    got, eng = _run(lm, tables, WORKLOAD, workload_prompts, paged=True,
                    mesh=mesh, kv_shard=True, dispatch_depth=1)
    assert got == ref
    assert float_cache_leaves(eng.arena.caches) == []


def test_kv_shard_requires_mesh(deployed):
    lm, tables = deployed
    with pytest.raises(ValueError, match="mesh"):
        make_engine(lm, tables, n_slots=2, max_len=16, kv_shard=True)
