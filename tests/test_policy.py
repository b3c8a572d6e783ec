"""Policy/mechanism split tests (ISSUE 7, DESIGN.md §Scheduling).

Acceptance pinned here:
  - FCFSPolicy (explicit or default) reproduces the engine's behavior
    — the policy extraction changed no tokens (the full pre-refactor
    parity matrix lives in tests/test_serving.py and keeps passing).
  - Preemption is bit-exact: a preempted request finishes with EXACTLY
    the tokens of an uninterrupted run, on both arenas, sync and
    async, including eviction mid-chunked-prefill — the engine's
    resume-parity oracle (re-prefill must regenerate the last emitted
    token) raises on any divergence.
  - Repeated preempt/resume cycles leak no pages: the paged arena
    returns to zero pages in use and zero committed after drain.
  - PrioritySLOPolicy plans class-ordered admission, LIFO lowest-class
    eviction with rollback, and SLO aging (order only) — checked
    against hand-built EngineViews, no model needed.
  - The Arena protocol + make_arena factory and the ServingConfig
    surface (validation) behave.
  - preempt/resume trace events validate through tools/trace_summary
    (ordering state machine), and malformed sequences are rejected.
"""
import importlib.util
import pathlib

import numpy as np
import pytest

from repro.launch.serve import deploy_model
from repro.serving import (
    Arena,
    EngineView,
    FCFSPolicy,
    PagedArena,
    PendingSnap,
    PrioritySLOPolicy,
    Request,
    SchedulerConfig,
    SchedulingPolicy,
    ServingConfig,
    ServingEngine,
    SlotArena,
    StepPlan,
    Telemetry,
    make_arena,
    make_policy,
)
from repro.serving.policy import DecodeSnap

MAX_LEN = 40


@pytest.fixture(scope="module")
def deployed():
    return deploy_model("granite_3_2b", reduced=True, max_seq=MAX_LEN)


def make_engine(lm, tables, **kw):
    return ServingEngine(lm, tables, ServingConfig(**kw))


def _trace_summary():
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "tools" / "trace_summary.py")
    spec = importlib.util.spec_from_file_location("trace_summary", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class ScriptedPreemptions:
    """FCFSPolicy plus scripted evictions — the deterministic harness
    for the preemption parity tests: at plan() call index k, evict one
    slot of the requested kind ("active": the most recently admitted
    decode that cannot finish before the eviction executes;
    "prefilling": a mid-prefill row, asserted to exist)."""

    name = "scripted"

    def __init__(self, script):
        self.inner = FCFSPolicy()
        self.script = dict(script)
        self.calls = 0
        self.n_scripted = 0
        # evictions of rows holding generated tokens — only these
        # leave a ResumeState behind and bump the completion's
        # n_preempts (an initial-prefill eviction just requeues)
        self.n_token_bearing = 0

    def plan(self, view: EngineView) -> StepPlan:
        plan = self.inner.plan(view)
        kind = self.script.get(self.calls)
        self.calls += 1
        if kind == "active":
            # Under dispatch_depth=1 the engine drains the in-flight
            # step BEFORE executing evictions, and rightly skips a
            # victim that finished in that drain (its tokens are real
            # output).  The drain harvests at most ONE token per slot,
            # so a victim with budget_left >= 2 at plan time is
            # guaranteed still leased when the eviction executes —
            # script only those, keeping the executed == scripted
            # accounting below exact at both dispatch depths.
            live = [d for d in view.active if d.budget_left >= 2]
            if live:
                v = max(live, key=lambda d: (d.admit_time, d.req_id))
                assert v.n_generated >= 1
                plan.preempt.append(v.slot)
                self.n_scripted += 1
                self.n_token_bearing += 1
        elif kind == "prefilling":
            mid = [s for s in view.prefilling if 0 < s.offset < s.total]
            assert mid, "script expected a mid-prefill row"
            plan.preempt.append(mid[0].slot)
            self.n_scripted += 1
            self.n_token_bearing += mid[0].is_resume
        return plan


# ---------------------------------------------------------------------
# policy contract + FCFS extraction
# ---------------------------------------------------------------------
def test_policies_satisfy_protocol():
    assert isinstance(FCFSPolicy(), SchedulingPolicy)
    assert isinstance(PrioritySLOPolicy(), SchedulingPolicy)
    assert isinstance(ScriptedPreemptions({}), SchedulingPolicy)
    assert make_policy("fcfs").name == "fcfs"
    assert make_policy("priority", preempt=False).name == "priority"
    with pytest.raises(ValueError):
        make_policy("srpt")


def test_explicit_fcfs_matches_default(deployed):
    """policy=FCFSPolicy() == policy=None, token for token — the
    config wiring changes nothing."""
    lm, tables = deployed
    rng = np.random.default_rng(11)
    specs = [(6, 6), (9, 4), (5, 8), (12, 5)]
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]

    def run(policy):
        eng = make_engine(
            lm, tables, n_slots=2, max_len=MAX_LEN, policy=policy,
            scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))
        ids = [eng.submit(pr, max_new_tokens=g)
               for pr, (_, g) in zip(prompts, specs)]
        done = {c.req_id: c.tokens for c in eng.run_until_drained()}
        return [done[rid] for rid in ids], eng.stats()

    base, s0 = run(None)
    expl, s1 = run(FCFSPolicy())
    assert expl == base
    assert s0["policy"] == s1["policy"] == "fcfs"
    assert s0["n_preempts"] == 0


# ---------------------------------------------------------------------
# preemption bit-exactness (the tentpole oracle)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("depth", [0, 1])
def test_preempt_resume_token_parity(deployed, paged, depth):
    """A preempted request finishes with EXACTLY the tokens of the
    uninterrupted run — both arenas x sync/async, evictions landing
    both mid-decode and mid-chunked-prefill.  The engine's resume
    oracle (re-prefill regenerates the last emitted token or raises)
    guards the KV reconstruction underneath."""
    lm, tables = deployed
    rng = np.random.default_rng(7)
    # long prompts + chunk=4 keep rows mid-prefill across many steps
    specs = [(14, 8), (6, 10), (18, 6), (9, 9), (5, 7)]
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    kw = dict(
        n_slots=2, max_len=MAX_LEN, paged=paged, page_size=8,
        dispatch_depth=depth,
        scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))

    def run(policy):
        eng = make_engine(lm, tables, policy=policy, **kw)
        ids = [eng.submit(pr, max_new_tokens=g)
               for pr, (_, g) in zip(prompts, specs)]
        done = {c.req_id: c for c in eng.run_until_drained()}
        return ids, done, eng

    ids, base, _ = run(None)
    script = {3: "prefilling", 6: "active", 10: "active", 15: "active"}
    pol = ScriptedPreemptions(script)
    ids2, got, eng = run(pol)
    assert pol.n_scripted >= 3, "script never fired"
    assert eng.stats()["n_preempts"] == pol.n_scripted
    resumed = 0
    for a, b in zip(ids, ids2):
        assert got[b].tokens == base[a].tokens
        assert got[b].finish_reason == base[a].finish_reason
        resumed += got[b].n_preempts
    # token-bearing evictions resume (and count on the completion);
    # an initial-prefill eviction requeues with nothing to restore
    assert resumed == pol.n_token_bearing
    assert len(got) == len(specs)  # nothing lost


def test_preempt_no_page_leak(deployed):
    """Repeated preempt/resume cycles must hand every page back: after
    drain the paged arena is at zero pages in use, zero committed, all
    slots free — across several serve/drain rounds on one engine."""
    lm, tables = deployed
    rng = np.random.default_rng(13)
    specs = [(10, 8), (6, 10), (13, 6), (8, 8)]
    prompts = [rng.integers(0, lm.cfg.vocab, size=(p,)) for p, _ in specs]
    eng = make_engine(
        lm, tables, n_slots=2, max_len=MAX_LEN, paged=True, page_size=8,
        policy=ScriptedPreemptions(
            {k: "active" for k in range(2, 60, 4)}),
        scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))
    total_pre = 0
    for _ in range(3):
        for pr, (_, g) in zip(prompts, specs):
            eng.submit(pr, max_new_tokens=g)
        eng.run_until_drained()
        g = eng.arena.gauges()
        assert g["pages_in_use"] == 0, "leaked physical pages"
        assert g["committed_pages"] == 0, "leaked page commitments"
        assert g["n_free"] == eng.arena.n_slots
        total_pre = eng.stats()["n_preempts"]
    assert total_pre > 0, "the leak test never actually preempted"
    assert not eng._resume, "orphaned parked resume state"


# ---------------------------------------------------------------------
# PrioritySLOPolicy planning (hand-built views, no model)
# ---------------------------------------------------------------------
def _pending(req_id, prio, arrival, *, need=2, plen=4):
    req = Request(np.zeros(plen, np.int32), 4, None, prio)
    req.req_id = req_id
    req.arrival_time = arrival
    return PendingSnap(
        req=req, req_id=req_id, priority=prio, arrival_time=arrival,
        prompt_len=plen, max_new_tokens=4, source_len=plen,
        need_pages=need, n_generated=0)


def _decoding(req_id, slot, prio, admit, *, pages=2):
    return DecodeSnap(
        req_id=req_id, slot=slot, priority=prio, arrival_time=admit,
        admit_time=admit, first_token_time=admit + 0.1, n_generated=2,
        budget_left=2, pages_committed=pages)


def _view(pending=(), active=(), *, free=0, budget=None, now=100.0,
          max_prefills=4):
    return EngineView(
        now=now, pending=tuple(pending), prefilling=(),
        active=tuple(active), free_slots=free, budget_left=budget,
        gauges={}, prefill_mode="chunked", prefill_chunk=8,
        max_chunks_per_step=None, max_prefills_per_step=max_prefills)


def test_priority_admission_order():
    """Highest class first, FCFS within a class."""
    v = _view(
        [_pending(0, 0, 1.0), _pending(1, 2, 2.0),
         _pending(2, 1, 3.0), _pending(3, 2, 4.0)],
        free=3, budget=None)
    plan = PrioritySLOPolicy().plan(v)
    assert [r.req_id for r in plan.admit] == [1, 3, 2]
    assert plan.rejects == [(0, "no_slot")]
    assert plan.preempt == []


def test_priority_eviction_lifo_lowest_class():
    """Eviction picks strictly-lower classes, lowest first, most
    recently admitted first; equal class is never evicted."""
    v = _view(
        [_pending(9, 2, 5.0, need=2)],
        [_decoding(0, 0, 0, admit=1.0), _decoding(1, 1, 0, admit=2.0),
         _decoding(2, 2, 2, admit=3.0)],
        free=0, budget=0)
    plan = PrioritySLOPolicy().plan(v)
    assert plan.preempt == [1]  # class 0, newest — NOT the class-2 peer
    assert [r.req_id for r in plan.admit] == [9]
    # equal-or-higher class only -> no victims, rolled back to reject
    v2 = _view([_pending(9, 0, 5.0)],
               [_decoding(0, 0, 0, admit=1.0)], free=0, budget=0)
    plan2 = PrioritySLOPolicy().plan(v2)
    assert plan2.preempt == [] and plan2.admit == []
    assert plan2.rejects == [(9, "no_slot")]


def test_priority_eviction_rollback_on_shortfall():
    """If the whole eligible victim set cannot free enough pages, the
    hypothetical evictions roll back — nobody is preempted for a
    request that still would not fit."""
    v = _view(
        [_pending(9, 2, 5.0, need=50)],  # needs more than exists
        [_decoding(0, 0, 0, admit=1.0, pages=2)],
        free=1, budget=3)
    plan = PrioritySLOPolicy().plan(v)
    assert plan.preempt == [] and plan.admit == []
    assert plan.rejects == [(9, "no_pages")]


def test_priority_slo_aging_affects_order_only():
    """A pending request older than slo_ttft_s jumps the class order;
    aging never makes it eviction-eligible against a higher class."""
    pol = PrioritySLOPolicy(slo_ttft_s=10.0)
    aged = _pending(0, 0, 1.0)    # waited 99s at now=100
    fresh = _pending(1, 2, 95.0)  # higher class, inside SLO
    plan = pol.plan(_view([fresh, aged], free=2, budget=None))
    assert [r.req_id for r in plan.admit] == [0, 1]  # aged first
    # but with zero capacity + a class-1 tenant, the aged class-0
    # request may NOT preempt it (base priorities gate eviction)
    plan2 = pol.plan(_view(
        [aged], [_decoding(5, 0, 1, admit=50.0)], free=0, budget=0))
    assert plan2.preempt == []
    assert plan2.rejects == [(0, "no_slot")]


def test_priority_no_preempt_flag():
    v = _view([_pending(9, 2, 5.0)],
              [_decoding(0, 0, 0, admit=1.0)], free=0, budget=0)
    plan = PrioritySLOPolicy(preempt=False).plan(v)
    assert plan.preempt == [] and plan.rejects == [(9, "no_slot")]


def test_priority_end_to_end_overload(deployed):
    """Organic (unscripted) preemption: a class-1 burst lands on a full
    class-0 arena; every request still finishes with its full budget
    and the class-0 victims resume bit-exactly (oracle-guarded)."""
    lm, tables = deployed
    rng = np.random.default_rng(17)
    lo = [rng.integers(0, lm.cfg.vocab, size=(6,)) for _ in range(2)]
    hi = [rng.integers(0, lm.cfg.vocab, size=(6,)) for _ in range(2)]

    # uninterrupted reference for the low-class victims
    ref = make_engine(
        lm, tables, n_slots=2, max_len=24, paged=True, page_size=8,
        scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))
    ref_ids = [ref.submit(p, max_new_tokens=12) for p in lo]
    ref_done = {c.req_id: c.tokens for c in ref.run_until_drained()}

    eng = make_engine(
        lm, tables, n_slots=2, max_len=24, paged=True, page_size=8,
        policy=PrioritySLOPolicy(),
        scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))
    ids = [eng.submit(p, max_new_tokens=12) for p in lo]
    # let the class-0 pair occupy every slot, then burst class 1
    for _ in range(6):
        eng.step()
    hi_ids = [eng.submit(p, max_new_tokens=4, priority=1) for p in hi]
    done = {c.req_id: c for c in eng.run_until_drained()}
    assert eng.stats()["n_preempts"] > 0, "overload never preempted"
    for rid, budget in zip(ids + hi_ids, [12, 12, 4, 4]):
        assert done[rid].finish_reason == "length"
        assert done[rid].n_generated == budget
    for a, b in zip(ref_ids, ids):
        assert done[b].tokens == ref_done[a]  # victims bit-exact
    g = eng.arena.gauges()
    assert g["pages_in_use"] == 0 and g["committed_pages"] == 0


# ---------------------------------------------------------------------
# Arena protocol + factory (satellite 2)
# ---------------------------------------------------------------------
def test_arena_protocol_and_factory(deployed):
    lm, _ = deployed
    slot = make_arena(lm, ServingConfig(n_slots=2, max_len=16))
    paged = make_arena(lm, ServingConfig(
        n_slots=2, max_len=16, paged=True, page_size=4))
    assert isinstance(slot, SlotArena) and isinstance(slot, Arena)
    assert isinstance(paged, PagedArena) and isinstance(paged, Arena)
    # default pool: SlotArena-equivalent positions
    assert paged.n_pages * paged.page_size == 2 * 16
    explicit = make_arena(lm, ServingConfig(
        n_slots=2, max_len=16, paged=True, page_size=4, n_pages=5))
    assert explicit.n_pages == 5
    # the protocol surface the engine/policies consume, both arenas
    for arena in (slot, paged):
        assert arena.n_free == 2 and arena.pages_needed(8) >= 0
        s = arena.alloc(req_id=1, prompt_len=4, total_len=8)
        assert arena.committed_for(s) == arena.pages_needed(8)
        assert (arena.budget_left is None) == isinstance(arena, SlotArena)
        arena.release(s)
    # release_pages on an unleased slot is an error on both
    for arena in (slot, paged):
        with pytest.raises(RuntimeError):
            arena.release_pages(0)


# ---------------------------------------------------------------------
# ServingConfig validation
# ---------------------------------------------------------------------
def test_serving_config_validation():
    with pytest.raises(ValueError):
        ServingConfig(n_slots=0)
    with pytest.raises(ValueError):
        ServingConfig(max_len=0)
    with pytest.raises(ValueError):
        ServingConfig(page_size=0)
    with pytest.raises(ValueError):
        ServingConfig(n_pages=0)
    with pytest.raises(ValueError):
        ServingConfig(dispatch_depth=2)
    with pytest.raises(ValueError):
        ServingConfig(kv_shard=True)  # needs a mesh
    assert isinstance(ServingConfig().scheduler, SchedulerConfig)


# ---------------------------------------------------------------------
# preempt/resume telemetry + trace validation (satellite 3)
# ---------------------------------------------------------------------
def test_preempt_resume_trace_validates(deployed, tmp_path):
    lm, tables = deployed
    rng = np.random.default_rng(29)
    tel = Telemetry()
    eng = make_engine(
        lm, tables, n_slots=2, max_len=MAX_LEN, paged=True, page_size=8,
        telemetry=tel,
        policy=ScriptedPreemptions({4: "active", 8: "active"}),
        scheduler=SchedulerConfig(prefill_bucket=8, prefill_chunk=4))
    for p, g in [(10, 8), (6, 10), (13, 6), (8, 8)]:
        eng.submit(rng.integers(0, lm.cfg.vocab, size=(p,)),
                   max_new_tokens=g)
    eng.run_until_drained()
    n_pre = eng.stats()["n_preempts"]
    assert n_pre > 0
    kinds = [e["event"] for e in tel.events]
    assert kinds.count("preempt") == n_pre
    assert kinds.count("resume") >= 1
    path = tmp_path / "trace.jsonl"
    tel.export_trace(str(path))
    ts = _trace_summary()
    events = ts.load_trace(str(path))
    ts.validate(events)
    reqs = ts.lifecycles(events)  # raises TraceError on bad ordering
    assert sum(r["preempts"] for r in reqs.values()) == n_pre
    # emit conservation across preemption: resume re-emits nothing
    for r in reqs.values():
        assert r["finish_reason"] == "length"


def test_trace_state_machine_rejects_malformed():
    ts = _trace_summary()
    base = [
        {"event": "submit", "t": 0.0, "req_id": 1, "prompt_len": 4,
         "max_new_tokens": 2},
        {"event": "admit", "t": 1.0, "req_id": 1, "slot": 0},
        {"event": "first_token", "t": 2.0, "req_id": 1, "slot": 0,
         "token": 5},
        {"event": "emit", "t": 2.0, "req_id": 1, "slot": 0, "token": 5},
    ]
    pre = {"event": "preempt", "t": 3.0, "req_id": 1, "slot": 0,
           "reason": "policy", "n_generated": 1}
    res = {"event": "resume", "t": 5.0, "req_id": 1, "slot": 0,
           "n_preempts": 1}
    adm = {"event": "admit", "t": 4.0, "req_id": 1, "slot": 1}
    fin = {"event": "finish", "t": 6.0, "req_id": 1, "slot": 1,
           "reason": "length", "n_generated": 2}
    emit2 = {"event": "emit", "t": 5.5, "req_id": 1, "slot": 1,
             "token": 6}
    # the legal lifecycle passes
    ts.lifecycles(base + [pre, adm, res, emit2, fin])
    # resume without re-admission
    with pytest.raises(ts.TraceError):
        ts.lifecycles(base + [pre, res, emit2, fin])
    # emit while evicted
    with pytest.raises(ts.TraceError):
        ts.lifecycles(base + [pre, emit2, adm, res, fin])
    # finish while evicted
    with pytest.raises(ts.TraceError):
        ts.lifecycles(base + [pre, fin])
    # double preempt without re-admission
    with pytest.raises(ts.TraceError):
        ts.lifecycles(base + [pre, pre, adm, res, emit2, fin])
    # resume count disagrees with the trace
    bad = dict(res, n_preempts=3)
    with pytest.raises(ts.TraceError):
        ts.lifecycles(base + [pre, adm, bad, emit2, fin])
