"""Serving telemetry (repro.serving.telemetry) tests.

Acceptance (ISSUE 6): telemetry is off by default (the NullTelemetry
singleton records nothing, ever); enabling it changes NO tokens —
telemetry-on and telemetry-off engines are token-for-token identical
on both arenas, sync and async (bit-neutrality, DESIGN.md
§Observability ¶Bit-neutrality); the exported JSONL trace validates
against the event schema and tools/trace_summary.py parses it; the
step records carry per-phase spans, queue depth, arena gauges, and
compile-cache accounting; stats() rolls up TTFT/ITL percentiles and
the queued/prefill/decode breakdown.
"""
import importlib.util
import json
import pathlib

import numpy as np
import pytest

from repro.launch.serve import deploy_model
from repro.serving import (
    NULL, Request, SchedulerConfig, ServingConfig, ServingEngine,
    Telemetry,
)
from repro.serving.request import Completion
from repro.serving.telemetry import EVENT_FIELDS, PHASES


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


def _workload(vocab, rng=None):
    rng = rng or np.random.default_rng(0)
    specs = [(8, 6), (3, 4), (12, 5), (1, 3), (8, 4), (5, 6)]
    return [
        (rng.integers(0, vocab, size=(p,)), g) for p, g in specs
    ]


def _run(lm, tables, workload, *, telemetry=None, paged=False,
         dispatch_depth=0, n_slots=3, n_pages=None, warmup=False,
         prefill_chunk=4):
    eng = make_engine(
        lm, tables, n_slots=n_slots, max_len=MAX_LEN, paged=paged,
        page_size=8, n_pages=n_pages, dispatch_depth=dispatch_depth,
        telemetry=telemetry,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8,
                                  prefill_chunk=prefill_chunk))
    if warmup:
        eng.warmup()
    ids = []
    for prompt, g in workload:
        ids.append(eng.submit(prompt, max_new_tokens=g))
        eng.step()
    done = {c.req_id: c for c in eng.run_until_drained()}
    return [done[rid].tokens for rid in ids], eng


# ---------------------------------------------------------------------
# bit-neutrality: telemetry must never change a token
# ---------------------------------------------------------------------
@pytest.mark.parametrize("profile_annotations", [False, True])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("dispatch_depth", [0, 1])
def test_bit_neutrality(deployed, paged, dispatch_depth,
                        profile_annotations):
    """Telemetry-on and telemetry-off engines produce token-for-token
    identical output on both arenas, sync and async, with profiler
    annotations on or off — the hooks read host state only (DESIGN.md
    §Observability ¶Bit-neutrality)."""
    lm, tables = deployed
    w = _workload(lm.cfg.vocab)
    off_toks, off_eng = _run(lm, tables, w, paged=paged,
                             dispatch_depth=dispatch_depth)
    tel = Telemetry(profile_annotations=profile_annotations)
    on_toks, on_eng = _run(lm, tables, w, telemetry=tel, paged=paged,
                           dispatch_depth=dispatch_depth)
    assert on_toks == off_toks
    assert len(tel.events) > 0 and len(tel.steps) > 0
    # the enabled run recorded the full lifecycle of every request
    kinds = {e["event"] for e in tel.events}
    assert {"submit", "admit", "first_token", "emit", "finish"} <= kinds


def test_telemetry_off_records_nothing(deployed):
    """The default sink is the shared NullTelemetry singleton: no
    buffers, no events, no step records — off means zero retained
    state, not merely unexported state."""
    lm, tables = deployed
    toks, eng = _run(lm, tables, _workload(lm.cfg.vocab))
    assert eng.tel is NULL
    assert eng.tel.enabled is False
    assert eng.tel.events == ()
    assert eng.tel.steps == ()
    assert sum(len(t) for t in toks) > 0  # the run itself did work


# ---------------------------------------------------------------------
# event schema + lifecycle ordering
# ---------------------------------------------------------------------
def test_event_schema_and_lifecycle(deployed):
    lm, tables = deployed
    tel = Telemetry()
    toks, eng = _run(lm, tables, _workload(lm.cfg.vocab),
                     telemetry=tel)
    last_t = None
    for e in tel.events:
        assert e["event"] in EVENT_FIELDS
        assert EVENT_FIELDS[e["event"]] <= e.keys()
        assert isinstance(e["t"], float)
        if last_t is not None:
            assert e["t"] >= last_t  # monotonic emission order
        last_t = e["t"]
    # per-request lifecycle: submit -> admit -> chunks -> first_token
    # -> emits -> finish, with emit count == generated count
    by_req = {}
    for e in tel.events:
        if "req_id" in e:
            by_req.setdefault(e["req_id"], []).append(e)
    done = {c.req_id: c for c in eng.completed}
    assert set(by_req) == set(done)
    for rid, evs in by_req.items():
        order = [e["event"] for e in evs]
        assert order[0] == "submit" and order[-1] == "finish"
        assert order.index("admit") < order.index("first_token")
        emits = [e for e in evs if e["event"] == "emit"]
        assert len(emits) == done[rid].n_generated
        assert [e["token"] for e in emits] == list(done[rid].tokens)
        # chunked prefill: every chunk span is recorded with its pages
        chunks = [e for e in evs if e["event"] == "prefill_chunk"]
        spans = sorted((c["start"], c["end"]) for c in chunks)
        assert spans[0][0] == 0
        assert spans[-1][1] == done[rid].prompt_len
        for (_, e0), (s1, _) in zip(spans, spans[1:]):
            assert e0 == s1  # contiguous, no overlap or gap
        for c in chunks:
            assert isinstance(c["pages"], list)


# ---------------------------------------------------------------------
# step records: spans, gauges, compile accounting
# ---------------------------------------------------------------------
def test_step_records_phases_and_gauges(deployed):
    lm, tables = deployed
    tel = Telemetry()
    _run(lm, tables, _workload(lm.cfg.vocab), telemetry=tel,
         paged=True)
    assert tel.steps, "no step records"
    seen_phases = set()
    for s in tel.steps:
        assert s["wall_s"] >= 0.0
        for ph, v in s["phases"].items():
            assert ph in PHASES
            assert v >= 0.0
            seen_phases.add(ph)
        # gauges folded in by ServingEngine._end_step
        for key in ("queue_depth", "n_pending", "n_active",
                    "n_prefilling", "admit_rejects", "n_leased",
                    "occupancy", "pages_in_use", "free_pages"):
            assert key in s, key
    # a drain of this workload exercises every phase of the sync
    # chunked loop (one unified dispatch per step — decode_dispatch
    # only exists on the non-chunked oracle paths)
    assert seen_phases >= {"admission", "plan_chunks",
                           "unified_dispatch", "harvest"}
    m = tel.metrics()
    assert m["n_steps"] == len(tel.steps)
    assert set(m["phase_mean_s"]) == seen_phases


@pytest.mark.parametrize("prefill_chunk", [4, 0])
@pytest.mark.parametrize("dispatch_depth", [0, 1])
def test_paged_walk_gauges(deployed, dispatch_depth, prefill_chunk):
    """`paged_pages_walked` of each step that dispatched is the sum
    over its rows of the pages the paged kernel walks, the pages up to
    the one holding the row's last query position (whole table rows
    for parked rows), against `paged_pages_table` = B x pages per
    slot; counted here from the positions each dispatch received.
    Unified (chunked) and decode (whole-prompt) dispatches, sync and
    async; the gather oracle records neither gauge."""
    lm, tables = deployed
    tel = Telemetry()
    eng = make_engine(
        lm, tables, n_slots=3, max_len=MAX_LEN, paged=True, page_size=8,
        dispatch_depth=dispatch_depth, telemetry=tel,
        scheduler=SchedulerConfig(max_prefills_per_step=2,
                                  prefill_bucket=8,
                                  prefill_chunk=prefill_chunk))
    pps = eng.arena.pages_per_slot
    want = []

    def spy(fn):
        def call(t, toks, caches, pos, *rest):
            last = np.asarray(pos).astype(np.int64) + toks.shape[1] - 1
            want.append((int(np.minimum(last // 8 + 1, pps).sum()),
                         last.size * pps))
            return fn(t, toks, caches, pos, *rest)
        return call

    eng._unified, eng._decode = spy(eng._unified), spy(eng._decode)
    for prompt, g in _workload(lm.cfg.vocab):
        eng.submit(prompt, max_new_tokens=g)
        eng.step()
    eng.run_until_drained()
    got = [(s["paged_pages_walked"], s["paged_pages_table"])
           for s in tel.steps if "paged_pages_walked" in s]
    assert got == want
    assert sum(w for w, _ in got) < sum(t for _, t in got)

    gather_tel = Telemetry()
    gather = make_engine(
        lm, tables, n_slots=3, max_len=MAX_LEN, paged=True, page_size=8,
        paged_kernel=False, dispatch_depth=dispatch_depth,
        telemetry=gather_tel,
        scheduler=SchedulerConfig(prefill_bucket=8,
                                  prefill_chunk=prefill_chunk))
    gather.submit(np.arange(1, 10), max_new_tokens=3)
    gather.run_until_drained()
    assert gather_tel.steps
    assert not any("paged_pages_walked" in s for s in gather_tel.steps)


@pytest.mark.parametrize("prefill_chunk", [4, 0])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("dispatch_depth", [0, 1])
def test_advance_is_inside_harvest(deployed, paged, dispatch_depth,
                                   prefill_chunk):
    """`advance` (the host bookkeeping once the tokens are on the host)
    is timed inside `harvest` in every step that records it, so it
    never exceeds harvest's time: harvest - advance is the wait for the
    token vector.  Both arenas, sync and async, unified and decode
    harvests."""
    lm, tables = deployed
    tel = Telemetry()
    _run(lm, tables, _workload(lm.cfg.vocab), telemetry=tel, paged=paged,
         dispatch_depth=dispatch_depth, prefill_chunk=prefill_chunk)
    with_advance = [s["phases"] for s in tel.steps
                    if "advance" in s["phases"]]
    assert with_advance, "no advance span recorded"
    for ph in with_advance:
        assert "harvest" in ph
        assert 0.0 <= ph["advance"] <= ph["harvest"]


def test_compile_cache_accounting_after_warmup(deployed):
    """warmup() registers its shapes with the telemetry dispatch
    accounting, so a warmed engine's measured window is all cache
    hits; the seen-set survives reset_stats (warmed shapes stay
    compiled) while the buffers start clean."""
    lm, tables = deployed
    tel = Telemetry()
    toks, eng = _run(lm, tables, _workload(lm.cfg.vocab),
                     telemetry=tel, warmup=True)
    assert tel.compile_misses > 0  # the warmup registrations
    eng.reset_stats()
    assert tel.events == [] and tel.steps == []
    assert tel.compile_hits == 0 and tel.compile_misses == 0
    for prompt, g in _workload(lm.cfg.vocab):
        eng.submit(prompt, max_new_tokens=g)
        eng.step()
    eng.run_until_drained()
    assert tel.compile_hits > 0
    assert tel.compile_misses == 0, "post-warmup window re-compiled"


# ---------------------------------------------------------------------
# SLO rollups + backpressure accounting
# ---------------------------------------------------------------------
def test_stats_slo_rollups(deployed):
    lm, tables = deployed
    toks, eng = _run(lm, tables, _workload(lm.cfg.vocab))
    s = eng.stats()
    for key in ("p99_ttft_s", "mean_itl_s", "p50_itl_s", "p95_itl_s",
                "p99_itl_s", "mean_queued_s", "mean_prefill_s",
                "mean_decode_s", "admit_rejects"):
        assert key in s, key
    assert s["p50_itl_s"] > 0.0
    assert s["p50_itl_s"] <= s["p95_itl_s"] <= s["p99_itl_s"]
    assert s["p50_ttft_s"] <= s["p95_ttft_s"] <= s["p99_ttft_s"]
    for c in eng.completed:
        assert len(c.emit_times) == c.n_generated
        assert len(c.itl) == c.n_generated - 1
        assert c.queued_s >= 0.0
        assert c.prefill_s >= 0.0
        assert c.decode_s >= 0.0
        assert c.admit_time >= c.arrival_time
        # breakdown partitions the request's total latency exactly
        total = c.queued_s + c.prefill_s + c.decode_s
        assert total == pytest.approx(c.latency)


def test_completion_derived_series():
    c = Completion(
        req_id=0, prompt_len=4, tokens=[1, 2, 3],
        finish_reason="length", arrival_time=1.0,
        first_token_time=3.0, finish_time=6.0, admit_time=2.0,
        emit_times=[3.0, 4.5, 6.0],
    )
    assert c.itl == [1.5, 1.5]
    assert c.queued_s == 1.0
    assert c.prefill_s == 1.0
    assert c.decode_s == 3.0


def test_admit_reject_backpressure(deployed):
    """A paged pool too small for the workload's concurrency produces
    admit_reject events naming the blocked FCFS head and the arena's
    reason — and the engine's run counter sees them too."""
    lm, tables = deployed
    tel = Telemetry()
    rng = np.random.default_rng(1)
    # 4 slots but a page pool of only ~2 concurrent requests' worth:
    # admission blocks on pages while slots are still free
    w = [(rng.integers(0, lm.cfg.vocab, size=(16,)), 12)
         for _ in range(6)]
    toks, eng = _run(lm, tables, w, telemetry=tel, paged=True,
                     n_slots=4, n_pages=8)
    assert all(len(t) == 12 for t in toks)  # everything still drains
    rejects = [e for e in tel.events if e["event"] == "admit_reject"]
    assert rejects, "no backpressure recorded"
    assert {e["reason"] for e in rejects} == {"no_pages"}
    assert eng.stats()["admit_rejects"] == len(rejects)


# ---------------------------------------------------------------------
# export + trace_summary round-trip
# ---------------------------------------------------------------------
def _load_trace_summary():
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "tools" / "trace_summary.py")
    spec = importlib.util.spec_from_file_location("trace_summary", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_roundtrip_and_validation(deployed, tmp_path):
    lm, tables = deployed
    tel = Telemetry()
    _run(lm, tables, _workload(lm.cfg.vocab), telemetry=tel)
    trace = tmp_path / "trace.jsonl"
    metrics = tmp_path / "metrics.json"
    tel.export_trace(str(trace))
    tel.export_metrics(str(metrics))

    ts = _load_trace_summary()
    events = ts.load_trace(str(trace))
    assert len(events) == len(tel.events)
    ts.validate(events)
    reqs = ts.lifecycles(events)
    assert len(reqs) == len(_workload(lm.cfg.vocab))
    for r in reqs.values():
        assert r["ttft_s"] > 0.0 and r["decode_s"] >= 0.0
    assert ts.summarize(events, reqs)
    assert ts.summarize_metrics(str(metrics))

    # malformed traces must be rejected, not summarized
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"event": "warp", "t": 0.0}\n')
    with pytest.raises(ts.TraceError):
        ts.validate(ts.load_trace(str(bad)))
    dropped = [e for e in tel.events if e["event"] != "emit"]
    with pytest.raises(ts.TraceError):  # emit count != n_generated
        ts.lifecycles(dropped)
    bad.write_text('not json\n')
    with pytest.raises(ts.TraceError):
        ts.load_trace(str(bad))


def test_metrics_export_is_json(deployed, tmp_path):
    lm, tables = deployed
    tel = Telemetry()
    _run(lm, tables, _workload(lm.cfg.vocab), telemetry=tel)
    path = tmp_path / "metrics.json"
    tel.export_metrics(str(path))
    m = json.loads(path.read_text())
    assert m["n_steps"] == len(tel.steps)
    assert m["n_events"] == len(tel.events)
    assert set(m["phase_mean_s"]) <= set(PHASES)


# ---------------------------------------------------------------------
# profiler hooks
# ---------------------------------------------------------------------
def test_profile_annotations_smoke(deployed):
    """profile_annotations=True wraps dispatches in
    jax.profiler.TraceAnnotation — tokens must be unchanged (the
    annotation is a host-side label, not a computation)."""
    lm, tables = deployed
    w = _workload(lm.cfg.vocab)
    plain, _ = _run(lm, tables, w)
    tel = Telemetry(profile_annotations=True)
    annotated, _ = _run(lm, tables, w, telemetry=tel)
    assert annotated == plain
    from repro.serving.telemetry import _NULL_CTX
    assert tel.annotate("x") is not _NULL_CTX
    assert Telemetry().annotate("x") is _NULL_CTX


def test_annotations_off_enter_nothing(deployed, monkeypatch):
    """With telemetry off, or on without profile_annotations, the
    engine enters no profiler annotation at all; with them on it
    does."""
    import jax.profiler

    entered = []

    def counting(base):
        class Counting(base):
            def __enter__(self):
                entered.append(base)
                return super().__enter__()

        return Counting

    originals = {name: getattr(jax.profiler, name)
                 for name in ("TraceAnnotation", "StepTraceAnnotation")}
    for name, base in originals.items():
        monkeypatch.setattr(jax.profiler, name, counting(base))
    lm, tables = deployed
    w = _workload(lm.cfg.vocab)
    for telemetry in (None, Telemetry(),
                      Telemetry(profile_annotations=False)):
        _run(lm, tables, w, telemetry=telemetry, paged=True)
        assert entered == []
    _run(lm, tables, w, telemetry=Telemetry(profile_annotations=True),
         paged=True)
    assert set(entered) == set(originals.values())


def test_profiler_trace_holds_the_engine_spans(deployed, tmp_path):
    """A profiler trace of a served run with profile_annotations=True
    holds the step annotation, `phase/harvest` with `phase/advance`
    nested inside it, and the dispatch's `repro.serving/unified`."""
    import glob

    import jax.profiler
    from jax.profiler import ProfileData

    lm, tables = deployed
    tel = Telemetry(profile_annotations=True)
    with jax.profiler.trace(str(tmp_path)):
        _run(lm, tables, _workload(lm.cfg.vocab)[:2], telemetry=tel,
             paged=True)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    steps = spans.get("repro.serving/step", [])
    assert len(steps) == len(tel.steps)
    assert spans.get("repro.serving/unified")
    harvests, advances = spans["phase/harvest"], spans["phase/advance"]
    assert advances
    for a, b in advances:
        assert any(h0 <= a and b <= h1 for h0, h1 in harvests)
    for a, b in harvests:
        assert any(s0 <= a and b <= s1 for s0, s1 in steps)


def test_submit_requires_engine_stamp():
    """Telemetry needs req_id: Request defaults are the unstamped
    sentinel until ServingEngine.submit() assigns them."""
    r = Request(prompt=np.asarray([1, 2, 3]), max_new_tokens=2)
    assert r.req_id == -1 and r.arrival_time == 0.0
