"""Structured serving telemetry (DESIGN.md §Observability).

Three layers of observability for the continuous-batching engine, all
OFF by default and all **bit-neutral** by construction:

  request-lifecycle trace — typed events (`submit`, `admit`,
  `admit_reject`, `prefill_chunk`, `first_token`, `emit`, `preempt`,
  `resume`, `finish`)
  carrying monotonic host timestamps and request/slot/page context,
  buffered in-process as plain dicts and exported as JSONL
  (DESIGN.md §Observability ¶Event schema).  The integer engine's
  determinism makes a trace exactly *replayable*: identical submits
  produce bit-identical tokens, so a trace is a complete record of a
  serving run, not a sample of one.

  step-phase spans — a context-manager span per engine-step phase
  (`PHASES`), aggregated into one per-step record
  together with dispatch-queue depth, compile-cache hit/miss counters,
  and the arena's instantaneous gauges (slot occupancy, pages in use /
  high water, backpressure rejections) — DESIGN.md §Observability
  ¶Span model.

  profiler hooks — with `profile_annotations=True` each engine step is
  a `jax.profiler.StepTraceAnnotation` named `STEP_SPAN`, each phase
  span a `TraceAnnotation` named `phase/<phase>`, and `annotate()`
  wraps each device dispatch (`repro.serving/unified`, ...), so the
  host's phases land on the profiler's clock beside the device's
  programs.  Off by default: an annotation is allocated and entered
  per span, which is not free on the per-step path.

Bit-neutrality (DESIGN.md §Observability ¶Bit-neutrality): every hook
reads HOST state only — wall-clock stamps, python counters, the
host-side page table — never a device value, and adds no dispatch and
no traced computation.  Telemetry-on and telemetry-off engines
therefore produce token-for-token identical output, which
tests/test_telemetry.py pins on both arenas, sync and async.

The default is the `NullTelemetry` singleton (`NULL`): every hook a
no-op, every buffer an empty tuple — the off path costs one attribute
check or an empty method call per hook site (DESIGN.md §Observability
¶Overhead budget).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Set, Tuple

# The event schema: kind -> required payload fields.  Every event also
# carries "t" (monotonic seconds, time.perf_counter) and — when emitted
# inside an engine step — "step".  tools/trace_summary.py validates
# traces against exactly this table (missing fields / unknown kinds are
# malformed), so extending it is a one-place change.
EVENT_FIELDS: Dict[str, frozenset] = {
    "submit": frozenset({"req_id", "prompt_len", "max_new_tokens"}),
    "admit": frozenset({"req_id", "slot"}),
    "admit_reject": frozenset({"req_id", "reason"}),
    "prefill_chunk": frozenset({"req_id", "slot", "start", "end", "pages"}),
    "first_token": frozenset({"req_id", "slot", "token"}),
    "emit": frozenset({"req_id", "slot", "token"}),
    # preemption lifecycle (DESIGN.md §Scheduling): a policy evicted
    # the request (its pages reclaimed, its decode progress parked
    # host-side), and it later re-entered decode after re-prefilling.
    # `resume` carries no token — nothing is re-emitted, which is what
    # keeps emit count == n_generated across preemptions.
    "preempt": frozenset({"req_id", "slot", "reason", "n_generated"}),
    "resume": frozenset({"req_id", "slot", "n_preempts"}),
    # prefix cache lifecycle (DESIGN.md §Prefix-caching): at admission
    # the request either reused `pages` cached full pages covering its
    # first `tokens` positions (prefill skipped them) or matched
    # nothing; `cow_split` marks a copy-on-write — the slot was about
    # to write inside a shared/registered page and got a private copy.
    "prefix_hit": frozenset({"req_id", "slot", "pages", "tokens"}),
    "prefix_miss": frozenset({"req_id", "slot"}),
    "cow_split": frozenset({"req_id", "slot", "old_page", "new_page"}),
    "finish": frozenset({"req_id", "slot", "reason", "n_generated"}),
}

# The engine-step phases a span may time (DESIGN.md §Observability
# ¶Span model).  `unified_dispatch` is the chunked-mode step's single
# fused dispatch (decode + prefill rows in one kernel call — DESIGN.md
# §Serving ¶Unified attention kernel); `decode_dispatch` survives on
# the non-chunked (bucketed/exact) oracle paths.  `harvest` covers the
# wait for a dispatch's token vector AND `advance`, nested inside it:
# the host bookkeeping once the tokens are on the host (emits,
# finishes, prefix registration, chunk cursors).  So harvest's own
# time (harvest - advance) is the wait.  Under async dispatch (depth
# 1) `harvest` covers the drain of the PREVIOUS step's in-flight
# dispatch — the pipeline's one blocking point — so a fat `harvest`
# there is device time the host successfully overlapped, not host work.
PHASES: Tuple[str, ...] = (
    "admission",
    "plan_chunks",
    "unified_dispatch",
    "decode_dispatch",
    "harvest",
    "advance",
)

# The engine step's name in the profiler trace (DESIGN.md
# §Observability ¶Span model); a phase's is `phase/<phase>`.
STEP_SPAN = "repro.serving/step"


class _NullCtx:
    """Reusable no-op context manager (singleton `_NULL_CTX`)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class NullTelemetry:
    """The off-by-default sink: every hook a no-op, every buffer an
    empty tuple.  A single shared instance (`NULL`) serves every
    engine, so "telemetry off" allocates nothing per engine and
    records nothing ever (pinned by tests/test_telemetry.py)."""

    enabled = False
    events: tuple = ()
    steps: tuple = ()
    compile_hits = 0
    compile_misses = 0

    def begin_step(self, idx: int):
        pass

    def end_step(self, **gauges):
        pass

    def span(self, phase: str):
        return _NULL_CTX

    def event(self, kind: str, **fields):
        pass

    def dispatch(self, kind: str, key):
        pass

    def annotate(self, name: str):
        return _NULL_CTX

    def clear(self):
        pass


NULL = NullTelemetry()


class _Span:
    """Times one phase of the current step; re-entry within a step
    accumulates (the async harvest drains a deque)."""

    __slots__ = ("tel", "phase", "t0")

    def __init__(self, tel: "Telemetry", phase: str):
        self.tel = tel
        self.phase = phase

    def __enter__(self):
        self.t0 = self.tel.clock()
        return self

    def __exit__(self, *exc):
        cur = self.tel._cur
        if cur is not None:
            ph = cur["phases"]
            ph[self.phase] = (
                ph.get(self.phase, 0.0) + self.tel.clock() - self.t0
            )
        return False


class _AnnotatedSpan(_Span):
    """A `_Span` that also writes `phase/<phase>` into the profiler
    trace.  A TraceAnnotation records one interval per object, so each
    entry makes a new one."""

    __slots__ = ("name", "ann")

    def __init__(self, tel: "Telemetry", phase: str):
        super().__init__(tel, phase)
        self.name = "phase/" + phase

    def __enter__(self):
        self.ann = self.tel._annotation(self.name)
        self.ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.ann.__exit__(*exc)
        return False


class Telemetry:
    """Buffering telemetry sink (DESIGN.md §Observability).

    Events and per-step records accumulate as plain dicts; nothing is
    serialized until `export_trace` / `export_metrics`, so the enabled
    hot path is list-appends and perf_counter reads only (¶Overhead
    budget).  `ServingEngine.reset_stats()` clears the buffers along
    with the run statistics, so a measured window's trace starts clean
    after a warmup workload; the compile-cache seen-set deliberately
    survives `clear()` — warmed shapes stay compiled, so post-clear
    dispatches of those shapes are honest cache hits.
    """

    enabled = True

    def __init__(self, *, profile_annotations: bool = False):
        self.profile_annotations = bool(profile_annotations)
        self._span_cls = _Span
        if self.profile_annotations:
            from jax import profiler

            self._annotation = profiler.TraceAnnotation
            self._step_annotation = profiler.StepTraceAnnotation
            self._span_cls = _AnnotatedSpan
        self._step_ann = None
        self.clock = time.perf_counter
        self.events: List[dict] = []
        self.steps: List[dict] = []
        self.compile_hits = 0
        self.compile_misses = 0
        self._seen_shapes: Set[tuple] = set()
        self._cur: Optional[dict] = None
        self._step_idx: Optional[int] = None
        # one reusable span per phase: the hot path allocates nothing
        # for a span (phases never nest with themselves, and the
        # engine is single-threaded, so reuse is safe) — keeps
        # allocation pressure low enough that telemetry does not tip
        # Python GC cycles into the measured window (¶Overhead budget)
        self._spans: Dict[str, _Span] = {}

    # -- lifecycle events ----------------------------------------------
    def event(self, kind: str, **fields):
        """Record one typed event, stamped with the monotonic clock
        (and the current step index when inside a step)."""
        rec: Dict[str, Any] = {"event": kind, "t": self.clock()}
        if self._step_idx is not None:
            rec["step"] = self._step_idx
        rec.update(fields)
        self.events.append(rec)

    # -- step spans + gauges -------------------------------------------
    def begin_step(self, idx: int):
        if self.profile_annotations:
            self._step_ann = self._step_annotation(STEP_SPAN, step_num=idx)
            self._step_ann.__enter__()
        self._step_idx = idx
        self._cur = {"step": idx, "t": self.clock(), "phases": {}}

    def span(self, phase: str):
        """Context manager timing `phase` of the current step
        (reused per phase — see __init__)."""
        s = self._spans.get(phase)
        if s is None:
            s = self._spans[phase] = self._span_cls(self, phase)
        return s

    def end_step(self, **gauges):
        """Close the step record, folding in the engine's gauges
        (queue depth, arena occupancy/pages, rejection count, ...)."""
        cur = self._cur
        if cur is None:
            return
        cur["wall_s"] = self.clock() - cur["t"]
        cur["compile_hits"] = self.compile_hits
        cur["compile_misses"] = self.compile_misses
        cur.update(gauges)
        self.steps.append(cur)
        self._cur = None
        self._step_idx = None
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None

    # -- compile-cache counters ----------------------------------------
    def dispatch(self, kind: str, key):
        """Account one jitted dispatch of shape `key`: the first
        sighting of a (kind, key) is a compile-cache miss (a real XLA
        compile), every later one a hit.  The engine registers its
        warmup dispatches here too, so a warmed engine's serving
        window reads as all-hits — a mid-burst miss in the step
        records IS the TTFT spike it caused."""
        k = (kind, tuple(key))
        if k in self._seen_shapes:
            self.compile_hits += 1
        else:
            self._seen_shapes.add(k)
            self.compile_misses += 1

    # -- profiler hooks ------------------------------------------------
    def annotate(self, name: str):
        """`jax.profiler.TraceAnnotation(name)` when profiler hooks are
        on — host-side spans then line up with device traces — else a
        no-op context."""
        if not self.profile_annotations:
            return _NULL_CTX
        return self._annotation(name)

    # -- export --------------------------------------------------------
    def clear(self):
        """Drop buffered events/steps and zero the hit/miss counters
        (the shape seen-set survives — see class doc)."""
        self.events.clear()
        self.steps.clear()
        self.compile_hits = 0
        self.compile_misses = 0
        self._cur = None
        self._step_idx = None

    def metrics(self) -> dict:
        """Aggregate the step records: per-phase totals and means,
        compile counters, and the raw per-step series."""
        phase_s: Dict[str, float] = {}
        phase_n: Dict[str, int] = {}
        for s in self.steps:
            for ph, v in s["phases"].items():
                phase_s[ph] = phase_s.get(ph, 0.0) + v
                phase_n[ph] = phase_n.get(ph, 0) + 1
        return {
            "n_steps": len(self.steps),
            "n_events": len(self.events),
            "phase_total_s": phase_s,
            "phase_mean_s": {
                ph: phase_s[ph] / phase_n[ph] for ph in phase_s
            },
            "compile_hits": self.compile_hits,
            "compile_misses": self.compile_misses,
            "steps": self.steps,
        }

    def export_trace(self, path: str):
        """Write the event buffer as JSONL (one event per line) — the
        format tools/trace_summary.py consumes."""
        with open(path, "w") as f:
            for rec in self.events:
                f.write(json.dumps(rec) + "\n")

    def export_metrics(self, path: str):
        """Write the aggregated step metrics as one JSON document."""
        with open(path, "w") as f:
            json.dump(self.metrics(), f, indent=2)
            f.write("\n")
