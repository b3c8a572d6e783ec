"""Admission queue + prefill shape bucketing (mechanism).

Since the policy/mechanism split (DESIGN.md §Scheduling) the
*decisions* — admission order, capacity gating, chunk packing, who
decodes — live in serving/policy.py; this module is the queue
MECHANISM those policies read through the EngineView and the engine
manipulates when executing a StepPlan: FIFO storage (`submit` /
`requeue` / `take`), the per-step shape knobs (`SchedulerConfig`), and
prompt-shape bucketing (`bucket_len`).

The default FCFSPolicy reproduces the historical behavior exactly:
FCFS by arrival up to `max_prefills_per_step` (bounds per-step prefill
latency so active decodes are not starved — the unified prefill+decode
batch idea from the lmdeploy/turbomind decoder, specialized to
per-slot prefill + fused decode), gated by the arena-capacity
predicate.  The contiguous arena admits while a slot is free; the
paged arena admits while the request's worst-case page budget fits
(DESIGN.md §Serving ¶Paged KV).  FCFS admission is head-of-line
blocking: when the oldest request does not fit, nothing younger
overtakes it — out-of-pages backpressure stays FCFS-fair and
preemption-free.  Iteration: every leased slot advances one token
through a single fused decode step with a per-slot position vector;
completed slots are recycled the same step.

Chunked prefill (`prefill_chunk` > 0, dense and moe): admission only
leases a slot; the prompt then enters the arena `prefill_chunk` tokens
at a time through a *packed* compact dispatch — one (row-bucket,
chunk) prefill per engine step carrying the next chunk of every
prefilling request (capped by `max_chunks_per_step`, the fairness knob:
fewer chunk rows per step = less prefill compute stalling the decode
dispatch that follows it).  Long prompts therefore interleave with
ongoing decode instead of monopolizing a step, and a burst of arrivals
shares one dispatch instead of queueing B=1 prefills.  The packing
policy lives in `plan_chunks`: FIFO by admission order, one chunk per
request per step (chunks of one request are sequential by definition).

Async dispatch (`ServingConfig(dispatch_depth=1)`, DESIGN.md §Serving
¶Multi-device): every decision this module makes — `pop_if` admission,
`plan_chunks` packing — reads host-side state only (queue order, arena
counters, chunk cursors), never a device value.  That is what lets the
engine's DispatchQueue run the whole scheduling pass for step t+1 while
step t's fused decode is still executing on the device: the scheduler
needs no token to decide, so the only forced synchronization left is
the engine's token harvest.  Under that overlap admission sees slot
releases one harvest later than the synchronous engine — a pure timing
shift (per-request tokens are pinned identical by the parity tests).

Whole-prompt mode (`prefill_chunk` == 0, and always for non-dense
families): prompts are right-padded to a shape *bucket*
(`prefill_bucket` multiple) before a B=1 prefill, so the number of
distinct prefill compilations is bounded by max_len / prefill_bucket
regardless of how ragged the workload's prompt lengths are.  Padding
is exact for causally masked (dense-family) prefill: padded positions
sit strictly after the true last token, masking hides them from every
real position, and the first decode writes over them.  The engine
forces exact-length whole-prompt prefill for families whose prefill
state integrates every position (MoE routing, SSM/hybrid recurrences)
— see DESIGN.md §Serving.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Deque, Iterable, List, Optional, Tuple

from repro.serving.request import PrefillState, Request


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_prefills_per_step: int = 2  # admission cap per engine step
    prefill_bucket: int = 16  # prompt-shape bucket (compile bound)
    # chunked prefill (dense, moe): tokens per chunk; 0 falls back to
    # the whole-prompt bucketed path (the parity oracle)
    prefill_chunk: int = 32
    # fairness knob: chunk rows packed per dispatch (None: every
    # prefilling slot) — bounds per-step prefill compute so decode
    # latency stays flat while long prompts stream in
    max_chunks_per_step: Optional[int] = None


class Scheduler:
    """FCFS admission queue + prefill shape bucketing."""

    def __init__(self, cfg: SchedulerConfig, max_len: int):
        if cfg.prefill_bucket < 1:
            raise ValueError(
                f"prefill_bucket must be >= 1, got {cfg.prefill_bucket}"
            )
        if cfg.max_prefills_per_step < 1:
            raise ValueError(
                "max_prefills_per_step must be >= 1, "
                f"got {cfg.max_prefills_per_step}"
            )
        if cfg.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {cfg.prefill_chunk}"
            )
        if (cfg.max_chunks_per_step is not None
                and cfg.max_chunks_per_step < 1):
            raise ValueError(
                "max_chunks_per_step must be >= 1, "
                f"got {cfg.max_chunks_per_step}"
            )
        self.cfg = cfg
        self.max_len = max_len
        self.pending: Deque[Request] = collections.deque()

    # -- queue ----------------------------------------------------------
    def submit(self, req: Request):
        if req.prompt_len + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {req.prompt_len + req.max_new_tokens} "
                f"positions but the arena holds {self.max_len}"
            )
        self.pending.append(req)

    @property
    def n_pending(self) -> int:
        return len(self.pending)

    def requeue(self, req: Request):
        """Put a request back at the queue HEAD — the preemption
        requeue site (an evicted request was already served once; it
        must not lose its place to younger arrivals).  Priority
        policies re-sort the whole view anyway, so head placement is
        only load-bearing for FCFS-style orderings."""
        self.pending.appendleft(req)

    def take(self, req: Request) -> bool:
        """Remove a specific request from the queue (the engine's plan
        executor pops exactly what the policy admitted, wherever it
        sits).  Returns False when the request is not pending — a
        stale plan entry, skipped.  Matched by IDENTITY, not `==`:
        plans carry the very Request objects the view snapshotted, and
        dataclass equality over the numpy prompt raises on ambiguous
        truth for any non-identical pair it scans past."""
        for i, queued in enumerate(self.pending):
            if queued is req:
                del self.pending[i]
                return True
        return False

    def peek(self) -> Optional[Request]:
        """The FCFS queue head without popping it (None when empty).
        The engine's backpressure accounting reads this: when the head
        does not fit, IT is the blocked request — head-of-line blocking
        means nothing younger is even considered — so the telemetry
        `admit_reject` event names it (DESIGN.md §Observability)."""
        return self.pending[0] if self.pending else None

    # -- admission (legacy reference) -----------------------------------
    def pop_if(self, fits: Callable[[Request], bool]) -> Optional[Request]:
        """Pop the FCFS queue head if the arena predicate accepts it
        (head-of-line blocking — a too-big head request is
        backpressure, not a skip).  LEGACY: the engine no longer calls
        this — FCFSPolicy (serving/policy.py) simulates the same loop
        over the EngineView; kept as the reference semantics and for
        external callers."""
        if self.pending and fits(self.pending[0]):
            return self.pending.popleft()
        return None

    # -- chunk packing (legacy reference) -------------------------------
    def plan_chunks(
        self, prefilling: Iterable[PrefillState]
    ) -> List[Tuple[PrefillState, int, int]]:
        """FIFO packing for one chunked-prefill dispatch: (state,
        offset, n_tokens) triples — the next `prefill_chunk`-token
        chunk of each prefilling request, FIFO by admission order,
        capped at `max_chunks_per_step` rows (the fairness knob).  The
        final chunk of a source may be partial (n_tokens < chunk); the
        dispatch pads it and the engine reads logits only when
        offset + n_tokens reaches the source length.  LEGACY: the
        packing decision now lives in the policy (FCFSPolicy emits the
        same rows); kept as the reference semantics."""
        chunk = self.cfg.prefill_chunk
        cap = self.cfg.max_chunks_per_step
        plan: List[Tuple[PrefillState, int, int]] = []
        for st in prefilling:
            if cap is not None and len(plan) >= cap:
                break
            n = min(chunk, st.source_len - st.offset)
            plan.append((st, st.offset, n))
        return plan

    # -- shape bucketing ------------------------------------------------
    def bucket_len(self, prompt_len: int) -> int:
        """Padded prefill length for a prompt: next bucket multiple,
        capped at the arena's sequence capacity."""
        b = self.cfg.prefill_bucket
        return min(-(-prompt_len // b) * b, self.max_len)
