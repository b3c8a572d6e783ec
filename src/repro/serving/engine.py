"""`ServingEngine`: continuous batching over the integer-only model.

The engine owns a fixed-shape cache arena (an `Arena` — cache.SlotArena
or cache.PagedArena, built by cache.make_arena from the ServingConfig)
and drives the ID-representation `prefill` / `decode_step` of
models/lm.py.

Policy/mechanism split (DESIGN.md §Scheduling): the engine is pure
MECHANISM.  Every step it samples a read-only `EngineView` (queue,
per-slot progress + SLO clocks, arena gauges), asks its
`SchedulingPolicy` (serving/policy.py; FCFSPolicy by default) for a
`StepPlan`, and executes the plan — it makes no admission, packing,
eviction, or decode decision of its own:

  submit()            enqueue a Request (queue order; the POLICY
                        decides service order)
  step()              execute one StepPlan:
                        1. preempt the planned slots: reclaim their
                           pages (arena.release/release_pages) and
                           requeue the evicted requests with their
                           decode progress parked host-side — integer
                           determinism makes the later re-prefill
                           resume bit-exactly (¶Preemption
                           bit-exactness)
                        2. admit the planned requests (lease a slot,
                           commit the page budget)
                        3. ONE unified dispatch over every arena row
                           (DESIGN.md §Serving ¶Unified attention
                           kernel): decode rows carry their last
                           token at width 1 of the row, prefill rows
                           carry the next chunk of C tokens at their
                           per-slot offsets, free rows park at
                           INACTIVE_POS.  Every row takes its next
                           token from the dispatch's per-row
                           last-index logits — graduation and decode
                           are the same argmax.  Paged arenas run the
                           fused paged-attention kernel by default
                           (paged_kernel=False keeps the
                           write-then-gather oracle for the whole
                           step)
  run_until_drained() step until queue + prefills + slots are empty

Non-chunked modes (bucketed/exact, below) keep the separate
whole-prompt prefill + fused decode dispatches — they are the parity
oracles for the chunked path, not hot paths.

The prefill dispatch decision is made in ONE place (_prefill_mode):
"chunked" (dense and moe families, prefill_chunk > 0 — the default),
"bucketed" (chunking disabled: whole prompt at bucket-padded length,
B=1 — kept as the token-parity oracle for the chunked path), or
"exact" (ssm/hybrid: whole prompt at exact length — their recurrences
integrate every position, so neither padding nor garbage chunk rows
are admissible; DESIGN.md §Serving).  MoE layers route dropless on the
integer path and route padding and parked rows nowhere, so their rows
are as independent as a dense model's.

Greedy sampling is argmax on int32 logits — no dequantization anywhere
(the paper's integer-only deployment invariant; asserted on the cache
arena at construction).  Requests stream tokens through an optional
`on_token` callback the moment they are decoded.

Multi-device serving (DESIGN.md §Serving ¶Multi-device): with a
`mesh`, the decode and chunked-prefill dispatches are jitted with
EXPLICIT in/out shardings — tables and token/position vectors
replicated, the cache arena placed by its own sharding views
(`kv_shard=True` splits KV leaves along kv heads over the mesh "model"
axis; serving/cache.py) — and traced under the mesh + hints profile so
layer-level constraints and the per-shard-head paged kernel engage.
Sharded serving is BIT-EXACT with single-device serving: the integer
path's accumulations are associative and the softmax island is
per-(row, head), so partitioning cannot reorder anything observable.

Async dispatch (`dispatch_depth=1`, the `DispatchQueue`): the engine
runs a one-step-deep pipeline — while step t's unified dispatch
executes on the device, the host already runs step t+1's planning,
preemption, and admission, and only blocks (`np.asarray` on a
(B,)-token array, the only forced sync) at token harvest.  Chunk
materialization and the next dispatch follow the harvest: the decode
rows of dispatch t+1 need dispatch t's argmax, and chunk cursors
advance at harvest — the autoregressive feedback that bounds the
pipeline at ONE in-flight step.  Depth 1 produces token-for-token the
same output as the synchronous engine for row-independent families
(each request's greedy chain depends only on its own slot), which the
parity tests pin; request *timing* may shift by a step (admission
sees slot releases one harvest later).

Decode rows of free slots compute garbage that is never read; rows
are independent in every family (MoE routing on the integer path is
dropless), so active slots are bit-exact with the lockstep path.

Telemetry (`telemetry=`, DESIGN.md §Observability): the engine threads
an off-by-default, bit-neutral observability sink through every
lifecycle transition (typed trace events), every step phase (spans:
admission / plan_chunks / unified_dispatch / decode_dispatch /
harvest, with advance inside harvest), and every jitted dispatch
(compile-cache hit/miss accounting); with profile_annotations the
step, its phases and its dispatches land in the profiler trace.  All
hooks read host state only — no device values, no extra dispatches —
so enabling telemetry cannot change a single token (pinned by
tests/test_telemetry.py).  Independent of telemetry, per-token emit
stamps always accrue on RequestState/Completion, and stats() rolls
them up into p50/p95/p99 TTFT/ITL plus a queued/prefill/decode latency
breakdown — the SLO surface an open-loop harness or a preemption
scheduler reports through.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rep import Rep
from repro.kernels.paged_attention import TPU_PAGE_ALIGN, page_walk
from repro.layers.attention import INACTIVE_POS
from repro.serving.cache import (
    Arena,
    assert_integer_caches,
    make_arena,
)
from repro.serving.config import ServingConfig
from repro.serving.policy import (
    DecodeSnap,
    EngineView,
    FCFSPolicy,
    PendingSnap,
    PrefillSnap,
    StepPlan,
)
from repro.serving.request import (
    FINISH_LENGTH,
    FINISH_MAX_LEN,
    FINISH_STOP,
    Completion,
    PrefillState,
    Request,
    RequestState,
    ResumeState,
)
from repro.serving.scheduler import Scheduler
from repro.serving.telemetry import NULL as NULL_TELEMETRY


@dataclasses.dataclass
class _InFlightDecode:
    """One dispatched-but-unharvested fused decode step (the
    non-chunked modes' decode dispatch)."""

    tokens: Any  # device (n_slots,) int32 — the step's argmax
    slots: List[int]  # active slots at dispatch time


@dataclasses.dataclass
class _InFlightStep:
    """One dispatched-but-unharvested UNIFIED step (chunked mode):
    decode rows and prefill-chunk rows of the same dispatch."""

    tokens: Any  # device (n_slots,) int32 — per-row last-index argmax
    chunk_plan: List  # the (PrefillState, offset, n) triples dispatched
    decode_slots: List[int]  # active slots decoded by this dispatch


class DispatchQueue:
    """Host/device pipeline for the engine's fused step dispatches
    (DESIGN.md §Serving ¶Multi-device).

    depth 0 — synchronous: every dispatch is harvested in the same
    engine step (the pre-queue behavior, kept as the token-parity
    oracle for depth 1).

    depth 1 — double-buffered: the engine leaves one step (unified, or
    decode in the non-chunked modes) in flight and overlaps the NEXT
    step's host work (planning, preemption, admission) with it,
    harvesting only when the next dispatch needs the tokens.  Deeper
    pipelines are rejected: step t+1's input IS step t's argmax, so a
    second in-flight step would have to speculate tokens — out of
    scope for a bit-exact serving engine.
    """

    def __init__(self, depth: int = 0):
        if depth not in (0, 1):
            raise ValueError(
                "dispatch_depth must be 0 (synchronous) or 1 (the "
                "autoregressive token feedback bounds the pipeline at "
                f"one in-flight step), got {depth}"
            )
        self.depth = depth
        self._inflight: Deque[Any] = collections.deque()

    @property
    def pending(self) -> int:
        return len(self._inflight)

    def push(self, rec):
        if len(self._inflight) >= max(self.depth, 1):
            raise RuntimeError("dispatch queue overfilled")
        self._inflight.append(rec)

    def drain(self, harvest: Callable[[Any], None]):
        """Harvest every in-flight record (oldest first)."""
        while self._inflight:
            harvest(self._inflight.popleft())


class ServingEngine:
    def __init__(
        self,
        lm,
        tables,
        config: Optional[ServingConfig] = None,
        *,
        on_token: Optional[Callable[[int, int], None]] = None,
    ):
        cfg = self.config = config if config is not None else ServingConfig()
        if lm.cfg.input_mode != "tokens":
            raise ValueError(
                "ServingEngine serves token LMs "
                f"(input_mode={lm.cfg.input_mode!r})"
            )
        mesh = cfg.mesh
        if mesh is not None and "model" not in mesh.axis_names:
            raise ValueError(
                f'serving mesh needs a "model" axis, got {mesh.axis_names}'
            )
        self.lm = lm
        self.mesh = mesh
        self.kv_shard = bool(cfg.kv_shard)
        self.queue = DispatchQueue(cfg.dispatch_depth)
        # the scheduling brain (DESIGN.md §Scheduling): every per-step
        # decision flows through policy.plan(EngineView) -> StepPlan
        self.policy = cfg.policy if cfg.policy is not None else FCFSPolicy()
        # observability sink (DESIGN.md §Observability): the shared
        # no-op singleton unless the caller hands in a Telemetry —
        # every hook below is bit-neutral (host state only)
        self.tel = (
            NULL_TELEMETRY if cfg.telemetry is None else cfg.telemetry
        )
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # weights stay replicated over the serving mesh (the
            # weight-stationary serving layout): the arena — KV memory,
            # the serving bottleneck — is what shards.  One placement
            # at construction, so no per-step transfers.
            repl = NamedSharding(mesh, P())
            tables = jax.device_put(
                tables, jax.tree.map(lambda _: repl, tables)
            )
        self.tables = tables
        # paged attention path: the fused paged-attention kernel by
        # default (kernels/paged_attention.py — K/V stream page by page
        # through the table, no dense logical gather), or the
        # write-then-gather jnp oracle when paged_kernel=False.  Both
        # the decode and the unified (S-wide) dispatch pass it to the
        # model, so each compiled dispatch bakes the chosen path in.
        self.paged_kernel = cfg.paged and cfg.paged_kernel
        device = (
            mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
        )
        if (self.paged_kernel and device.platform == "tpu"
                and cfg.page_size % TPU_PAGE_ALIGN):
            raise ValueError(
                f"page_size {cfg.page_size}: the TPU paged-attention "
                f"kernel needs a multiple of {TPU_PAGE_ALIGN}"
            )
        self.arena: Arena = make_arena(lm, cfg)
        assert_integer_caches(
            self.arena.caches,
            allow_ssm_state=lm.cfg.family in ("ssm", "hybrid"),
        )
        self.sched = Scheduler(cfg.scheduler, cfg.max_len)
        self.on_token = on_token

        self.active: Dict[int, RequestState] = {}  # slot -> state
        # slot -> chunked-prefill progress; insertion order IS the
        # admission order policies see in EngineView.prefilling
        self.prefilling: Dict[int, PrefillState] = {}
        self.completed: List[Completion] = []
        # req_id -> decode progress parked by a preemption, waiting in
        # the pending queue for re-admission (¶Preemption bit-exactness)
        self._resume: Dict[int, ResumeState] = {}
        self._next_id = 0

        def _decode_step(t, token, caches, pos):
            logits, new_caches = lm.decode_step(
                t, token, caches, pos, paged_kernel=self.paged_kernel
            )
            # greedy argmax stays on-device: the async dispatch queue
            # harvests a (B,) token vector, never (B, 1, V) logits
            return jnp.argmax(logits[:, 0, :], axis=-1), new_caches

        def _prefill_one(t, prompt, last_index):
            caches = lm.init_caches(1, cfg.max_len, Rep.ID)
            return lm.prefill(t, prompt, caches, last_index=last_index)

        def _unified_step(t, toks, caches, start, last):
            # THE chunked-mode dispatch (DESIGN.md §Serving ¶Unified
            # attention kernel): lm.prefill_chunk over every arena row
            # at once — decode rows are width-1 chunks (last_index 0),
            # so one kernel call serves the mixed prefill+decode batch.
            logits, new_caches = lm.prefill_chunk(
                t, toks, caches, start, last, paged_kernel=self.paged_kernel
            )
            return jnp.argmax(logits[:, 0, :], axis=-1), new_caches

        if mesh is None:
            self._decode = jax.jit(_decode_step)
            # compiles once per prompt-shape bucket (bucket_len)
            self._prefill = jax.jit(_prefill_one)
            # the unified dispatch: compile-cache keyed on its
            # (n_slots, width) shape — exactly two widths exist, the
            # chunk width C (mixed/prefill steps) and 1 (decode-only
            # steps), both warmed by warmup()
            self._unified = jax.jit(_unified_step)
        else:
            # explicit in/out shardings (DESIGN.md §Serving
            # ¶Multi-device): replicated tables/tokens/positions are
            # prefix-broadcast over their pytrees; the arena supplies
            # the cache-view shardings, and pinning them on the outputs
            # keeps the arena's layout fixed across steps instead of
            # drifting with GSPMD propagation
            dv_sh = self.arena.decode_shardings()
            self._decode = jax.jit(
                _decode_step,
                in_shardings=(repl, repl, dv_sh, repl),
                out_shardings=(repl, dv_sh),
            )
            self._prefill = jax.jit(
                _prefill_one,
                in_shardings=(repl, repl, repl),
                out_shardings=(repl, repl),
            )
            self._unified = jax.jit(
                _unified_step,
                in_shardings=(repl, repl, dv_sh, repl, repl),
                out_shardings=(repl, dv_sh),
            )
        # THE prefill dispatch decision (single place; see module doc):
        #   chunked  — dense and moe, prefill_chunk > 0: packed
        #              fixed-shape chunk dispatch straight into the arena
        #   bucketed — chunking disabled: whole prompt at bucket-padded
        #              length, B=1 (the parity oracle).  Padding is
        #              exact only when rows/positions are causally
        #              independent: attention masks padded positions,
        #              and dropless MoE routing is per token.
        #   exact    — SSM/hybrid recurrent conv/scan state integrates
        #              every prefilled position — those families
        #              prefill the whole prompt at exact length (one
        #              compile per distinct length).  DESIGN.md §Serving.
        if lm.cfg.family in ("ssm", "hybrid"):
            self._prefill_mode = "exact"
        elif self.sched.cfg.prefill_chunk > 0:
            self._prefill_mode = "chunked"
        else:
            self._prefill_mode = "bucketed"
        self._bucketed_prefill = self._prefill_mode == "bucketed"

        # prefix caching (DESIGN.md §Prefix-caching): admission passes
        # source tokens to the arena, chunk/decode harvests publish
        # completed full pages, and the arena reports CoW splits back
        # through the on_cow hook.  Sharing rides the chunked path —
        # its per-chunk touch_range is what resolves CoW before every
        # dispatch, and a skipped prefix is just a chunk cursor that
        # starts late.
        # int4-packed KV pools (DESIGN.md §Serving ¶Sub-8-bit KV) only
        # ever see the paged write path; the contiguous write_slot /
        # SlotArena `_write` of the exact and bucketed prefill modes
        # assumes full-width int8 columns, so kv_bits=4 is restricted
        # to the chunked path where every token enters through
        # `_paged_column_write`.
        if cfg.kv_bits == 4 and self._prefill_mode != "chunked":
            raise ValueError(
                "kv_bits=4 requires the chunked prefill path "
                f"(prefill_chunk > 0, dense or moe family); this engine "
                f"is in "
                f"{self._prefill_mode!r} mode"
            )
        self._prefix_on = bool(cfg.prefix_cache)
        if self._prefix_on and self._prefill_mode != "chunked":
            raise ValueError(
                "prefix_cache=True requires the chunked prefill path "
                f"(prefill_chunk > 0, dense or moe family); this engine "
                f"is in "
                f"{self._prefill_mode!r} mode"
            )
        if self._prefix_on:
            self._page_size = cfg.page_size
            self.arena.on_cow = self._on_cow

        # run statistics
        self._steps = 0
        self._occupancy_sum = 0.0
        self._n_generated = 0
        self._max_active = 0
        self._n_admit_rejects = 0  # steps the policy reported a block
        self._n_preempts = 0  # policy evictions executed
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        # the paged kernel's walk in this step's dispatch (telemetry)
        self._walk: Dict[str, int] = {}

    # -- submission -----------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int = 16,
        stop_token: Optional[int] = None,
        priority: int = 0,
    ) -> int:
        """Enqueue a request; returns its req_id.  `prompt` may be a
        token array or an already-built Request.  `priority` is a
        policy hint (serving/policy.py) — FCFS ignores it."""
        req = (
            prompt
            if isinstance(prompt, Request)
            else Request(prompt, max_new_tokens, stop_token, priority)
        )
        self.arena.check_request(
            req.prompt_len, req.prompt_len + req.max_new_tokens
        )
        req.req_id = self._next_id
        self._next_id += 1
        req.arrival_time = time.perf_counter()
        self.sched.submit(req)
        if self.tel.enabled:
            self.tel.event(
                "submit",
                req_id=req.req_id,
                prompt_len=req.prompt_len,
                max_new_tokens=req.max_new_tokens,
            )
        return req.req_id

    # -- one scheduler iteration ---------------------------------------
    def step(self) -> bool:
        """Admit + chunk-prefill + fused-decode once.  Returns False if
        idle.  With `dispatch_depth=1` the decode dispatched here is
        harvested by the NEXT step (the DispatchQueue pipeline)."""
        if self._t_first is None:
            self._t_first = time.perf_counter()
        if self.queue.depth > 0:
            return self._step_async()
        return self._step_sync()

    def _step_sync(self) -> bool:
        """The synchronous engine step (dispatch_depth=0) — every
        device dispatch is harvested before the step returns; the
        token-parity oracle for the async path.  Telemetry spans time
        each phase (DESIGN.md §Observability ¶Span model); with the
        Null sink each span is a shared no-op context.

        Chunked mode issues ONE unified dispatch per step (decode rows
        + prefill-chunk rows in the same kernel call — DESIGN.md
        §Serving ¶Unified attention kernel); the non-chunked modes
        keep the separate fused decode."""
        tel = self.tel
        tel.begin_step(self._steps)
        with tel.span("admission"):
            plan = self.policy.plan(self._view())
            progressed = self._execute_preemptions(plan)
            progressed |= self._execute_admissions(plan)
        if self._prefill_mode == "chunked":
            chunk_plan = []
            if plan.chunks:
                with tel.span("plan_chunks"):
                    chunk_plan = self._materialize_chunks(plan)
            do_decode = bool(plan.decode and self.active)
            if chunk_plan or do_decode:
                rec = self._dispatch_unified(chunk_plan, do_decode)
                self._tick_stats()
                with tel.span("harvest"):
                    self._harvest_unified(rec)
                progressed = True
            else:
                self._tick_stats()
        else:
            self._tick_stats()
            if plan.decode and self.active:
                drec = self._dispatch_decode()
                with tel.span("harvest"):
                    self._harvest_decode(drec)
                progressed = True
        self._t_last = time.perf_counter()
        self._end_step()
        return progressed

    def _step_async(self) -> bool:
        """One-step-deep pipelined step (dispatch_depth=1): the host
        work above the harvest line — planning, preemption, admission —
        overlaps the step dispatched by the PREVIOUS engine step, which
        is still executing on the device.  The only forced sync is the
        (B,)-token harvest.  In chunked mode the harvest precedes chunk
        materialization and the next dispatch: the unified dispatch's
        decode rows need the in-flight argmax, and chunk cursors
        advance at harvest (the autoregressive feedback that bounds the
        pipeline at depth 1).

        Preemption is the exception: a plan that evicts slots first
        drains the in-flight step (the victim's token from step t is
        real output and must be harvested into its resume record, and
        an in-flight dispatch must not write through pages about to be
        reclaimed), then executes sync-style.  FCFS never preempts, so
        the overlap schedule below is the default async path."""
        tel = self.tel
        tel.begin_step(self._steps)
        progressed = self.queue.pending > 0
        unified = self._prefill_mode == "chunked"
        harvester = (
            self._harvest_unified if unified else self._harvest_decode
        )
        # (1) host scheduling: overlaps the in-flight dispatch.
        # Planning therefore sees slot releases (and chunk-cursor
        # advances) one harvest later than the sync engine — a timing
        # shift only; per-request tokens are pinned equal by the
        # parity tests (_materialize_chunks re-resolves the plan's
        # rows against live offsets after the harvest below).
        with tel.span("admission"):
            plan = self.policy.plan(self._view())
            if plan.preempt and self.queue.pending:
                # drain BEFORE evicting: harvest the victims' in-flight
                # tokens, and let finished slots release normally (the
                # preemption executor skips slots that emptied)
                with tel.span("harvest"):
                    self.queue.drain(harvester)
            progressed |= self._execute_preemptions(plan)
            progressed |= self._execute_admissions(plan)
        # (2) token harvest: the pipeline's one blocking point — under
        # depth 1 a fat `harvest` span is overlapped DEVICE time (the
        # previous step's dispatch finishing), not host work
        with tel.span("harvest"):
            self.queue.drain(harvester)
        if unified:
            chunk_plan = []
            if plan.chunks:
                with tel.span("plan_chunks"):
                    chunk_plan = self._materialize_chunks(plan)
            self._tick_stats()
            do_decode = bool(plan.decode and self.active)
            # (3) dispatch this step's unified step; harvested next step
            if chunk_plan or do_decode:
                self.queue.push(
                    self._dispatch_unified(chunk_plan, do_decode)
                )
                progressed = True
        else:
            self._tick_stats()
            # (3) dispatch this step's decode; the next step harvests it
            if plan.decode and self.active:
                self.queue.push(self._dispatch_decode())
                progressed = True
        self._t_last = time.perf_counter()
        self._end_step()
        return progressed

    # -- plan construction + execution (mechanism only) -----------------
    def _view(self) -> EngineView:
        """Sample the read-only host-state snapshot the policy plans
        from (DESIGN.md §Scheduling ¶Policy contract).  Host counters
        only — building a view never waits on the device, which is what
        lets planning overlap an in-flight decode."""
        arena = self.arena
        pending = []
        for r in self.sched.pending:
            resume = self._resume.get(r.req_id)
            n_gen = len(resume.tokens) if resume is not None else 0
            # resume re-prefills prompt + tokens[:-1] (source_len);
            # the page commitment is the request's own worst case,
            # minus whatever prefix the cache already holds
            # (need_pages is the SUFFIX-ONLY charge when the prefix
            # cache is on — DESIGN.md §Prefix-caching)
            source_len = r.prompt_len + max(n_gen - 1, 0)
            if self._prefix_on:
                need = arena.admit_cost(
                    r.prompt_len + r.max_new_tokens,
                    tokens=self._resume_source(r, resume),
                )
            else:
                need = arena.pages_needed(r.prompt_len + r.max_new_tokens)
            pending.append(
                PendingSnap(
                    req=r,
                    req_id=r.req_id,
                    priority=r.priority,
                    arrival_time=r.arrival_time,
                    prompt_len=r.prompt_len,
                    max_new_tokens=r.max_new_tokens,
                    source_len=source_len,
                    need_pages=need,
                    n_generated=n_gen,
                )
            )
        prefilling = tuple(
            PrefillSnap(
                req_id=st.request.req_id,
                slot=slot,
                priority=st.request.priority,
                arrival_time=st.request.arrival_time,
                admit_time=st.admit_time,
                offset=st.offset,
                total=st.source_len,
                is_resume=st.resume is not None,
                pages_committed=arena.committed_for(slot),
            )
            for slot, st in self.prefilling.items()
        )
        active = tuple(
            DecodeSnap(
                req_id=st.request.req_id,
                slot=slot,
                priority=st.request.priority,
                arrival_time=st.request.arrival_time,
                admit_time=st.admit_time,
                first_token_time=st.first_token_time,
                n_generated=len(st.tokens),
                budget_left=st.request.max_new_tokens - len(st.tokens),
                pages_committed=arena.committed_for(slot),
            )
            for slot, st in self.active.items()
        )
        cfg = self.sched.cfg
        return EngineView(
            now=time.perf_counter(),
            pending=tuple(pending),
            prefilling=prefilling,
            active=active,
            free_slots=arena.n_free,
            budget_left=arena.budget_left,
            gauges=arena.gauges(),
            prefill_mode=self._prefill_mode,
            prefill_chunk=cfg.prefill_chunk,
            max_chunks_per_step=cfg.max_chunks_per_step,
            max_prefills_per_step=cfg.max_prefills_per_step,
        )

    def _execute_preemptions(self, plan: StepPlan) -> bool:
        """Evict the planned slots (reversed, so appendleft-requeueing
        leaves them at the queue head in plan order).  Slots that are
        no longer leased — e.g. finished during the async drain that
        preceded this — are skipped: plans are advisory against the
        state the engine actually holds."""
        did = False
        for slot in reversed(plan.preempt):
            did |= self._preempt_slot(slot)
        return did

    def _preempt_slot(self, slot: int) -> bool:
        """The reclaim half of preemption (DESIGN.md §Scheduling):
        release the slot's pages + lease, park decode progress in a
        host-side ResumeState, and requeue the request at the queue
        head.  Nothing device-side is touched beyond the release —
        re-prefill rebuilds the KV image bit-exactly on resume."""
        if slot in self.prefilling:
            st = self.prefilling.pop(slot)
            req, resume = st.request, st.resume
            if resume is not None:
                resume.n_preempts += 1
        elif slot in self.active:
            ast = self.active.pop(slot)
            req = ast.request
            resume = ResumeState(
                tokens=list(ast.tokens),
                first_token_time=ast.first_token_time,
                admit_time=ast.admit_time,
                emit_times=list(ast.emit_times),
                n_preempts=ast.n_preempts + 1,
            )
        else:
            return False  # already finished/released; nothing to evict
        n_gen = len(resume.tokens) if resume is not None else 0
        self._n_preempts += 1
        if self.tel.enabled:
            self.tel.event(
                "preempt",
                req_id=req.req_id,
                slot=slot,
                reason="policy",
                n_generated=n_gen,
            )
        self.arena.release(slot)  # pages + lease back to the pool
        if resume is not None:
            self._resume[req.req_id] = resume
        self.sched.requeue(req)
        return True

    def _execute_admissions(self, plan: StepPlan) -> bool:
        """Lease slots to the planned requests, in plan order.  The
        arena predicate is re-checked per admission (defense against a
        policy over-promising); the policy's rejects are accounting
        only and recorded as admit_reject events."""
        progressed = False
        for req in plan.admit:
            if not self.sched.take(req):
                continue  # not pending anymore; stale plan entry
            tokens = (
                self._resume_source(req, self._resume.get(req.req_id))
                if self._prefix_on
                else None
            )
            if not self.arena.can_admit(
                req.prompt_len,
                req.prompt_len + req.max_new_tokens,
                tokens=tokens,
            ):
                # the plan over-committed: put the request back where
                # the policy found it and count the block
                self.sched.requeue(req)
                plan.rejects.append(
                    (
                        req.req_id,
                        self.arena.reject_reason(
                            req.prompt_len,
                            req.prompt_len + req.max_new_tokens,
                        ),
                    )
                )
                break
            self._admit(req)
            progressed = True
        self._n_admit_rejects += len(plan.rejects)
        if self.tel.enabled:
            for req_id, reason in plan.rejects:
                self.tel.event(
                    "admit_reject", req_id=req_id, reason=reason
                )
        return progressed

    def _materialize_chunks(
        self, plan: StepPlan
    ) -> List[Tuple[PrefillState, int, int]]:
        """Resolve the plan's (req_id, n) chunk rows against live
        prefill state: the engine owns offsets (mechanism), the policy
        owns membership/order/row count.  n is clamped to the compiled
        chunk width and the remaining source; empty or stale rows are
        dropped."""
        if not plan.chunks:
            return []
        by_id = {
            st.request.req_id: st for st in self.prefilling.values()
        }
        C = self.sched.cfg.prefill_chunk
        out: List[Tuple[PrefillState, int, int]] = []
        seen = set()
        for req_id, n in plan.chunks:
            st = by_id.get(req_id)
            if st is None or req_id in seen:
                continue
            seen.add(req_id)
            n = min(int(n), C, st.source_len - st.offset)
            if n > 0:
                out.append((st, st.offset, n))
        return out

    def _tick_stats(self):
        self._occupancy_sum += self.arena.n_leased / self.arena.n_slots
        self._max_active = max(self._max_active, len(self.active))
        self._steps += 1

    def _end_step(self):
        """Close the telemetry step record, folding in the queue depth
        and the arena's instantaneous gauges (host counters only)."""
        if not self.tel.enabled:
            return
        self.tel.end_step(
            queue_depth=self.queue.pending,
            n_pending=self.sched.n_pending,
            n_active=len(self.active),
            n_prefilling=len(self.prefilling),
            admit_rejects=self._n_admit_rejects,
            **self.arena.gauges(),
            **self._walk,
        )
        self._walk = {}

    def _record_walk(self, pos: np.ndarray, width: int):
        """Telemetry gauges of the paged kernel's page walk in one
        dispatch's full-attention layers, from the host positions the
        dispatch carries: `paged_pages_walked` sums each row's pages
        lo .. hi (`kernels.paged_attention.page_walk`), against
        `paged_pages_table`, the whole B x pages-per-slot table."""
        if not (self.tel.enabled and self.paged_kernel):
            return
        pps = self.arena.pages_per_slot
        lo, hi = page_walk(pos, width, self.arena.page_size, pps, xp=np)
        self._walk = {
            "paged_pages_walked": int(np.sum(hi - lo)),
            "paged_pages_table": pos.size * pps,
        }

    def _dispatch_decode(self) -> _InFlightDecode:
        """Enqueue one fused decode over every active slot (async wrt
        the host: jax returns futures; nothing blocks here)."""
        tel = self.tel
        with tel.span("decode_dispatch"):
            B = self.arena.n_slots
            toks = np.zeros((B, 1), np.int32)
            # rows without an active decode (free slots, slots still
            # mid-prefill) are parked at INACTIVE_POS: their cache
            # writes mask to no-ops, so the fused step can never
            # clobber a neighbor's prefilled positions
            pos = np.full((B,), INACTIVE_POS, np.int32)
            for slot, st in self.active.items():
                toks[slot, 0] = st.last_token
                pos[slot] = st.pos
                # paged arena: allocate the page holding `pos` before
                # the decode that writes there (no-op for SlotArena)
                self.arena.touch(slot, st.pos)
            self._record_walk(pos, 1)
            tel.dispatch("decode", (B,))
            with self._dispatch_ctx(), tel.annotate(
                "repro.serving/decode"
            ):
                nxt, new_caches = self._decode(
                    self.tables,
                    jnp.asarray(toks),
                    self.arena.decode_view(),
                    jnp.asarray(pos),
                )
            self.arena.absorb(new_caches)
        return _InFlightDecode(tokens=nxt, slots=list(self.active))

    def _harvest_decode(self, rec: _InFlightDecode):
        """Block on the step's token vector, then advance host state
        under the `advance` span (the rest of `harvest` is the wait).
        Slots in `rec.slots` cannot have been released in between: the
        only release site is this harvest."""
        nxt = np.asarray(rec.tokens)  # the pipeline's blocking point
        with self.tel.span("advance"):
            now = time.perf_counter()
            for slot in rec.slots:
                st = self.active[slot]
                tok = int(nxt[slot])
                st.tokens.append(tok)
                st.last_token = tok
                st.pos += 1
                st.emit_times.append(now)  # the token's host-visible stamp
                self.arena.advance(slot)
                if self._prefix_on and st.pos % self._page_size == 0:
                    # a page just filled (positions [0, pos) are written
                    # and final): publish it.  This is what keeps a later
                    # preemption victim's pages warm through its release —
                    # the resume re-prefills only the unregistered tail.
                    self.arena.register_prefix(
                        slot,
                        np.concatenate(
                            [
                                st.request.prompt,
                                np.asarray(st.tokens, np.int32),
                            ]
                        ),
                        st.pos,
                    )
                self._emit(st.request, tok, slot)
                self._maybe_finish(st, now)

    def run_until_drained(
        self, max_steps: int = 1_000_000
    ) -> List[Completion]:
        """Step until the queue, in-flight prefills, in-flight decode
        dispatches, and every slot are empty."""
        steps = 0
        while (self.sched.n_pending or self.prefilling or self.active
               or self.queue.pending):
            if steps >= max_steps:
                raise RuntimeError(f"not drained after {max_steps} steps")
            self.step()
            steps += 1
        return list(self.completed)

    # -- internals ------------------------------------------------------
    def _dispatch_ctx(self):
        """Trace-time context for the jitted dispatches: the serving
        mesh + hints profile (layer constraints, the per-shard-head
        paged kernel).  A no-op without a mesh — and in the
        mesh-but-unsharded ablation (kv_shard=False): there the arena
        is pinned replicated, so head-sharding constraints inside the
        step would only buy a full reshard round-trip per dispatch.
        Entering per call is cheap; only the tracing call of each
        shape reads it."""
        if self.mesh is None or not self.kv_shard:
            return contextlib.nullcontext()
        from repro.sharding.hints import use_profile

        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(use_profile(self.mesh))
        return stack

    def _resume_source(
        self, req: Request, resume: Optional[ResumeState]
    ) -> np.ndarray:
        """What to prefill: the prompt, or prompt + tokens[:-1] for a
        preempted request — whose last-index logits regenerate
        tokens[-1] exactly (¶Preemption bit-exactness)."""
        if resume is None:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(resume.tokens[:-1], np.int32)]
        )

    def _admit(self, req: Request):
        """Lease a slot and start the request's prefill (mode-dependent:
        chunked admission only enqueues; whole-prompt prefills now).
        The slot-lease stamp ends the request's `queued_s` window.  A
        preempted request re-enters here: its parked ResumeState rides
        the PrefillState and its original stamps survive."""
        resume = self._resume.pop(req.req_id, None)
        if self._prefill_mode == "chunked":
            source = self._resume_source(req, resume)
            slot = self.arena.alloc(
                req.req_id,
                int(source.size),
                req.prompt_len + req.max_new_tokens,
                written=0,  # partial-prefill state: chunks arrive later
                tokens=source if self._prefix_on else None,
            )
            # shared-prefix skip: the arena reports how many leading
            # positions admission installed from the cache — the chunk
            # cursor starts there, so only the unshared tail prefills
            # (a preempted victim whose pages stayed warm re-prefills
            # at most one chunk — DESIGN.md §Prefix-caching ¶Warm
            # pages)
            off0 = int(self.arena.lengths[slot]) if self._prefix_on else 0
            self.prefilling[slot] = PrefillState(
                request=req,
                slot=slot,
                offset=off0,
                admit_time=(
                    resume.admit_time
                    if resume is not None
                    else time.perf_counter()
                ),
                source=source,
                resume=resume,
            )
            if self.tel.enabled:
                self.tel.event("admit", req_id=req.req_id, slot=slot)
                if self._prefix_on:
                    pages = int(self.arena.shared_at_admit[slot])
                    if pages:
                        self.tel.event(
                            "prefix_hit",
                            req_id=req.req_id,
                            slot=slot,
                            pages=pages,
                            tokens=off0,
                        )
                    else:
                        self.tel.event(
                            "prefix_miss", req_id=req.req_id, slot=slot
                        )
            return
        self._admit_whole(req, resume)

    def _admit_whole(
        self, req: Request, resume: Optional[ResumeState] = None
    ):
        """Prefill at batch 1 (bucketed or exact shape) and lease a
        slot — the one-shot path (parity oracle; non-dense families).
        On resume the source is prompt + tokens[:-1] and the prefill's
        last-index argmax must equal tokens[-1] (asserted)."""
        source = self._resume_source(req, resume)
        L = int(source.size)
        slot = self.arena.alloc(
            req.req_id,
            L,
            req.prompt_len + req.max_new_tokens,
        )
        admit_t = (
            resume.admit_time if resume is not None
            else time.perf_counter()
        )
        if self.tel.enabled:
            self.tel.event("admit", req_id=req.req_id, slot=slot)
        Pb = self.sched.bucket_len(L) if self._bucketed_prefill else L
        padded = np.zeros((1, Pb), np.int32)
        padded[0, :L] = source
        self.tel.dispatch("prefill", (Pb,))
        # first token: greedy on the TRUE last source position (padded
        # positions after it are causally invisible to it)
        with self._dispatch_ctx(), self.tel.annotate(
            "repro.serving/prefill"
        ):
            logits, single = self._prefill(
                self.tables, jnp.asarray(padded), jnp.int32(L - 1)
            )
        first = int(jnp.argmax(logits[0, 0]))
        self.arena.write_slot(slot, single)
        now = time.perf_counter()
        if resume is not None:
            self._resume_decoding(req, slot, first, now, resume)
        else:
            self._start_decoding(req, slot, first, now, admit_t)

    def _dispatch_unified(
        self,
        chunk_plan: List[Tuple[PrefillState, int, int]],
        do_decode: bool,
    ) -> _InFlightStep:
        """THE chunked-mode dispatch (DESIGN.md §Serving ¶Unified
        attention kernel): one fused call over every arena row — row
        index IS the slot, no compaction.  Decode rows carry their
        last token as a width-1 chunk at their decode position
        (last_index 0: the same per-row last-index argmax graduates
        prefills and advances decodes); prefill rows carry the next
        chunk of their source at their per-slot offsets (last_index
        n - 1); everything else — free slots, decode rows when the
        plan pauses decode, the padded tail of a final partial chunk —
        parks at INACTIVE_POS, where writes mask to no-ops and the
        attention output is garbage the harvest never reads.

        The dispatch width is the chunk width C when any prefill row
        rides along and 1 on decode-only steps, so exactly TWO compile
        shapes exist per engine ((n_slots, C) and (n_slots, 1) — both
        warmed by warmup()).  Decode rows under width C write C - 1
        garbage columns past their position — each lands either in
        the slot's own current page (overwritten by a later real write
        before any causally visible read) or on the PAGE_NULL trash
        page, exactly like the padded tail of a partial chunk, so the
        garbage is unobservable (the kernel masks every position past
        the row's query position)."""
        tel = self.tel
        with tel.span("unified_dispatch"):
            B = self.arena.n_slots
            C = self.sched.cfg.prefill_chunk
            W = C if chunk_plan else 1
            toks = np.zeros((B, W), np.int32)
            start = np.full((B,), INACTIVE_POS, np.int32)
            last = np.zeros((B,), np.int32)
            decode_slots: List[int] = []
            if do_decode:
                for slot, st in self.active.items():
                    toks[slot, 0] = st.last_token
                    start[slot] = st.pos
                    # paged arena: allocate the page holding `pos`
                    # before the write there (no-op for SlotArena)
                    self.arena.touch(slot, st.pos)
                    decode_slots.append(slot)
            for st, off, n in chunk_plan:
                toks[st.slot, :n] = st.source[off:off + n]
                start[st.slot] = off
                last[st.slot] = n - 1
                # paged arena: allocate pages covering the chunk before
                # the dispatch writes there (no-op for SlotArena; the
                # padded tail of a final partial chunk lands on the
                # trash page)
                self.arena.touch_range(st.slot, off, off + n)
                if tel.enabled:
                    # chunk span + the physical pages it landed on
                    # (touch_range just materialized them)
                    tel.event(
                        "prefill_chunk",
                        req_id=st.request.req_id,
                        slot=st.slot,
                        start=off,
                        end=off + n,
                        pages=self.arena.span_pages(st.slot, off, off + n),
                    )
            self._record_walk(start, W)
            tel.dispatch("unified", (B, W))
            with self._dispatch_ctx(), tel.annotate(
                "repro.serving/unified"
            ):
                nxt, new_caches = self._unified(
                    self.tables,
                    jnp.asarray(toks),
                    self.arena.decode_view(),
                    jnp.asarray(start),
                    jnp.asarray(last),
                )
            self.arena.absorb(new_caches)
        return _InFlightStep(
            tokens=nxt, chunk_plan=chunk_plan, decode_slots=decode_slots
        )

    def _harvest_unified(self, rec: _InFlightStep):
        """Block on the step's token vector, then advance host state
        for both row kinds under the `advance` span (the rest of
        `harvest` is the wait).  Decode slots in `rec.decode_slots` cannot have
        been released in between (the only release site is a harvest);
        chunk rows advance their cursors and graduate when their final
        chunk just completed — a graduating row's first decode rides
        the NEXT unified dispatch.  A resuming row re-enters decode
        instead of emitting a first token (¶Preemption
        bit-exactness)."""
        nxt = np.asarray(rec.tokens)  # the pipeline's blocking point
        with self.tel.span("advance"):
            now = time.perf_counter()
            for slot in rec.decode_slots:
                st = self.active[slot]
                tok = int(nxt[slot])
                st.tokens.append(tok)
                st.last_token = tok
                st.pos += 1
                st.emit_times.append(now)
                self.arena.advance(slot)
                if self._prefix_on and st.pos % self._page_size == 0:
                    # a page just filled (positions [0, pos) are written
                    # and final): publish it — see _harvest_decode
                    self.arena.register_prefix(
                        slot,
                        np.concatenate(
                            [
                                st.request.prompt,
                                np.asarray(st.tokens, np.int32),
                            ]
                        ),
                        st.pos,
                    )
                self._emit(st.request, tok, slot)
                self._maybe_finish(st, now)
            for st, off, n in rec.chunk_plan:
                self.arena.advance(st.slot, n)
                if self._prefix_on:
                    # the chunk completed every position below off + n:
                    # its full pages are final — publish them so later
                    # requests (and this request's own resume) share them
                    self.arena.register_prefix(st.slot, st.source, off + n)
                if off + n < st.source_len:
                    st.offset = off + n  # carried into the next dispatch
                    continue
                del self.prefilling[st.slot]  # final chunk completed
                if st.resume is not None:
                    self._resume_decoding(
                        st.request, st.slot, int(nxt[st.slot]), now, st.resume
                    )
                else:
                    self._start_decoding(
                        st.request, st.slot, int(nxt[st.slot]), now,
                        st.admit_time,
                    )

    def _start_decoding(self, req: Request, slot: int, first: int,
                        now: float, admit_time: float):
        """Graduate a prefilled request to the fused decode batch; its
        TTFT clock stops here (first generated token)."""
        st = RequestState(
            request=req,
            slot=slot,
            tokens=[first],
            last_token=first,
            pos=req.prompt_len,
            first_token_time=now,
            admit_time=admit_time,
            emit_times=[now],
        )
        self.active[slot] = st
        if self.tel.enabled:
            self.tel.event(
                "first_token", req_id=req.req_id, slot=slot, token=first
            )
        self._emit(req, first, slot)
        self._maybe_finish(st, now)

    def _resume_decoding(self, req: Request, slot: int,
                         predicted: int, now: float,
                         resume: ResumeState):
        """Re-enter decode after a preemption's re-prefill.  The
        re-prefilled source was prompt + tokens[:-1], so its last-index
        argmax must regenerate tokens[-1] — the integer path is
        deterministic, making this THE runtime oracle for preemption
        bit-exactness (DESIGN.md §Scheduling).  No token is emitted:
        everything in `resume.tokens` was already emitted before the
        eviction; decode continues from tokens[-1] at the exact
        position the victim was stopped at (pos = P + len(tokens) - 1,
        the next cache write position)."""
        if predicted != resume.tokens[-1]:
            raise RuntimeError(
                "resume parity violated: re-prefill regenerated token "
                f"{predicted} but the preempted request had emitted "
                f"{resume.tokens[-1]} (req {req.req_id})"
            )
        st = RequestState(
            request=req,
            slot=slot,
            tokens=list(resume.tokens),
            last_token=resume.tokens[-1],
            pos=req.prompt_len + len(resume.tokens) - 1,
            first_token_time=resume.first_token_time,
            admit_time=resume.admit_time,
            emit_times=list(resume.emit_times),
            n_preempts=resume.n_preempts,
        )
        self.active[slot] = st
        if self.tel.enabled:
            self.tel.event(
                "resume",
                req_id=req.req_id,
                slot=slot,
                n_preempts=resume.n_preempts,
            )

    def _on_cow(self, slot: int, old_page: int, new_page: int):
        """Arena hook: a copy-on-write split happened while touching
        `slot` (DESIGN.md §Prefix-caching ¶Copy-on-write).  Fires
        inside the pre-dispatch touch loop, so the slot is always in
        prefilling or active here."""
        if not self.tel.enabled:
            return
        st = self.prefilling.get(slot) or self.active.get(slot)
        req_id = st.request.req_id if st is not None else -1
        self.tel.event(
            "cow_split",
            req_id=req_id,
            slot=slot,
            old_page=old_page,
            new_page=new_page,
        )

    def _emit(self, req: Request, tok: int, slot: int):
        self._n_generated += 1
        if self.tel.enabled:
            self.tel.event("emit", req_id=req.req_id, slot=slot, token=tok)
        if self.on_token is not None:
            self.on_token(req.req_id, tok)

    def _maybe_finish(self, st: RequestState, now: float):
        req = st.request
        reason = None
        if req.stop_token is not None and st.last_token == req.stop_token:
            reason = FINISH_STOP
        elif len(st.tokens) >= req.max_new_tokens:
            reason = FINISH_LENGTH
        elif st.pos >= self.arena.max_len:
            reason = FINISH_MAX_LEN  # unreachable when submit() validates
        if reason is None:
            return
        self.completed.append(
            Completion(
                req_id=req.req_id,
                prompt_len=req.prompt_len,
                tokens=list(st.tokens),
                finish_reason=reason,
                arrival_time=req.arrival_time,
                first_token_time=st.first_token_time,
                finish_time=now,
                admit_time=st.admit_time,
                emit_times=list(st.emit_times),
                n_preempts=st.n_preempts,
            )
        )
        if self.tel.enabled:
            self.tel.event(
                "finish",
                req_id=req.req_id,
                slot=st.slot,
                reason=reason,
                n_generated=len(st.tokens),
            )
        del self.active[st.slot]
        self.arena.release(st.slot)

    # -- warmup ---------------------------------------------------------
    def warmup(self):
        """Precompile every dispatch shape this engine can emit — in
        chunked mode the TWO unified widths ((n_slots, C) for
        mixed/prefill steps and (n_slots, 1) for decode-only steps),
        otherwise the fused decode — so no compile lands inside a
        serving window (a mid-burst compile inflates the TTFT of
        everything queued behind it).  All warmup rows are parked at
        INACTIVE_POS: writes mask to no-ops and results are discarded,
        so arena state is untouched.  Requires an idle engine.
        Whole-prompt prefill compiles per prompt-length bucket as
        requests arrive and is not warmed here (lengths are
        workload-dependent)."""
        if (self.sched.n_pending or self.prefilling or self.active
                or self.queue.pending):
            raise RuntimeError("warmup on a non-idle engine")
        B = self.arena.n_slots
        parked = np.full((B,), INACTIVE_POS, np.int32)
        if self._prefill_mode != "chunked":
            # register warmed shapes with the telemetry compile-cache
            # accounting: post-warmup dispatches of these shapes are
            # HITS
            self.tel.dispatch("decode", (B,))
            with self._dispatch_ctx():
                jax.block_until_ready(self._decode(
                    self.tables,
                    jnp.zeros((B, 1), jnp.int32),
                    self.arena.decode_view(),
                    jnp.asarray(parked),
                ))
            return
        C = self.sched.cfg.prefill_chunk
        for W in (1, C):
            self.tel.dispatch("unified", (B, W))
            with self._dispatch_ctx():
                nxt, caches = self._unified(
                    self.tables,
                    jnp.zeros((B, W), jnp.int32),
                    self.arena.decode_view(),
                    jnp.asarray(parked),
                    jnp.zeros((B,), jnp.int32),
                )
            jax.block_until_ready(nxt)
            # identity round-trip (every write was masked): warms the
            # absorb path too
            self.arena.absorb(caches)

    # -- statistics -----------------------------------------------------
    def reset_stats(self):
        """Zero run statistics and the completion log (e.g. after a
        warmup workload that pre-compiled the jit'd steps).  Requires
        an idle engine — in-flight state would skew the next window."""
        if (self.sched.n_pending or self.prefilling or self.active
                or self.queue.pending):
            raise RuntimeError("reset_stats on a non-idle engine")
        self.completed.clear()
        self._steps = 0
        self._occupancy_sum = 0.0
        self._n_generated = 0
        self._max_active = 0
        self._n_admit_rejects = 0
        self._n_preempts = 0
        self._t_first = None
        self._t_last = None
        self.arena.reset_peaks()
        # start the measured window's trace clean too (the telemetry
        # compile-cache seen-set survives: warmed shapes stay compiled)
        self.tel.clear()

    def stats(self) -> dict:
        wall = (
            (self._t_last - self._t_first)
            if self._t_first is not None and self._t_last is not None
            else 0.0
        )
        ttfts = [c.ttft for c in self.completed]
        itls = [d for c in self.completed for d in c.itl]
        queued = [c.queued_s for c in self.completed]
        prefills = [c.prefill_s for c in self.completed]
        decodes = [c.decode_s for c in self.completed]
        out = {
            "n_completed": len(self.completed),
            "n_generated": self._n_generated,
            "steps": self._steps,
            "wall_s": wall,
            "throughput_tok_s": (self._n_generated / wall) if wall else 0.0,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "p50_ttft_s": float(np.percentile(ttfts, 50)) if ttfts else 0.0,
            "p95_ttft_s": float(np.percentile(ttfts, 95)) if ttfts else 0.0,
            "p99_ttft_s": float(np.percentile(ttfts, 99)) if ttfts else 0.0,
            "max_ttft_s": float(np.max(ttfts)) if ttfts else 0.0,
            # inter-token latency: pooled per-request emit gaps
            # (DESIGN.md §Observability) — the steady-state SLO metric
            "mean_itl_s": float(np.mean(itls)) if itls else 0.0,
            "p50_itl_s": float(np.percentile(itls, 50)) if itls else 0.0,
            "p95_itl_s": float(np.percentile(itls, 95)) if itls else 0.0,
            "p99_itl_s": float(np.percentile(itls, 99)) if itls else 0.0,
            # latency breakdown: where a request's wall time went
            "mean_queued_s": float(np.mean(queued)) if queued else 0.0,
            "mean_prefill_s": float(np.mean(prefills)) if prefills else 0.0,
            "mean_decode_s": float(np.mean(decodes)) if decodes else 0.0,
            "admit_rejects": self._n_admit_rejects,
            # policy evictions executed (DESIGN.md §Scheduling); FCFS
            # never preempts, so this is 0 under the default policy
            "n_preempts": self._n_preempts,
            "policy": getattr(self.policy, "name", "?"),
            "mean_occupancy": (
                self._occupancy_sum / self._steps if self._steps else 0.0
            ),
            "max_active": self._max_active,
            "dispatch_depth": self.queue.depth,
            "mesh_devices": (
                int(np.prod(list(dict(self.mesh.shape).values())))
                if self.mesh is not None else 1
            ),
            "kv_shard": self.kv_shard,
        }
        out.update(self.arena.stats())
        return out
