"""Drive the program's `ServingEngine` through set-up and the window.

The engine is the system under test: requests enter through
`ServingEngine.submit`, and every step is `ServingEngine.step`, which
issues one unified dispatch (`DecoderLM.prefill_chunk` under
`ServingEngine._unified`, the Mosaic paged kernel over the
`PagedArena`).  The harness records, around those calls, what it
needs for its metrics: each request's scheduled arrival and submit
time, and for each dispatch its width and the rows it carried, read
from the engine's host state as the dispatch is issued.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax

from harness.traffic import Spec

JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Dispatch:
    t: float  # host clock when issued
    width: int  # 1: decode only; the chunk width: a chunk rides along
    decode: List[int]  # positions of the decode rows
    chunks: List[tuple]  # (offset, n) of the chunk rows


@dataclasses.dataclass
class Sent:
    spec: Spec
    req_id: int
    scheduled: float  # host clock; closed loop: the submit time
    submitted: float


class Recorder:
    """Stands in for `engine._unified` and logs each dispatch."""

    def __init__(self, engine):
        self.engine = engine
        self.fn = engine._unified
        engine._unified = self
        self.log: List[Dispatch] = []
        self.on = False

    def __call__(self, tables, toks, caches, start, last):
        if self.on:
            e, width = self.engine, toks.shape[1]
            decode = [st.pos for st in e.active.values()]
            chunks = []
            if width > 1:  # FCFS packs a chunk of every prefilling slot
                chunks = [(st.offset, min(width, st.source_len - st.offset))
                          for st in e.prefilling.values()]
            self.log.append(Dispatch(time.perf_counter(), width, decode,
                                     chunks))
        return self.fn(tables, toks, caches, start, last)


class JitCounter:
    """Counts traces and compiles JAX reports while `on`."""

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in JIT_EVENTS:
            self.n += 1


class GcTimer:
    """Times every Python garbage collection of the process: a pause of
    the whole process that the device trace shows only as idle time."""

    def __init__(self):
        import gc

        self.log: List[tuple] = []  # (start, seconds, generation)
        self._t = None
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.log.append((self._t, time.perf_counter() - self._t,
                             info["generation"]))

    def within(self, t0: float, t1: float) -> List[tuple]:
        """(seconds, generation) of each collection begun in [t0, t1)."""
        return [(d, g) for t, d, g in self.log if t0 <= t < t1]


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    sent: Dict[int, Sent]  # req_id -> what was sent
    dispatches: List[Dispatch]
    jits: int  # traces and compiles inside the window


def warm(engine, vocab: int):
    """Compile both unified widths, then serve one real two-chunk
    request, so that work done on first use lands in set-up."""
    import numpy as np

    engine.warmup()
    C = engine.sched.cfg.prefill_chunk
    engine.submit(np.arange(C + 1, dtype=np.int32) % vocab,
                  max_new_tokens=3)
    engine.run_until_drained()


def served(engine) -> Dict[int, tuple]:
    """req_id -> (tokens so far, emit stamps, finished) for every
    request the engine has seen that produced a token."""
    out = {c.req_id: (c.tokens, c.emit_times, True)
           for c in engine.completed}
    for st in engine.active.values():
        out[st.request.req_id] = (st.tokens, st.emit_times, False)
    return out


def _nothing():
    pass


def closed(engine, clients: List[List[Spec]], seconds: float,
           rec: Recorder, jits: JitCounter, log, on_open=_nothing,
           on_close=_nothing) -> Window:
    sent: Dict[int, Sent] = {}
    owner: Dict[int, int] = {}
    nxt = [0] * len(clients)

    def send(c: int):
        spec = clients[c][nxt[c] % len(clients[c])]
        nxt[c] += 1
        now = time.perf_counter()
        rid = engine.submit(spec.prompt, max_new_tokens=spec.max_new)
        sent[rid] = Sent(spec, rid, now, now)
        owner[rid] = c

    seen = len(engine.completed)

    def step():
        nonlocal seen
        engine.step()
        for comp in engine.completed[seen:]:
            if comp.req_id in owner:
                send(owner[comp.req_id])
        seen = len(engine.completed)

    for c in range(len(clients)):
        send(c)
    fill = 0
    while engine.sched.n_pending or engine.prefilling:
        step()
        fill += 1
    log(f"fill_steps {fill}")
    on_open()
    rec.on = jits.on = True
    t0 = t = time.perf_counter()
    while t - t0 < seconds:
        step()
        t = time.perf_counter()
    rec.on = jits.on = False
    on_close()
    return Window(t0, t, sent, rec.log, jits.n)


def open_(engine, specs: List[Spec], seconds: float, ramp: float,
          rec: Recorder, jits: JitCounter, log, on_open=_nothing,
          on_close=_nothing) -> Window:
    sent: Dict[int, Sent] = {}
    base = time.perf_counter() + ramp  # the window opens here
    i = 0

    def arrivals(now):
        nonlocal i
        while i < len(specs) and base + specs[i].arrival <= now:
            spec = specs[i]
            rid = engine.submit(spec.prompt, max_new_tokens=spec.max_new)
            sent[rid] = Sent(spec, rid, base + spec.arrival,
                             time.perf_counter())
            i += 1

    def idle():
        return not (engine.sched.n_pending or engine.prefilling
                    or engine.active)

    def step(now):
        arrivals(now)
        if idle():
            wait = base + specs[i].arrival - now if i < len(specs) else 0
            time.sleep(min(max(wait, 0.0), 0.05))
        else:
            engine.step()

    now = time.perf_counter()
    while now < base:
        step(now)
        now = time.perf_counter()
    on_open()
    rec.on = jits.on = True
    while now < base + seconds:
        step(now)
        now = time.perf_counter()
    t1 = now
    rec.on = jits.on = False
    on_close()
    # late first tokens: step on, arrivals continuing, for at most one
    # more window, until every request due in the window has its first
    due = [s.req_id for s in sent.values()
           if base <= s.scheduled < base + seconds]
    deadline = t1 + seconds
    while now < deadline:
        toks = served(engine)
        if all(rid in toks for rid in due):
            break
        step(now)
        now = time.perf_counter()
    return Window(base, t1, sent, rec.log, jits.n)
