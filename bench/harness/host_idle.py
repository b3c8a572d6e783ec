"""Split the chip's idle time between step programs by what the host
was doing, from the program's own spans in the trace.

Between-program idle time is window time on chip 0 in which no
operation and no step program (an event of "XLA Modules", any name)
runs.  Each instant of it goes to the first rule below that matches
among the host spans covering it; a rule's spans are found by name and
merged into the union of their intervals, so a span written twice
counts once:

  enqueue   `repro.serving/unified`: the step's argument transfers,
            any program run to prepare them, the launch
  host      any `phase/*` but `phase/harvest`: `phase/advance` (the
            bookkeeping after the tokens arrive), admission, chunk
            planning, the array building of `phase/unified_dispatch`
  readback  `phase/harvest` outside `phase/advance`: from the step
            program's end until its token vector reaches the host

Idle time inside a step program, between its operations, is not
between programs and is counted apart, as is idle time under no
`repro.serving/step`.  Each per-step number is the time summed over
the window over the number of unified step programs in it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from harness import xtrace
from harness.measure import STEP_PROGRAM

Interval = Tuple[float, float]

STEP = "repro.serving/step"
RULES = (
    ("enqueue", lambda n: n == "repro.serving/unified"),
    ("host", lambda n: n.startswith("phase/") and n != "phase/harvest"),
    ("readback", lambda n: n == "phase/harvest"),
)


def _gaps(busy: List[Interval], t0: float, t1: float) -> List[Interval]:
    """[t0, t1] less the sorted, disjoint intervals of `busy`."""
    out, t = [], t0
    for a, b in busy:
        if a >= t1:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t1 > t:
        out.append((t, t1))
    return out


def _intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    if not xs:
        return []
    return _intersect(xs, _gaps(ys, xs[0][0], xs[-1][1]))


def _length(xs: List[Interval]) -> float:
    return sum(b - a for a, b in xs)


def split(tr) -> Dict[str, Optional[float]]:
    """Seconds of chip 0's idle time in the window: `enqueue`, `host`
    and `readback` of the between-program idle time (None where no span
    of the rule is in the trace), `in_program` idle time, between-
    program idle time under no `repro.serving/step` (`outside_step`),
    and all idle time (`idle`); `steps` counts the unified step
    programs."""
    ops, mods = tr.ops[0], tr.modules[0]
    idle = _gaps(xtrace.union(ops), tr.t0, tr.t1)
    left = _gaps(xtrace.union(ops + mods), tr.t0, tr.t1)
    out: Dict[str, Optional[float]] = {
        "idle": _length(idle),
        "in_program": _length(idle) - _length(left),
        "outside_step": _length(_subtract(
            left, xtrace.union([s for s in tr.host if s[0] == STEP]))),
        "steps": len(xtrace.module_spans(tr, STEP_PROGRAM)),
    }
    for name, match in RULES:
        spans = [s for s in tr.host if match(s[0])]
        if not spans:
            out[name] = None
            continue
        cover = xtrace.union(spans)
        out[name] = _length(_intersect(left, cover))
        left = _subtract(left, cover)
    return out


def per_step_ms(run, rule: str) -> Optional[float]:
    """Milliseconds per unified step of the idle time under `rule`."""
    got = split(run.trace)
    if got[rule] is None or not got["steps"]:
        return None
    return 1e3 * got[rule] / got["steps"]
