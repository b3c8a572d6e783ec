"""Reduce a profiler trace (`.xplane.pb`) to device numbers.

The traced run wraps its window in a host annotation named `WINDOW`;
its bounds on the trace's clock are the traced window.  Device
operations are the events of each TPU plane's "XLA Ops" line, named by
their HLO instruction without its number (`%paged_attention.11 = ...`
is `paged_attention`), and its step programs those of the "XLA Modules"
line.  Busy time is the union of the operations' intervals inside the
window (averaged over the chips); idle share is 1 - busy / window.  An
idle gap is labelled with the innermost host annotation of the engine
(`phase/...`, `repro.serving/...`) or of the harness (`bench/...`) that
covers its middle, and `host` where none does.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench/window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
LABELS = ("phase/", "repro.serving/", "bench/")  # host spans that label
CONTROL_FLOW = ("while", "conditional", "call")  # ops that hold others

Span = Tuple[str, float, float]  # name, start, end in seconds


@dataclasses.dataclass
class Trace:
    t0: float
    t1: float
    ops: List[List[Span]]  # per chip, inside the window
    modules: List[List[Span]]
    host: List[Span]  # host annotations overlapping the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def find(directory: str) -> Optional[str]:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def op_name(hlo: str) -> str:
    """`%fusion.362.clone = s8[...] fusion(...)` -> `fusion`."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.(\d|clone).*$", "", head)


def _events(line, name=lambda n: n) -> List[Span]:
    return [(name(e.name), e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path: str) -> Optional[Trace]:
    """None when the trace holds no window or no device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Span] = []
    ops, mods = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(s for s in _events(line)
                            if s[0].startswith(LABELS))
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                ops.append(_events(lines[OPS_LINE], op_name))
                mods.append(_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])
    win = [s for s in host if s[0] == WINDOW]
    if not win or not ops:
        return None
    _, t0, t1 = win[0]

    def clip(spans):
        return [(n, max(a, t0), min(b, t1)) for n, a, b in spans
                if b > t0 and a < t1]

    return Trace(t0, t1, [clip(o) for o in ops], [clip(m) for m in mods],
                 [s for s in host if s[2] > t0 and s[1] < t1
                  and s[0] != WINDOW])


def union(spans: List[Span]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, a, b in sorted(spans, key=lambda s: s[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    per_chip = [sum(b - a for a, b in union(o)) for o in tr.ops]
    return sum(per_chip) / len(per_chip)


def kernel_s(tr: Trace, name: str) -> float:
    """Device seconds of the operations named `name`, summed per chip
    and averaged over the chips."""
    per_chip = [sum(b - a for n, a, b in o if n == name) for o in tr.ops]
    return sum(per_chip) / len(per_chip)


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """Device seconds by operation name, averaged over the chips, with
    control-flow operations (which hold the others) left out."""
    tot: Dict[str, float] = {}
    for o in tr.ops:
        for name, a, b in o:
            if name not in CONTROL_FLOW:
                tot[name] = tot.get(name, 0.0) + (b - a) / len(tr.ops)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """Idle seconds on chip 0 inside the window, summed by the host
    annotation under each gap's middle."""
    busy = union(tr.ops[0])
    gaps, t = [], tr.t0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if tr.t1 > t:
        gaps.append((t, tr.t1))
    # one sweep over the gaps in time order: `open_` holds, shortest
    # first (the first listed on a tie), the spans begun by the gap's
    # middle; one that ended before it can never cover a later middle
    spans = sorted(range(len(tr.host)), key=lambda i: tr.host[i][1])
    open_: List[Tuple[float, int]] = []
    nxt = 0
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        while nxt < len(spans) and tr.host[spans[nxt]][1] <= mid:
            _, s0, s1 = tr.host[spans[nxt]]
            heapq.heappush(open_, (s1 - s0, spans[nxt]))
            nxt += 1
        while open_ and tr.host[open_[0][1]][2] < mid:
            heapq.heappop(open_)
        label = tr.host[open_[0][1]][0] if open_ else "host"
        tot[label] = tot.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def module_spans(tr: Trace, marker: str) -> List[Span]:
    """Step programs on chip 0 whose name holds `marker`, in order."""
    return sorted((s for s in tr.modules[0] if marker in s[0]),
                  key=lambda s: s[1])
