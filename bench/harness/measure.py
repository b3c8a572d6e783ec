"""End-to-end numbers of a window, and what per-layer readers receive.

Every timing is the host clock (`time.perf_counter`).  A token counts in
the window when it became host-visible after the window opened and no
later than it closed.  An inter-token gap is the time from one token of
a request to its next; it counts in the window when the later token
does.  A request's first token ends no gap.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

STEP_PROGRAM = "unified_step"  # the engine's jitted unified dispatch


def p(values, q) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def in_window(t, win) -> bool:
    return win.t0 < t <= win.t1


def tokens_and_gaps(win, served) -> tuple:
    """Tokens emitted in the window, and every inter-token gap that
    ended in it."""
    n, gaps = 0, []
    for toks, stamps, _ in served.values():
        n += sum(1 for t in stamps if in_window(t, win))
        gaps += [b - a for a, b in zip(stamps, stamps[1:])
                 if in_window(b, win)]
    return n, gaps


def longest_gap(win, served) -> tuple:
    """(seconds, seconds into the window at which it ended) of the
    longest inter-token gap that ended in the window; a stall of the
    whole engine shows here."""
    best = (0.0, 0.0)
    for toks, stamps, _ in served.values():
        for a, b in zip(stamps, stamps[1:]):
            if in_window(b, win) and b - a > best[0]:
                best = (b - a, b - win.t0)
    return best


def due(win, seconds) -> List[int]:
    """Open loop: requests scheduled to arrive inside the window."""
    return [r for r, s in win.sent.items()
            if win.t0 <= s.scheduled < win.t0 + seconds]


def end_to_end(win, served, seconds, open_loop: bool) -> dict:
    n, gaps = tokens_and_gaps(win, served)
    out = {
        "out_tok_s": n / (win.t1 - win.t0),
        "itl_p95_s": p(gaps, 95),
    }
    if open_loop:
        ttft = [served[r][1][0] - win.sent[r].scheduled
                for r in due(win, seconds) if r in served]
        out["ttft_p50_s"] = p(ttft, 50)
        out["ttft_p95_s"] = p(ttft, 95)
    return out


def attempted_failed(win, served, seconds, open_loop: bool) -> tuple:
    if open_loop:
        d = due(win, seconds)
        return len(d), sum(1 for r in d if r not in served)
    active = [r for r, (_, stamps, _) in served.items()
              if r in win.sent and any(in_window(t, win) for t in stamps)]
    return len(active), 0


@dataclasses.dataclass
class Run:
    """What a per-layer reader gets: the window, what was served, the
    reduced device trace (None without --trace 1), the model's sizes,
    the engine geometry, the device's peaks, and the configuration's
    reference module."""

    window: object
    served: Dict[int, tuple]
    seconds: float
    trace: object
    dims: object
    engine: dict
    peaks: dict
    counts: object  # harness.spec.counts
    reference: object  # bench/references/<name>.py

    def rows(self, d) -> list:
        """(start, n) of each real row of dispatch `d`."""
        return [(pos, 1) for pos in d.decode] + list(d.chunks)

    def step_device_s(self):
        """(width, device seconds) of each step of the window, pairing
        the trace's step programs with the harness's dispatches in
        order; None when their numbers differ."""
        from harness import xtrace

        mods = xtrace.module_spans(self.trace, STEP_PROGRAM)
        if not mods or len(mods) != len(self.window.dispatches):
            return None
        return [(d.width, b - a)
                for d, (_, a, b) in zip(self.window.dispatches, mods)]


def idle_share(run) -> Optional[float]:
    """% of the traced window in which no operation ran on the device:
    1 - (union of the operations' intervals) / window."""
    from harness import xtrace

    w = run.trace.window_s
    return 100.0 * (1.0 - xtrace.busy_s(run.trace) / w) if w > 0 else None


def step_mfu(run) -> Optional[float]:
    """% of the chip's int8 peak: the integer operations the window's
    steps needed (the reference's `step_ops`) over traced window x
    peak."""
    ops = sum(run.reference.step_ops(run.rows(d), run.dims)
              for d in run.window.dispatches)
    w = run.trace.window_s
    return 100.0 * ops / (w * run.peaks["int8_ops_s"]) if w > 0 else None


def kernel_roofline(run, kernel: str) -> Optional[float]:
    """% of its roofline a kernel reaches: the least time the chip could
    take for the work the window's steps needed of it (counts/<kernel>
    .py, every layer) over the device time of its operations."""
    from harness import xtrace

    k = run.counts(kernel)
    d, eng = run.dims, run.engine
    pps = -(-eng["max_len"] // eng["page_size"])
    need = 0.0
    for step in run.window.dispatches:
        ops, nbytes = k.call(
            run.rows(step), n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
            head_dim=d.head_dim, page_size=eng["page_size"],
            pages_per_slot=pps, kv_bits=eng["kv_bits"])
        need += d.n_layers * k.roofline_s(ops, nbytes, run.peaks)
    spent = xtrace.kernel_s(run.trace, k.KERNEL)
    return 100.0 * need / spent if spent > 0 and need > 0 else None


def mean_step_ms(run, wide: bool) -> Optional[float]:
    """Mean device time of the window's width-1 steps (`wide` False) or
    of its steps that carry a prefill chunk (`wide` True), from the step
    programs in the trace."""
    steps = run.step_device_s()
    pick = [s for w, s in steps or () if (w > 1) == wide]
    return 1e3 * sum(pick) / len(pick) if pick else None
