"""The reduction from a profiler trace to device numbers, on a small
trace recorded on a TPU v5e by `bench/run.py --trace 1` (a two-second
window of granite_3_2b_l1.decode_closed)."""
import os

import pytest

from harness import xtrace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "window.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return xtrace.load(TRACE)


def test_union_merges_overlaps():
    spans = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0),
             ("d", 3.5, 3.6), ("e", 4.0, 4.5)]
    assert xtrace.union(spans) == [(0.0, 2.0), (3.0, 4.5)]


def test_op_names_drop_the_instruction_number():
    assert xtrace.op_name("%paged_attention.11 = s32[32] custom-call(x)") \
        == "paged_attention"
    assert xtrace.op_name("%copy-start.16 = (s32[32]) copy-start(y)") \
        == "copy-start"
    assert xtrace.op_name("%fusion.3.clone.1 = s8[4] fusion(z)") == "fusion"


def test_window_and_busy(tr):
    assert tr is not None and len(tr.ops) == 1
    assert 1.5 < tr.window_s < 3.0
    busy = xtrace.busy_s(tr)
    assert 0 < busy <= tr.window_s
    # busy is the union: no more than the summed durations, and every
    # operation lies inside the window
    assert busy <= sum(b - a for _, a, b in tr.ops[0]) + 1e-12
    assert all(tr.t0 <= a <= b <= tr.t1 for _, a, b in tr.ops[0])


def test_kernel_time_is_its_own_operations(tr):
    """The kernel's device time, read again from the raw trace: only the
    custom calls named for it, not the fusions that consume its output
    (whose HLO text names it too)."""
    from jax.profiler import ProfileData

    raw = 0.0
    for plane in ProfileData.from_file(TRACE).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == xtrace.OPS_LINE:
                    for e in line.events:
                        a = e.start_ns * 1e-9
                        b = a + e.duration_ns * 1e-9
                        if (e.name.startswith("%paged_attention")
                                and b > tr.t0 and a < tr.t1):
                            raw += min(b, tr.t1) - max(a, tr.t0)
    pa = xtrace.kernel_s(tr, "paged_attention")
    assert pa > 0 and pa == pytest.approx(raw)
    assert pa < xtrace.busy_s(tr)


def test_breakdown(tr):
    top = xtrace.top_ops(tr)
    assert 0 < len(top) <= 10
    assert "while" not in [n for n, _ in top]
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = xtrace.idle_gaps(tr)
    idle = tr.window_s - xtrace.busy_s(tr)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    assert all(n.startswith(xtrace.LABELS) or n == "host" for n, _ in gaps)


def _idle_gaps_by_scan(tr):
    """`xtrace.idle_gaps` as a plain scan of every host span per gap."""
    gaps, t = [], tr.t0
    for a, b in xtrace.union(tr.ops[0]):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if tr.t1 > t:
        gaps.append((t, tr.t1))
    tot = {}
    for a, b in gaps:
        mid = (a + b) / 2
        over = [s for s in tr.host if s[1] <= mid <= s[2]]
        label = min(over, key=lambda s: s[2] - s[1])[0] if over else "host"
        tot[label] = tot.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]


@pytest.mark.parametrize("name", ["window.xplane.pb",
                                  "window_spans.xplane.pb"])
def test_idle_gaps_sweep_equals_the_scan(name):
    """The one sweep labels every gap as the scan of all host spans
    does, to the last digit, on both recorded traces."""
    tr = xtrace.load(os.path.join(os.path.dirname(TRACE), name))
    assert tr.host
    assert xtrace.idle_gaps(tr, n=100) == _idle_gaps_by_scan(tr)


def test_step_programs(tr):
    steps = xtrace.module_spans(tr, "unified_step")
    assert steps and all(b > a for _, a, b in steps)


def test_decode_readers_on_the_recorded_trace(tr):
    """Every decode metric's reader, on the recorded trace, with one
    dispatch of 32 rows per step program in it, every eighth carrying a
    chunk: each reads a number, and no share passes 100%."""
    import json

    from harness import measure, spec, window

    with open(spec.BENCH / "configs" / "granite_3_2b_l1.json") as f:
        config = json.load(f)
    n = len(xtrace.module_spans(tr, measure.STEP_PROGRAM))
    win = window.Window(t0=tr.t0, t1=tr.t1, sent={}, jits=0, dispatches=[
        window.Dispatch(t=0.0, width=128, decode=[1000] * 31,
                        chunks=[(0, 128)]) if i % 8 == 0 else
        window.Dispatch(t=0.0, width=1, decode=[1000] * 32, chunks=[])
        for i in range(n)])
    ref = spec.config_reference(config)
    run = measure.Run(win, {}, tr.window_s, tr,
                      ref.Dims.from_config(config["model"]), config["engine"],
                      spec.peaks("TPU v5 lite"), spec.counts, ref)
    cell = spec.cell("granite_3_2b_l1.decode_closed")
    got = {m["name"]: spec.metric_reader(m["name"])(run)
           for m in cell.per_layer}
    assert all(v is not None for v in got.values()), got
    assert got["rows_per_step.decode"] == 32
    for name in ("paged_attention_roofline.decode", "mfu.decode",
                 "idle_share.decode", "chunk_share.decode"):
        assert 0 < got[name] < 100, (name, got[name])
    assert 1 < got["decode_step_ms"] < 100
