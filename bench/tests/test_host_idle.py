"""The split of the chip's between-program idle time by the program's
host spans (`harness.host_idle`): exact on a hand-built trace, and a
partition of the idle time on a trace recorded on a TPU v5e by
`bench/run.py --trace 1` (a two-second window of
granite_3_2b_l1.decode_closed) from a program that writes its own
step and phase spans."""
import dataclasses
import os
import types

import pytest

from harness import host_idle, spec, xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEP = "jit__unified_step(1)"


def hand_built(duplicate=False, advance=True, unified=True) -> xtrace.Trace:
    """Two engine steps in a 10 s window, then 2 s under no step.

    step 0 [0, 4]: admission 0-0.2, unified_dispatch 0.2-1 (unified
    0.5-1), harvest 1-3.6 (advance 3.2-3.6); its program 1-3, with
    operations 1-1.5 and 2-3.
    step 1 [4, 8]: admission 4-4.5, unified_dispatch 4.5-5.5 (unified
    5-5.5, during which another program runs 5.2-5.3 with one
    operation 5.2-5.25), harvest 5.5-7.5 (advance 7-7.5); its program
    5.5-6.8, one operation throughout.
    """
    host = [
        ("repro.serving/step", 0.0, 4.0), ("repro.serving/step", 4.0, 8.0),
        ("phase/admission", 0.0, 0.2), ("phase/unified_dispatch", 0.2, 1.0),
        ("phase/harvest", 1.0, 3.6),
        ("phase/admission", 4.0, 4.5), ("phase/unified_dispatch", 4.5, 5.5),
        ("phase/harvest", 5.5, 7.5),
    ]
    if advance:
        host += [("phase/advance", 3.2, 3.6), ("phase/advance", 7.0, 7.5)]
    if duplicate:  # the traced run writes each phase span twice
        host += [s for s in host if s[0].startswith("phase/")]
    if unified:
        host += [("repro.serving/unified", 0.5, 1.0),
                 ("repro.serving/unified", 5.0, 5.5)]
    ops = [("fusion", 1.0, 1.5), ("fusion", 2.0, 3.0),
           ("broadcast", 5.2, 5.25), ("fusion", 5.5, 6.8)]
    mods = [(STEP, 1.0, 3.0), ("jit_broadcast_in_dim(2)", 5.2, 5.3),
            (STEP, 5.5, 6.8)]
    return xtrace.Trace(0.0, 10.0, [ops], [mods], host)


def approx(x):
    return pytest.approx(x, abs=1e-9)


@pytest.mark.parametrize("duplicate", [False, True])
def test_rules_on_a_hand_built_trace(duplicate):
    """unified wins over the dispatch phase around it; advance counts
    as host inside harvest; harvest's own time is readback; idle time
    inside a program (1.5-2, 5.25-5.3) and under a step but no phase
    (3.6-4, 7.5-8) goes to no rule; a span written twice counts once."""
    got = host_idle.split(hand_built(duplicate=duplicate))
    assert got["steps"] == 2
    assert got["enqueue"] == approx(0.5 + 0.4)
    assert got["host"] == approx(0.2 + 0.3 + 0.4 + 0.5 + 0.5 + 0.5)
    assert got["readback"] == approx(0.2 + 0.2)
    assert got["in_program"] == approx(0.5 + 0.05)
    assert got["outside_step"] == approx(2.0)
    assert got["idle"] == approx(10.0 - 2.85)


def test_without_advance_readback_holds_the_bookkeeping():
    """A program that writes no `phase/advance`: harvest's whole idle
    time is readback."""
    got = host_idle.split(hand_built(advance=False))
    assert got["readback"] == approx(0.2 + 0.4 + 0.2 + 0.5)
    assert got["host"] == approx(0.2 + 0.3 + 0.5 + 0.5)
    assert got["enqueue"] == approx(0.9)


def test_per_step_ms_and_missing_spans():
    Run = types.SimpleNamespace(trace=hand_built())
    assert host_idle.per_step_ms(Run, "enqueue") == approx(450.0)
    assert host_idle.per_step_ms(Run, "host") == approx(1200.0)
    assert host_idle.per_step_ms(Run, "readback") == approx(200.0)
    Run.trace = hand_built(unified=False)
    assert host_idle.per_step_ms(Run, "enqueue") is None
    # without the unified span its idle time is the dispatch phase's
    assert host_idle.per_step_ms(Run, "host") == approx(1650.0)
    Run.trace = xtrace.Trace(0.0, 10.0, [[]], [[]], [])
    for rule in ("enqueue", "host", "readback"):
        assert host_idle.per_step_ms(Run, rule) is None


@pytest.fixture(scope="module")
def recorded():
    return xtrace.load(os.path.join(DATA, "window_spans.xplane.pb"))


def test_recorded_trace_partitions_the_idle_time(recorded):
    """On the recorded trace the three metrics, times the steps, plus
    idle time inside programs and under no step, are the idle share's
    idle time within 1%; readback is less than all idle time under
    `phase/harvest`, since `phase/advance` takes its part."""
    names = {s[0] for s in recorded.host}
    assert {"repro.serving/step", "phase/harvest", "phase/advance",
            "repro.serving/unified"} <= names
    run = types.SimpleNamespace(trace=recorded)
    got = {m: spec.metric_reader(m)(run) for m in (
        "enqueue_idle_ms.decode", "host_idle_ms.decode",
        "readback_idle_ms.decode")}
    assert all(v is not None and v > 0 for v in got.values()), got
    split = host_idle.split(recorded)
    assert split["steps"] > 10
    idle = (spec.metric_reader("idle_share.decode")(run) / 100
            * recorded.window_s)
    parts = (sum(got.values()) * split["steps"] / 1e3
             + split["in_program"] + split["outside_step"])
    assert parts == pytest.approx(idle, rel=0.01)
    no_advance = dataclasses.replace(recorded, host=[
        s for s in recorded.host if s[0] != "phase/advance"])
    assert split["readback"] < host_idle.split(no_advance)["readback"]
