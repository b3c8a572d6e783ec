"""The end-to-end numbers of a window, on stamps made up by hand."""
import types

import pytest

from harness import measure


def _window(t0, t1):
    return types.SimpleNamespace(t0=t0, t1=t1, sent={})


def test_gaps_count_where_the_later_token_lands():
    win = _window(10.0, 20.0)
    served = {
        # first token before the window: the gap 9.5 -> 10.5 ends inside
        1: ([0] * 4, [9.5, 10.5, 11.0, 21.0], False),
        # one token only: no gap
        2: ([0], [12.0], False),
    }
    n, gaps = measure.tokens_and_gaps(win, served)
    assert n == 3
    assert sorted(gaps) == pytest.approx([0.5, 1.0])


def test_itl_p95_reads_every_gap():
    win = _window(0.0, 100.0)
    stamps = [0.1 * i for i in range(1, 101)]  # 99 gaps of 0.1 s
    stamps[50:] = [s + 3.0 for s in stamps[50:]]  # one stall of 3.1 s
    out = measure.end_to_end(win, {1: ([0] * 100, stamps, False)}, 100.0,
                             open_loop=False)
    assert out["out_tok_s"] == pytest.approx(1.0)
    assert out["itl_p95_s"] == pytest.approx(0.1)


def test_longest_gap_names_the_stall():
    win = _window(0.0, 10.0)
    served = {1: ([0] * 4, [1.0, 1.1, 3.6, 3.7], False),
              2: ([0] * 2, [-5.0, 0.5], False)}
    gap, at = measure.longest_gap(win, served)
    assert gap == pytest.approx(5.5) and at == pytest.approx(0.5)


def test_gc_timer_keeps_the_collections_of_a_span():
    import gc
    import time

    from harness import window

    t = window.GcTimer()
    gc.collect()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    gc.collect()
    gc.callbacks.remove(t._hear)
    inside = t.within(t0, t1)
    assert len(inside) == 1 and inside[0][1] == 2 and inside[0][0] > 0
