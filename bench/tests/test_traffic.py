"""The generator: a seed fixes the work, and seeds differ only in the
order and content of the same work."""
import json
import os

import numpy as np

from harness import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


def mix(name):
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           f"{name}.json")) as f:
        return json.load(f)


def lengths(clients):
    return sorted((len(s.prompt), s.max_new) for c in clients for s in c)


def test_closed_loop_same_seed_same_requests():
    a = traffic.closed_loop(mix("decode_closed"), BIG, 1000)
    b = traffic.closed_loop(mix("decode_closed"), BIG, 1000)
    for ca, cb in zip(a, b):
        for sa, sb in zip(ca, cb):
            assert np.array_equal(sa.prompt, sb.prompt)
            assert sa.max_new == sb.max_new


def test_closed_loop_seeds_share_the_work():
    m = mix("decode_closed")
    a = traffic.closed_loop(m, 1, 1000)
    b = traffic.closed_loop(m, BIG, 1000)
    # the same lengths, client by client, and other tokens
    assert [[(len(s.prompt), s.max_new) for s in c] for c in a] == \
        [[(len(s.prompt), s.max_new) for s in c] for c in b]
    assert not np.array_equal(a[0][0].prompt, b[0][0].prompt)


def test_closed_loop_fits_the_context():
    m = mix("decode_closed")
    for c in traffic.closed_loop(m, 7, 1000):
        for s in c:
            assert s.max_new >= 1
            assert len(s.prompt) + s.max_new <= traffic.max_context(m)
            assert s.prompt.max() < 1000


def test_quantiles_are_clipped_and_centred():
    q = traffic.quantiles({"median": 100, "sigma": 1.0, "min": 10,
                           "max": 400}, 101)
    assert q.min() >= 10 and q.max() <= 400
    assert q[50] == 100


def test_open_loop_arrivals():
    m = {"loop": "open", "rate": 2.0, "ramp_s": 5,
         "prompt": {"median": 64, "sigma": 0.5, "min": 8, "max": 256},
         "output": {"median": 16, "sigma": 0.5, "min": 4, "max": 64}}
    a = traffic.open_loop(m, BIG, 500, 10.0)
    b = traffic.open_loop(m, 3, 500, 10.0)
    assert len(a) == len(b) == 50
    arr = np.array([s.arrival for s in a])
    assert np.all(np.diff(arr) > 0) and arr[0] > -5.0
    assert sorted(len(s.prompt) for s in a) == sorted(
        len(s.prompt) for s in b)


def test_closed_loop_spreads_the_caught_requests():
    """The first requests are caught at evenly spread shares of their
    output, so some complete soon and others late."""
    m = mix("decode_closed")
    first = [c[0] for c in traffic.closed_loop(m, 5, 1000)]
    left = sorted(s.max_new for s in first)
    assert left[0] < m["output"]["min"] // 8
    assert left[-1] > m["output"]["min"]
