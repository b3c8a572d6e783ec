"""The one traffic generator: every mix is a data file it reads.

A mix (`bench/traffic/<name>.json`) gives the loop and its lengths:

  {"loop": "closed", "streams": 32, "start": "residual",
   "prompt": {"median": 128, "sigma": 0.8, "min": 32, "max": 512},
   "output": {"median": 1536, "sigma": 0.6, "min": 512, "max": 3584}}

  {"loop": "open", "rate": 0.3, "ramp_s": 20, "prompt": {...},
   "output": {...}}

Lengths are lognormal (median, sigma of the log), clipped to [min,
max].  Every seed gets the same set of lengths and gaps, taken at
evenly spaced quantiles, in an order and with prompt tokens that the
seed draws: so the amount of work does not change with the seed, only
its order and content.

Closed loop: `streams` clients each send their next request the moment
the last one completes.  Each client's chain of request lengths is the
same for every seed, which draws only the tokens: the engine admits a
few requests per step in the order they come, so a seed that dealt the
chains to the clients in another order would change the work.  With
`start: residual` each client's first
request is caught in flight, a share of its output already spoken:
those tokens are appended to its prompt (filled in set-up) and its
remaining budget is what is left, so the window opens on the context
lengths of a steady state.  The shares are evenly spaced and paired
with the lengths in a fixed order.

Open loop: arrivals at `rate` per second with exponential gaps, from
`ramp_s` seconds before the window opens; each request is timed from
its scheduled arrival.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

POOL = 8  # requests drawn per closed-loop client


@dataclasses.dataclass
class Spec:
    prompt: np.ndarray  # int32 tokens, filler of a caught request included
    max_new: int
    arrival: float = 0.0  # open loop: scheduled, seconds from window start
    stream: int = -1  # closed loop: the client


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n lognormal lengths at the quantiles (i + 0.5) / n, clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *salt])


def max_context(mix: dict) -> int:
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def closed_loop(mix: dict, seed: int, vocab: int) -> List[List[Spec]]:
    """Per client, the requests it will send, in order."""
    streams = int(mix["streams"])
    rng = _rng(seed, 1)
    out: List[List[Spec]] = [[] for _ in range(streams)]
    for j in range(POOL):
        fixed = np.random.default_rng(j)  # the pairing, not the seed's
        prompts = quantiles(mix["prompt"], streams)
        outputs = fixed.permutation(quantiles(mix["output"], streams))
        spoken = fixed.permutation((np.arange(streams) + 0.5) / streams)
        for s in range(streams):
            p, o = int(prompts[s]), int(outputs[s])
            done = 0
            if j == 0 and mix.get("start") == "residual":
                done = int(spoken[s] * o)
            toks = rng.integers(0, vocab, size=p + done).astype(np.int32)
            out[s].append(Spec(toks, o - done, stream=s))
    return out


def open_loop(mix: dict, seed: int, vocab: int, seconds: float) -> List[Spec]:
    """Requests in arrival order, arrivals from -ramp_s to twice the
    window (the tail keeps the load on while late requests finish)."""
    rate, ramp = float(mix["rate"]), float(mix["ramp_s"])
    n = int(math.ceil(rate * (ramp + 2 * seconds)))
    rng = _rng(seed, 2)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    arrivals = np.cumsum(gaps) - ramp
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outputs = rng.permutation(quantiles(mix["output"], n))
    return [
        Spec(rng.integers(0, vocab, size=int(p)).astype(np.int32), int(o),
             arrival=float(a))
        for a, p, o in zip(arrivals, prompts, outputs)
    ]
