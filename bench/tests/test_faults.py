"""A run with the timed path broken underneath reads `correct: false`,
and so does the control put in the program's place.

The run skips the look for a chip and drives the rest of bench/run.py
on the CPU at a tiny size; the faults are planted where the step
produces its tokens and its state (the unified dispatch the harness
records)."""
import argparse
import json

import jax
import numpy as np
import pytest

import run as bench_run  # bench/run.py
from harness import check, deploy, spec, window

import tiny


def _alter_every_token(out, args, vocab):
    toks, caches = out
    return (toks + 1) % vocab, caches


def _drop_half_the_rows(out, args, vocab):
    toks, caches = out
    half = toks.shape[0] // 2
    return toks.at[:half].set(0), caches


def _state_unchanged(out, args, vocab):
    """The step's KV writes are lost: it hands back the cache it got."""
    toks, _ = out
    return toks, args[2]


@pytest.mark.parametrize("fault", [None, _alter_every_token,
                                   _drop_half_the_rows, _state_unchanged])
def test_fault_reads_incorrect(fault, monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(deploy, "STATE", tmp_path)
    if fault is not None:
        call = window.Recorder.__call__

        def broken(self, *a):
            return fault(call(self, *a), a, tiny.CONFIG["model"]["vocab"])

        monkeypatch.setattr(window.Recorder, "__call__", broken)
    dev = jax.devices()[0]
    rc = bench_run.run(tiny.args(), tiny.cell(), dev, [dev],
                       spec.peaks("TPU v5 lite"))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "check"
    assert out["correct"] is (fault is None), out["check"]
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"setup_s", "itl_p95_s", "out_tok_s"}


@pytest.mark.parametrize("seed", [21, 22, 2 ** 31 + 5])
def test_control_reads_incorrect(seed, monkeypatch, tmp_path):
    """The reference at four bits, read at the program's own prompts and
    tokens, lies above the limit on every seed."""
    monkeypatch.setattr(deploy, "STATE", tmp_path)
    cell = tiny.cell()
    got = bench_run.serve(
        argparse.Namespace(seed=seed, seconds=1.0, trace=0), cell,
        jax.devices())
    ids, g = bench_run.gaps(cell, got, seed)
    _, c = bench_run.gaps(cell, got, seed, bits=4)
    assert ids and g.size == c.size
    nums, ctrl = check.numbers(g), check.numbers(c)
    assert check.judge(nums, cell.check["limits"]), nums
    assert not check.judge(ctrl, cell.check["limits"]), ctrl
    assert np.all(c >= 0)
