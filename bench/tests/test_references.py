"""Each configuration runs the plain reference it names, a module found
by its name under `bench/references/`: the dense model's arithmetic is
what it was before it moved there, and a model of another block joins
the benchmark as new files alone."""
import argparse
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

import run as bench_run  # bench/run.py
from harness import check, deploy, reference, spec, traffic

import tiny

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _inputs(vocab, seed, served_per_request):
    """Prompts of the tiny mix's first requests, drawn from `seed`, and
    as many tokens of each drawn as if served."""
    clients = traffic.closed_loop(tiny.TRAFFIC, seed, vocab)
    rng = np.random.default_rng([seed, 9])
    prompts, served = {}, {}
    for r, c in enumerate(clients):
        s = c[0]
        prompts[r] = s.prompt
        n = min(s.max_new, served_per_request)
        served[r] = (rng.integers(0, vocab, size=n).astype(np.int32), [],
                     True)
    return prompts, served


def test_dense_arithmetic_unchanged():
    """The tiny dense cell's gaps and control gaps at a fixed seed, and
    the weights it hands the program, equal, with tolerance 0, those the
    harness gave before the dense model moved into
    `references/dense_swiglu.py` (`data/dense_swiglu_gaps.json`)."""
    with open(os.path.join(DATA, "dense_swiglu_gaps.json")) as f:
        want = json.load(f)
    ref = tiny.REFERENCE
    dims = ref.Dims.from_config(tiny.CONFIG["model"])
    key = jax.random.PRNGKey(tiny.CONFIG["weights_seed"])
    prompts, served = _inputs(dims.vocab, want["seed"],
                              want["served_per_request"])
    length = reference.padded_length(traffic.max_context(tiny.TRAFFIC))
    assert length == want["length"]
    for name, bits in (("served", None), ("control_w4a4", 4)):
        g = check.gaps(ref, key, dims, prompts, served, want["ids"], length,
                       bits=bits)
        assert g.dtype == np.float32
        assert np.array_equal(g, np.asarray(want[name], np.float32)), name
    p = jax.jit(ref.program_params, static_argnums=(1, 2))(key, dims, 256)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(p):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == want["params_sha256"]


NEW = "tiny_relu2"
NEW_CELL = NEW + ".tiny_closed"
NEW_SIZES = {"n_layers": 1, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 32, "d_ff": 256, "vocab": 256}
# read on the CPU at this size over 6 seeds: the program's mean gap
# 0.0052-0.0081, the four-bit control's 0.103-0.125
NEW_LIMITS = {"mean_gap": 0.03}


def _bench_with_a_new_reference(root):
    """A copy of bench/ and BENCHMARK.json under `root` to which a
    configuration of Nemotron-4's block (LayerNorm with bias, ungated
    squared-ReLU MLP), its reference, a mix and a check are added as
    files; no file of the copy is edited but BENCHMARK.json, by
    appending."""
    bench = root / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    shutil.copy(os.path.join(DATA, "relu2_layernorm.py"),
                bench / "references" / "relu2_layernorm.py")
    config = {
        "name": NEW, "reference": "relu2_layernorm",
        "model": dict(NEW_SIZES, rope_theta=10000.0, norm_eps=1e-06),
        "program": {"arch": "nemotron_4_340b", "overrides": NEW_SIZES},
        "weights_seed": 0, "engine": dict(tiny.CONFIG["engine"]),
    }
    (bench / "configs" / f"{NEW}.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny_closed.json").write_text(
        json.dumps(tiny.TRAFFIC))
    (bench / "checks" / f"{NEW_CELL}.json").write_text(json.dumps(
        {"sample_requests": 8, "limits": NEW_LIMITS}))
    bm = spec.benchmark()
    bm["configs"].append({"name": NEW, "source": "arXiv:2402.16819",
                          "file": f"bench/configs/{NEW}.json",
                          "reduced": [], "why": "a test"})
    bm["workloads"].append({"name": NEW_CELL, "config": NEW,
                            "traffic": "tiny_closed", "chips": 1,
                            "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


@pytest.mark.parametrize("seed", [31, 2 ** 31 + 9])
def test_a_reference_joins_as_files(seed, tmp_path, monkeypatch, capsys):
    """A cell whose model is not a dense SwiGLU decoder runs through
    `bench/run.py`'s `run` on the CPU and reads correct, and its
    four-bit control reads incorrect.  Before each configuration named
    its reference, `deploy.arch_config` refused this cell ("the
    reference models a dense SwiGLU decoder")."""
    root = tmp_path / "root"
    _bench_with_a_new_reference(root)
    monkeypatch.setattr(deploy, "STATE", tmp_path / "state")
    cell = spec.cell(NEW_CELL, root)
    assert cell.reference.__file__ == str(
        root / "bench" / "references" / "relu2_layernorm.py")
    dev = jax.devices()[0]
    rc = bench_run.run(tiny.args(seed=seed), cell, dev, [dev],
                       spec.peaks("TPU v5 lite"))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"setup_s", "itl_p95_s"}

    got = bench_run.serve(argparse.Namespace(seed=seed, seconds=1.0,
                                             trace=0), cell, [dev])
    ids, c = bench_run.gaps(cell, got, seed, bits=4)
    assert ids and not check.judge(check.numbers(c), cell.check["limits"])
