"""The sparse-expert, sliding-window configuration through the harness
on the CPU, at a tiny shape of its block, and the dense cell at four
layers now that the residual Add rounds."""
import argparse
import json

import jax
import pytest

import run as bench_run  # bench/run.py
from harness import check, deploy, spec

import tiny

ARCH = "mellum2_tiny"
MODEL = {"n_layers": 4, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 32, "d_expert": 64, "n_experts": 8, "top_k": 2,
         "vocab": 256, "window": 16,
         "pattern": ["sliding", "sliding", "sliding", "full"],
         "rope_theta": 500000.0, "yarn_factor": 16.0,
         "yarn_original_max_position": 8192, "yarn_beta_fast": 32.0,
         "yarn_beta_slow": 1.0,
         "yarn_attention_factor": 1.2772588722239782, "norm_eps": 1e-06}
CONFIG = {
    "name": "tiny_mellum", "reference": "moe_swa", "model": MODEL,
    "program": {"arch": ARCH}, "weights_seed": 0,
    "engine": dict(tiny.CONFIG["engine"]),
}
# read on the CPU at this size over six seeds (5, 6, 31, 1000003,
# 2**31 - 1, 2**31 + 9): the program's mean gap 0.0070-0.0231, the
# four-bit control's 0.069-0.179; the tiny dense cell's limit lies
# between them
LIMITS = {"mean_gap": 0.04}


def _register():
    """The program's config of the tiny shape: mellum2_12b with its
    widths, experts and window cut (a JSON override cannot name an
    attention kind)."""
    import dataclasses

    from repro.configs.base import AttnKind, get_config, register

    base = get_config("mellum2_12b")
    sliding = dataclasses.replace(base.attn_pattern[0], window=16)
    full = base.attn_pattern[3]
    assert isinstance(full, AttnKind) and not full.window
    register(dataclasses.replace(
        base, name=ARCH, n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=64, n_experts=8, top_k=2, vocab=256,
        attn_pattern=(sliding, sliding, sliding, full)))


def _cell(config, limits):
    bm = spec.benchmark()
    e2e = [m for m in bm["end_to_end"]
           if m["name"] in ("setup_s", "itl_p95_s", "out_tok_s")]
    return spec.Cell(
        name=config["name"] + ".decode", chips=1,
        config=json.loads(json.dumps(config)),
        reference=spec.config_reference(config),
        traffic=dict(tiny.TRAFFIC),
        check={"sample_requests": 8, "limits": dict(limits)},
        end_to_end=e2e, per_layer=[])


def _read(cell, seed, tmp_path, monkeypatch, capsys):
    """The run's result line, and the four-bit control's numbers."""
    monkeypatch.setattr(deploy, "STATE", tmp_path / "state")
    dev = jax.devices()[0]
    rc = bench_run.run(tiny.args(seed=seed), cell, dev, [dev],
                       spec.peaks("TPU v5 lite"))
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = bench_run.serve(argparse.Namespace(seed=seed, seconds=1.0,
                                             trace=0), cell, [dev])
    _, c = bench_run.gaps(cell, got, seed, bits=4)
    return out, check.numbers(c)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_tiny_mellum_reads_correct(seed, tmp_path, monkeypatch, capsys):
    """Served through the chunked paged engine, the tiny cell's tokens
    sit within the limit of the reference's best, and the four-bit
    control's do not."""
    _register()
    out, control = _read(_cell(CONFIG, LIMITS), seed, tmp_path,
                         monkeypatch, capsys)
    print("program", out["check"], "control", control)
    assert out["correct"] is True, out["check"]
    assert not check.judge(control, LIMITS), control


@pytest.mark.parametrize("seed", [5, 6])
def test_tiny_dense_at_four_layers_reads_correct(seed, tmp_path,
                                                 monkeypatch, capsys):
    """With the residual Add rounding to nearest, four dense layers
    read within the tiny cell's limit (0.04); with the floor they read
    0.159 and 0.114 at these seeds."""
    config = json.loads(json.dumps(tiny.CONFIG))
    config["name"] = "tiny_dense_l4"
    config["model"]["n_layers"] = 4
    config["program"]["overrides"]["n_layers"] = 4
    out, control = _read(_cell(config, tiny.LIMITS), seed, tmp_path,
                         monkeypatch, capsys)
    print("program", out["check"], "control", control)
    assert out["correct"] is True, out["check"]
    assert not check.judge(control, tiny.LIMITS), control
