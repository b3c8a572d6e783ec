"""A tiny cell that a CPU test run can hold: the same harness, engine,
reference and check as a real cell, at a few hundred thousand
parameters."""
import argparse
import json

from harness import spec

CONFIG = {
    "name": "tiny_dense",
    "reference": "dense_swiglu",
    "model": {"n_layers": 1, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab": 256,
              "rope_theta": 10000.0, "rope_fraction": 1.0,
              "norm_eps": 1e-06},
    "program": {"arch": "granite_3_2b",
                "overrides": {"n_layers": 1, "d_model": 128, "n_heads": 4,
                              "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                              "vocab": 256}},
    "weights_seed": 0,
    "engine": {"n_slots": 4, "n_pages": 80, "page_size": 16,
               "prefill_chunk": 16, "kv_bits": 8, "max_len": 256},
}
TRAFFIC = {"loop": "closed", "streams": 4, "start": "residual",
           "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
           "output": {"median": 160, "sigma": 0.2, "min": 120, "max": 200}}
# read on the CPU at this size over 13 seeds: the program's mean gap
# 0.009-0.025, the four-bit control's 0.067-0.187
LIMITS = {"mean_gap": 0.04}
REFERENCE = spec.config_reference(CONFIG)  # loaded once: its programs
# compile once per test process


def cell(limits=None):
    bm = spec.benchmark()
    e2e = [m for m in bm["end_to_end"]
           if m["name"] in ("setup_s", "itl_p95_s", "out_tok_s")]
    return spec.Cell(
        name="tiny_dense.decode", chips=1, config=json.loads(
            json.dumps(CONFIG)), reference=REFERENCE, traffic=dict(TRAFFIC),
        check={"sample_requests": 8, "limits": dict(limits or LIMITS)},
        end_to_end=e2e, per_layer=[])


def args(seed=5, seconds=1.0):
    return argparse.Namespace(workload="tiny_dense.decode", seed=seed,
                              seconds=seconds, trace=0)
