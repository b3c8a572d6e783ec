"""Find a cell's pieces by name.

`BENCHMARK.json` names each cell's configuration and traffic mix; each
of those, each per-layer metric's reader and each kernel's counts is a
file of its own under `bench/`, found here by its name alone, so that a
cell, a mix or a metric is added by adding files:

  bench/configs/<config>.json    model sizes, source, cuts, engine,
                                 and the name of its reference
  bench/references/<name>.py     the model's plain reference: weights,
                                 float32 forward pass, step operations
                                 (the contract: harness/reference.py)
  bench/traffic/<traffic>.json   arrival process and length mixes
  bench/checks/<workload>.json   the limits of the correctness check
  bench/metrics/<metric>.py      read(run) -> number or None
  bench/counts/<kernel>.py       operations and bytes of one call
  bench/peaks.json               device peaks keyed by device_kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(Exception):
    """A name in BENCHMARK.json that no file under bench/ answers."""


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    reference: object  # the module bench/references/<name>.py
    traffic: dict
    check: dict
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list


def load(kind: str, name: str, root: Path = ROOT) -> dict:
    """bench/<kind>/<name>.json"""
    return _json(root / "bench" / kind / f"{name}.json")


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve workload `name` to its files; raises SpecError."""
    bm = benchmark(root)
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    e2e = [m for m in bm["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _reports(m, name) and m["moves"] in e2e_names]
    config = load("configs", w["config"], root)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        reference=config_reference(config, root),
        traffic=load("traffic", w["traffic"], root),
        check=load("checks", name, root),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reference(name: str, root: Path = ROOT):
    """The plain-reference module `name`."""
    return _module(root / "bench" / "references" / f"{name}.py",
                   "reference_" + name)


def config_reference(config: dict, root: Path = ROOT):
    """The reference module that configuration `config` names."""
    if "reference" not in config:
        raise SpecError(f"configuration {config['name']!r} names no "
                        "reference")
    return reference(config["reference"], root)


def metric_reader(name: str, root: Path = ROOT):
    """`read(run)` of per-layer metric `name`."""
    path = root / "bench" / "metrics" / f"{name}.py"
    return _module(path, "metric_" + name.replace(".", "_")).read


def counts(kernel: str, root: Path = ROOT):
    """The counting module of `kernel` (ops and bytes per call)."""
    return _module(root / "bench" / "counts" / f"{kernel}.py",
                   "counts_" + kernel)


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = _json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(
            f"device_kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]
