"""The harness finds a cell's pieces by name alone: a cell, a mix or a
metric is added by adding files."""
import json
import shutil

import pytest

from harness import spec


CONTRACT = ("Dims", "program_params", "check_program", "embed", "layer",
            "head_rows", "step_ops")


def test_every_cell_resolves():
    bm = spec.benchmark()
    for c in bm["configs"]:
        with open(spec.ROOT / c["file"]) as f:
            ref = spec.config_reference(json.load(f))
        assert all(hasattr(ref, a) for a in CONTRACT), c["name"]
    for w in bm["workloads"]:
        cell = spec.cell(w["name"])
        assert all(hasattr(cell.reference, a) for a in CONTRACT)
        assert cell.end_to_end and cell.per_layer
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(spec.metric_reader(m["name"]))


def test_configs_match_their_source():
    """Each size the cell runs is the published one, or is listed in
    `reduced` with the published and the cut value; BENCHMARK.json's
    `reduced` names every changed key, cuts and departures alike."""
    bm = spec.benchmark()
    for c in bm["configs"]:
        with open(spec.ROOT / c["file"]) as f:
            cfg = json.load(f)
        changed = list(cfg["reduced"]) + list(cfg["departures"])
        assert sorted(changed) == sorted(c["reduced"])
        for key, cut in {**cfg["reduced"], **cfg["departures"]}.items():
            assert cfg["published"][key] == cut["from"], key
        for dim, key in cfg["source_keys"].items():
            if key in cfg["reduced"]:
                cut = cfg["reduced"][key]
                assert cfg["published"][key] == cut["from"]
                assert cfg["model"][dim] == cut["to"]
            else:
                assert cfg["model"][dim] == cfg["published"][key], key


def test_a_cell_added_as_data_resolves(tmp_path):
    """A later change adds a configuration with its reference, a mix, a
    check and a metric as files, edits none, and the harness runs the
    new cell."""
    root = tmp_path
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    bm = spec.benchmark()
    cfg0 = bm["configs"][0]
    bm["configs"].append({**cfg0, "name": "newcfg",
                          "file": "bench/configs/newcfg.json"})
    bm["workloads"].append({"name": "newcfg.burst", "config": "newcfg",
                            "traffic": "burst", "chips": 1, "why": "x"})
    bm["per_layer"].append({
        "name": "new_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "itl_p95_s", "workloads": ["newcfg.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    with open(spec.ROOT / cfg0["file"]) as f:
        newcfg = dict(json.load(f), name="newcfg", reference="newref")
    (root / "bench/configs/newcfg.json").write_text(json.dumps(newcfg))
    (root / "bench/references/newref.py").write_text(
        "from harness.reference import BLOCK\n\n\n"
        "def step_ops(rows, dims):\n    return BLOCK\n")
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"loop": "open", "rate": 1.0, "ramp_s": 1,
         "prompt": {"median": 8, "sigma": 0.1, "min": 4, "max": 16},
         "output": {"median": 8, "sigma": 0.1, "min": 4, "max": 16}}))
    (root / "bench/checks/newcfg.burst.json").write_text(json.dumps(
        {"sample_requests": 4, "limits": {"mean_gap": 1.0}}))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    cell = spec.cell("newcfg.burst", root)
    assert cell.traffic["loop"] == "open"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert "out_tok_s" not in [m["name"] for m in cell.end_to_end] or any(
        "newcfg.burst" in m.get("workloads", []) for m in cell.end_to_end)
    assert spec.metric_reader("new_metric", root)(None) == 42.0
    assert cell.reference.step_ops([], None) == 512


@pytest.mark.parametrize("named", [None, "no_such_reference"])
def test_a_config_without_its_reference_is_an_error(named, tmp_path):
    """A configuration that names no reference, or one with no file
    behind it, fails as the cell is resolved, before any deploy."""
    root = tmp_path
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    w = spec.benchmark()["workloads"][0]
    path = root / "bench" / "configs" / f"{w['config']}.json"
    cfg = json.loads(path.read_text())
    del cfg["reference"]
    if named:
        cfg["reference"] = named
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match=named or "names no reference"):
        spec.cell(w["name"], root)


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.cell("no_such.cell")
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")
    assert spec.peaks("TPU v5 lite")["int8_ops_s"] == 393e12
