"""BENCHMARK.json against the files it names and the contract's
limits on names, units and references."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load("BENCHMARK.json")


def check_manifest(m, root=ROOT):
    """Everything the harness relies on; raises AssertionError."""
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    under = lambda p: any(  # noqa: E731
        p == d or p.startswith(d + "/") for d in m["paths"])
    assert under(m["command"][1])
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert len(configs) == len(m["configs"])
    assert len(cells) == len(m["workloads"])
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and under(c["file"])
        with open(os.path.join(root, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        importlib.import_module(
            f"chipbench.families.{body['family']}.reference")
        importlib.import_module(
            f"chipbench.families.{body['family']}.arith")
        assert any(w["config"] == c["name"] for w in m["workloads"])
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        for sub in ("traffic", "workloads", "limits"):
            key = w["traffic"] if sub == "traffic" else w["name"]
            assert os.path.exists(os.path.join(
                root, "chipbench", sub, f"{key}.json")), (sub, key)
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    layers = {}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert p["name"] not in e2e
        assert p["better"] in ("lower", "higher")
        assert p["source"] in SOURCES
        assert p["moves"] in e2e
        assert 1 <= len(p["layer"]) <= 200
        layers.setdefault(p["layer"], []).append(p["name"])
        moved = e2e[p["moves"]]
        for w in p.get("workloads", list(cells)):
            assert w in cells, (p["name"], w)
            assert w in moved.get("workloads", list(cells))
        with open(os.path.join(root, "chipbench", "metrics",
                               f"{p['name']}.json")) as f:
            spec = json.load(f)
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == p[k], (p["name"], k)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        assert callable(reader.read)
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    for w in cells:  # every cell reports at least one per-layer metric
        assert any(w in p.get("workloads", list(cells))
                   for p in m["per_layer"])
    assert 1 <= m["run_seconds"] <= 51 and isinstance(
        m["run_seconds"], int)
    n_runs = 2 + 14 * 24
    assert (n_runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_manifest_holds(manifest):
    check_manifest(manifest)
    raw = open(os.path.join(ROOT, "BENCHMARK.json")).read()
    assert len(raw.encode()) <= 64 * 1024


def test_every_file_under_paths_is_named_from_allowed_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, d)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_limits_cover_every_cell(manifest):
    from chipbench import checks
    for w in manifest["workloads"]:
        lim = checks.limits_for(w["name"])
        assert set(lim) <= set(checks.NAMES)
        assert {"grad1_median_gap", "dparam_median_gap"} <= set(lim)
        assert all(0 < v < 0.2 for v in lim.values()), (w["name"], lim)


def test_an_addition_is_files_and_entries_only(manifest, tmp_path):
    """A later PR adds a configuration, a cell and a per-layer metric
    by adding files and manifest entries: the check passes on such an
    addition without touching a file that is there."""
    import shutil
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = load("chipbench", "configs", "resnet50.json")
    cfg.update(name="resnet50_remat")
    (root / "chipbench/configs/resnet50_remat.json").write_text(
        json.dumps(cfg))
    cell = load("chipbench", "workloads", "r50_b256_synth.json")
    cell.update(name="r50_remat_b256_synth", config="resnet50_remat",
                flags=["--remat"])
    (root / "chipbench/workloads/r50_remat_b256_synth.json").write_text(
        json.dumps(cell))
    shutil.copy(root / "chipbench/limits/r50_b256_synth.json",
                root / "chipbench/limits/r50_remat_b256_synth.json")
    spec = load("chipbench", "metrics", "input.wait_pct.json")
    spec.update(name="input.wait_ms", unit="ms",
                reader="span_ms_per_step")
    (root / "chipbench/metrics/input.wait_ms.json").write_text(
        json.dumps(spec))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "resnet50_remat", "source": "x",
                         "file": "chipbench/configs/resnet50_remat.json",
                         "reduced": [], "why": "y"})
    m["workloads"].append({"name": "r50_remat_b256_synth",
                           "config": "resnet50_remat",
                           "traffic": "synth_u8_b256_w12", "chips": 1,
                           "why": "z"})
    m["per_layer"].append({k: spec[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")}
        | {"workloads": ["r50_remat_b256_synth"]})
    check_manifest(m, root=str(root))
