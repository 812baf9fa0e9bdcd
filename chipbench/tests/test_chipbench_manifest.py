"""BENCHMARK.json and the files it names: all present, all well formed,
and a cell, a configuration, a traffic mix or a per-layer metric added as
new files is found with no edit."""

import json
import re

import pytest

from chipbench.tests import tinyroot

REPO = tinyroot.REPO
BENCH = REPO / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level(manifest):
    assert set(manifest) == KEYS
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert manifest["paths"] == ["chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in manifest[group]]
        assert len(group_names) == len(set(group_names))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        config = json.loads((REPO / configs[w["config"]]["file"]).read_text())
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "campaigns" / f"{config['campaign']}.py").is_file()
    for m in manifest["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_configs(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/")
        config = json.loads((REPO / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert set(c["reduced"]) == set(config["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_px")), key
        assert config["depth"] < config["published_depth"]
        assert set(config["limits"]), "every configuration states its limits"


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m["workloads"] for m in manifest["per_layer"])
    # every layer of BENCHMARK.json is in PERF.md's list of layers
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf, layer


def test_added_files_are_found_without_edits(tmp_path):
    root = tinyroot.make(tmp_path)
    result = tinyroot.run(root, "tiny-composite", seed=2**31 + 11, trace=1)
    assert result["correct"], result
    metrics = result["metrics"]
    assert metrics["tiles_seen"]["value"] == result["attempted"] >= 1
    assert metrics["tiles_seen"]["unit"] == "tiles"
    for name in ("read_decode_s_per_tile", "band_math_s_per_tile"):
        assert metrics[name]["value"] > 0, name
    # a traced run reads no device on the CPU: those metrics are left out
    assert "device_idle_share" not in metrics
    assert result["device"]["window_s"] > 0
    assert list(result)[-1] == "checks"
