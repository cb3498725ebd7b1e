"""``BENCHMARK.json`` against the rules a check refuses a manifest by
before any run (PR 34 was refused for one ``why`` of more than 200
characters): names, the printable one-line strings and their lengths,
every file an entry names, every cell's limits, configuration, mix and
generator, every per-layer metric's reader and the cells it lists, the
count of cells and of four-chip cells."""

import json
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def entries(kind: str) -> list:
    return [pytest.param(e, id=e["name"]) for e in manifest()[kind]]


def one_line(text) -> bool:
    """1 to 200 printable ASCII characters, no tab, no line break."""
    return isinstance(text, str) and 1 <= len(text) <= 200 and all(
        32 <= ord(ch) < 127 for ch in text)


def test_the_manifest_as_a_whole():
    spec = manifest()
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(one_line(word) for word in spec["command"])
    assert spec["paths"] == ["benchmark"] and isinstance(spec["run_seconds"], int)
    assert 1 <= len(spec["configs"]) <= 24 and 1 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["per_layer"]) <= 128
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in spec[kind]]
        assert len(names) == len(set(names)), kind
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}          # every configuration has a cell
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    # a full check fits the time a check has
    runs = 2 + 14 * len(spec["workloads"])
    assert runs * (spec["run_seconds"] + 60) + 2 * 90 * len(spec["workloads"]) + 1200 <= 43200


@pytest.mark.parametrize("config", entries("configs"))
def test_a_configuration(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["why"]), len(config["why"])
    assert one_line(config["source"]) and config["source"].startswith("https://")
    assert config["file"].startswith("benchmark/") and re.fullmatch(r"[A-Za-z0-9_.\-/]+", config["file"])
    assert len(config["reduced"]) <= 16 and all(NAME.match(key) for key in config["reduced"])
    with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as f:
        held = json.load(f)
    assert held.get("source", config["source"]) == config["source"]
    assert held.get("reduced", config["reduced"]) == config["reduced"]
    assert os.path.isfile(os.path.join(BENCH, "pipelines", held["pipeline"] + ".py"))
    widths = re.compile(r"(_dim|_rank)$|(hidden|intermediate)_size|head_dim|expand|experts_per_tok")
    assert not [key for key in config["reduced"] if widths.search(key)]      # no width is cut


@pytest.mark.parametrize("cell", entries("workloads"))
def test_a_cell(cell):
    spec = manifest()
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert one_line(cell["why"]), len(cell["why"])
    assert cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in spec["configs"]}
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert os.path.isfile(os.path.join(BENCH, "generators", mix["generator"] + ".py"))
    with open(os.path.join(BENCH, "limits", cell["name"] + ".json"), encoding="utf-8") as f:
        assert json.load(f)["limits"]
    # it reports set-up, another end-to-end metric and a per-layer one
    def reports(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])

    felt = [m["name"] for m in spec["end_to_end"] if reports(m)]
    assert "setup_s" in felt and len(felt) >= 2
    assert any("workloads" in m and reports(m) for m in spec["per_layer"])


@pytest.mark.parametrize("metric", entries("end_to_end") + entries("per_layer"))
def test_a_metric(metric):
    spec = manifest()
    felt = {m["name"]: m for m in spec["end_to_end"]}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    cells = {w["name"] for w in spec["workloads"]}
    listed = metric.get("workloads")
    if listed is not None:
        assert listed and len(listed) == len(set(listed)) and set(listed) <= cells
    if metric["name"] in felt:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace") and 0 < metric["bound"] < 1
        return
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert one_line(metric["layer"]) and metric["moves"] in felt
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    # every cell it lists reports the end-to-end metric it moves
    moved = felt[metric["moves"]].get("workloads", sorted(cells))
    assert set(listed if listed is not None else moved) <= set(moved)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%" and metric["better"] == "higher"
