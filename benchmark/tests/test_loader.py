"""A configuration, a mix, a cell and a per-layer metric are added as new
files plus new entries: the loader finds them with no file edited."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import loader  # noqa: E402


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)


def test_new_files_are_found_by_name(tmp_path):
    here = str(tmp_path / "benchmark")
    write(f"{here}/configs/new-model.json", json.dumps({"pipeline": "new_pipe", "hidden_size": 8}))
    write(f"{here}/pipelines/new_pipe.py", "def build(ctx):\n    ctx.built = 'new_pipe'\n")
    write(f"{here}/traffic/new-mix.json", json.dumps({"generator": "new_gen", "rate_per_s": 3}))
    write(f"{here}/generators/new_gen.py", "def setup(ctx):\n    return 'new_gen'\n")
    write(f"{here}/limits/new-model.new-mix.json", json.dumps({"limits": {"gap": 0.5}}))
    write(f"{here}/layer_metrics/new_layer.metric-1.py", "def read(ctx):\n    return 42.0\n")
    write(f"{here}/layer_metrics/silent.py", "def read(ctx):\n    return None\n")
    spec = {
        "configs": [{"name": "new-model", "file": "benchmark/configs/new-model.json"}],
        "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "new_rate", "unit": "x/s", "workloads": ["new-model.new-mix"]},
            {"name": "other_rate", "unit": "x/s", "workloads": ["some.other-cell"]},
        ],
        "per_layer": [
            {"name": "new_layer.metric-1", "unit": "%", "moves": "new_rate",
             "workloads": ["new-model.new-mix"]},
            {"name": "silent", "unit": "%", "moves": "new_rate"},
            {"name": "elsewhere", "unit": "%", "moves": "other_rate"},
        ],
    }
    cell = loader.Cell(spec, "new-model.new-mix", here=here)
    assert cell.config["hidden_size"] == 8 and cell.traffic["rate_per_s"] == 3
    assert cell.limits["limits"] == {"gap": 0.5}

    class Ctx:
        pass

    ctx = Ctx()
    cell.pipeline.build(ctx)
    assert ctx.built == "new_pipe" and cell.generator.setup(ctx) == "new_gen"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "new_rate"]
    assert [m["name"] for m in cell.per_layer] == ["new_layer.metric-1", "silent"]
    assert cell.reader("new_layer.metric-1")(ctx) == 42.0
    # a reader that finds nothing returns nothing, and the line leaves it out
    assert cell.reader("silent")(ctx) is None


def test_the_committed_cells_load():
    spec = loader.load()
    for entry in spec["workloads"]:
        cell = loader.Cell(spec, entry["name"])
        assert cell.limits["limits"], entry["name"]
        for metric in cell.per_layer:
            assert callable(cell.reader(metric["name"]))
