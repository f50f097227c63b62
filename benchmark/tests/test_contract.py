"""``BENCHMARK.json`` against the contract it is written to, and the
harness against its own rule: every cell, configuration, mix and metric is
a file found by its name, and ``run.py`` names none of them."""

import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert SPEC["command"] == ["python3", "benchmark/run.py"]
    assert SPEC["paths"] == ["benchmark"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells must fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert c["file"].startswith("benchmark/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank)$|hidden|intermediate|head_|width", key)
        held = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(held["reduced"])  # each with its reason
        assert (BENCH / held["reference"]).is_file()


def test_workloads():
    cells = SPEC["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        held = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert {"kernel_paths", "tpu_custom_calls"} <= set(held["expect"])
        mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert {"argv", "train_program", "window"} <= set(mix)


def test_metrics():
    e2e, layer = SPEC["end_to_end"], SPEC["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in SPEC["workloads"]}
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
        assert (BENCH / "end_to_end" / f"{m['name']}.py").is_file()
    for m in layer:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in {x["name"] for x in e2e}
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:  # every cell: set-up, another end-to-end, a per-layer
        mine = [m["name"] for m in e2e if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in layer)


def test_run_py_names_no_cell_configuration_mix_or_metric():
    text = (BENCH / "run.py").read_text()
    named = (
        [w["name"] for w in SPEC["workloads"]]
        + [w["traffic"] for w in SPEC["workloads"]]
        + [c["name"] for c in SPEC["configs"]]
        + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    )
    assert not [n for n in named if n in text]


def test_file_names_under_paths():
    for path in BENCH.rglob("*"):
        if "__pycache__" in path.parts or path.name.startswith(".call"):
            continue
        assert re.match(r"^[A-Za-z0-9_.\-]+$", path.name), path
