"""Every name in BENCHMARK.json resolves to its files, and the file meets the contract's shape."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["perf"] and 1 <= bench["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in bench["end_to_end"])


def test_every_workload_resolves(bench):
    from perf.run import load_cell

    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        loaded = load_cell(w["name"], rehearse=False)
        assert loaded.cell["why"] == w["why"]
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]]["file"]))
        assert os.path.isfile(os.path.join(ROOT, "perf", "systems", loaded.config["system"] + ".py"))
        assert os.path.isfile(os.path.join(ROOT, "perf", "reference", loaded.config["reference"] + ".py"))
        assert os.path.isfile(os.path.join(ROOT, "perf", "traffic", loaded.mix["generator"] + ".py"))  # found by name
        assert loaded.end_to_end and loaded.per_layer
        assert load_cell(w["name"], rehearse=True).config["hidden_size"] < loaded.config["hidden_size"]
    assert {w["config"] for w in bench["workloads"]} == set(configs)


def test_every_metric_has_a_reader_and_a_home(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "perf", "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e and m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), f"{m['name']} is reported where {m['moves']} is not"
    for cell in cells:
        own = [m for m in bench["end_to_end"] if cell in m.get("workloads", cells) and m["name"] != "setup_s"]
        assert own, f"{cell} reports only setup_s"
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def test_reduced_names_no_width(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|head)", key), key
        for key, published in held.get("published", {}).items():
            assert key in c["reduced"] and held[key] != published


def test_harness_imports_nothing_it_must_not():
    for folder, _, files in os.walk(os.path.join(ROOT, "perf")):
        for name in files:
            if name.endswith(".py") and "tests" not in folder:
                text = open(os.path.join(folder, name)).read()
                assert not re.search(r"^\s*(from|import) (chip_smoke|benchmarks|unionml_tpu\.workloads)", text, re.M), name
    for name in ("decoder.py", "encoder.py"):
        text = open(os.path.join(ROOT, "perf", "reference", name)).read()
        assert "unionml_tpu" not in re.sub(r'""".*?"""', "", text, flags=re.S), f"reference/{name} imports the program"
        assert "optax" not in text.split('"""')[2] and "flax" not in text.split('"""')[2]
