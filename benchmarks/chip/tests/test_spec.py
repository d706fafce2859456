import json
import os
import re
import subprocess
import sys

import pytest

import spec
from conftest import CHIP_DIR, REPO


def test_the_committed_benchmark_loads_and_keeps_the_contract():
    bm = spec.load_benchmark(REPO)
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bm["run_seconds"] <= 51
    assert [m["name"] for m in bm["end_to_end"]] == ["plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"]
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(bm["paths"][0] + "/") and len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in bm["workloads"])
        width = re.compile(r"(hidden_size|intermediate_size|head_dim|_dim$|_rank$|latent|state_size)")
        assert not [k for k in c["reduced"] if width.search(k)]
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        cell = spec.load_cell(w["name"], REPO)
        assert cell.per_layer and {m.name for m in cell.end_to_end} >= {"setup_s", "plans_per_s"}
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four <= max(1, len(bm["workloads"]) // 4)
    # every file under paths is named from the characters of a name and '/'
    for base, _dirs, files in os.walk(CHIP_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), REPO)
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_new_config_traffic_metric_and_cell_are_found_without_an_edit(tree):
    base = json.load(open(os.path.join(CHIP_DIR, "configs", "olmo2-1b.json")))
    new_cfg = {**base, "name": "tiny-new", "source": "https://example.org/tiny-new/config.json",
               "num_hidden_layers": 2, "reduced": {"num_hidden_layers": "test"}}
    new_traffic = {"loop": "paced", "intents": "session", "families": 4, "rate_per_s": 2.0}
    entry = {"name": "engine.decode_ms", "unit": "ms", "better": "lower", "source": "program_span",
             "layer": "engine", "moves": "plan_p50_ms", "workloads": ["tiny-new.session-paced"]}
    body = {"name": "engine.decode_ms", "unit": "ms", "layer": "engine", "moves": "plan_p50_ms",
            "reader": "span_self_quantile", "args": {"name": "engine.decode", "q": 0.5}}
    root = tree(
        cell={"name": "tiny-new.session-paced", "config": "tiny-new", "traffic": "session-paced",
              "chips": 1, "why": "test"},
        config=("tiny-new", new_cfg), traffic=("session-paced", new_traffic), metric=(entry, body),
    )
    cell = spec.load_cell("tiny-new.session-paced", root)
    assert cell.config["num_hidden_layers"] == 2 and cell.traffic["families"] == 4
    assert "engine.decode_ms" in {m.name for m in cell.per_layer}
    assert "engine.segment_ms" in {m.name for m in cell.per_layer}  # one with no 'workloads': every cell's
    old = spec.load_cell("olmo2-1b.distinct-closed", root)
    assert "engine.decode_ms" not in {m.name for m in old.per_layer}
    # the command itself resolves the new cell from the temporary tree (and
    # fails only for want of a TPU, after loading it)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks/chip/run.py"), "--workload", "nope"],
        capture_output=True, text=True,
    )
    assert r.returncode == 1 and "tiny-new.session-paced" in r.stderr and not r.stdout.strip()


@pytest.mark.parametrize("name", ["has space", "a/b", "a,b", "", "x" * 65, "-lead", ".lead", "grün", "μs"])
def test_names_outside_the_alphabet_are_rejected(name):
    with pytest.raises(spec.SpecError):
        spec.check_name(name, "test")


@pytest.mark.parametrize("name", ["plans_per_s", "olmo2-1b.distinct-closed", "_x", "7b", "a" * 64])
def test_good_names(name):
    assert spec.check_name(name, "test") == name


@pytest.mark.parametrize("unit", ["tokens per second", "", "μs", "x" * 17, "a,b"])
def test_bad_units_are_rejected(unit):
    with pytest.raises(spec.SpecError):
        spec.check_unit(unit, "test")


@pytest.mark.parametrize("unit", ["plans/s", "%", "ms", "GB", "tokens/s", "us"])
def test_good_units(unit):
    assert spec.check_unit(unit, "test") == unit


def test_bad_entries_are_refused(tree):
    root = tree.root
    path = os.path.join(root, "BENCHMARK.json")
    good = json.load(open(path))

    def broken(change):
        bm = json.loads(json.dumps(good))
        change(bm)
        json.dump(bm, open(path, "w"))
        with pytest.raises(spec.SpecError):
            spec.load_cell("olmo2-1b.distinct-closed", root)

    broken(lambda bm: bm["per_layer"][0].update(moves="nothing"))
    broken(lambda bm: bm["per_layer"][0].update(unit="milli seconds"))
    broken(lambda bm: bm["workloads"][0].update(chips=2))
    broken(lambda bm: bm["end_to_end"].pop())  # setup_s
    broken(lambda bm: bm["workloads"].append(dict(bm["workloads"][0])))  # duplicate name
    broken(lambda bm: bm["per_layer"][1].update(layer="another layer"))  # disagrees with its file
    broken(lambda bm: bm["configs"][0]["reduced"].append("no_such_key"))
