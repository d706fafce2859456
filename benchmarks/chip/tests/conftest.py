"""Harness tests (CPU; run by hand: ``python -m pytest benchmarks/chip/tests -q``;
not part of tier-1). The harness's modules import each other by bare name, as
they do when ``run.py`` is the script."""

import json
import os
import shutil
import sys

import pytest

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path.insert(0, CHIP_DIR)


@pytest.fixture
def tree(tmp_path):
    """A temporary checkout: BENCHMARK.json, a copy of benchmarks/chip and a
    link to the program. ``add(...)`` drops new data files and ONE new
    ``workloads`` entry in, editing nothing that was there."""
    root = tmp_path / "checkout"
    shutil.copytree(
        CHIP_DIR, root / "benchmarks" / "chip",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "mcpx"), root / "mcpx")

    def add(*, cell, config=None, traffic=None, metric=None):
        bm = json.loads((root / "BENCHMARK.json").read_text())
        chip = root / "benchmarks" / "chip"
        if config is not None:
            name, body = config
            (chip / "configs" / f"{name}.json").write_text(json.dumps(body))
            bm["configs"].append({
                "name": name, "source": body["source"],
                "file": f"benchmarks/chip/configs/{name}.json",
                "reduced": list(body.get("reduced", {})), "why": "test",
            })
        if traffic is not None:
            name, body = traffic
            (chip / "traffic" / f"{name}.json").write_text(json.dumps(body))
        if metric is not None:
            entry, body = metric
            (chip / "metrics" / f"{entry['name']}.json").write_text(json.dumps(body))
            bm["per_layer"].append(entry)
        bm["workloads"].append(cell)
        (root / "BENCHMARK.json").write_text(json.dumps(bm))
        return str(root)

    add.root = str(root)
    return add
