"""The committed four-chip cell, ``mistral-7b.distinct-closed``, through
``BENCHMARK.json``'s own entries: its files as data (always), and a traced
rehearsal of the whole command on four virtual CPU devices (``REHEARSE=1``,
about two minutes). A rehearsal proves the files and the harness, never the
chip: no number of it appears under a device metric's name."""

import json
import os
import subprocess
import sys

import pytest

import spec
from conftest import CHIP_DIR, REPO

CELL = "mistral-7b.distinct-closed"
NEW_METRICS = ("device.collective_busy_share", "engine.weights_init_s", "device.hbm_spread_gb")


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL, REPO)


def test_the_cell_is_the_published_model_on_four_chips(cell):
    cfg = cell.config
    assert cell.chips == cfg["chips"] == 4 and cfg["mesh"] == {"data": 2, "model": 2}
    assert (cfg["mcpx"]["engine"]["data_axis"], cfg["mcpx"]["engine"]["model_axis"]) == (2, 2)
    assert cfg["slab_rows"] == cfg["max_batch_size"] == 16
    entry = next(c for c in spec.load_benchmark(REPO)["configs"] if c["name"] == "mistral-7b")
    assert "num_hidden_layers" not in entry["reduced"] and sorted(entry["reduced"]) == sorted(cfg["reduced"])
    with open(os.path.join(CHIP_DIR, "configs", "mistral-7b-1chip.json")) as f:
        one_chip = json.load(f)
    published = spec.model_keys(cfg)
    assert published["num_hidden_layers"] == 32
    # every width is the one-chip guard's, which is the source's
    assert {k: v for k, v in published.items() if k != "num_hidden_layers"} == \
        {k: v for k, v in spec.model_keys(one_chip).items() if k != "num_hidden_layers"}


def test_its_traffic_is_distinct_closed_with_a_shorter_slice(cell):
    with open(os.path.join(CHIP_DIR, "traffic", "distinct-closed.json")) as f:
        base = json.load(f)
    differs = {k for k in base if base[k] != cell.traffic[k]}
    assert differs == {"why", "warm_plans", "trace_seconds"} and set(base) == set(cell.traffic)
    assert cell.traffic["warm_plans"] == 32 and cell.traffic["trace_seconds"] == 3.0


def test_its_metrics_are_the_old_ones_and_the_three_new(cell):
    names = [m.name for m in cell.per_layer]
    assert set(NEW_METRICS) <= set(names) and len(names) == 22
    for other in ("olmo2-1b.distinct-closed", "mistral-7b-1chip.distinct-closed"):
        theirs = {m.name for m in spec.load_cell(other, REPO).per_layer}
        # the one-chip cells keep their 19: one of them fetching /metrics too would need an
        # edit to test_run_helpers.py's pin of their endpoints, a benchmark PR's to make
        assert set(names) - theirs == set(NEW_METRICS) and len(theirs) == 19


@pytest.mark.skipif(os.environ.get("REHEARSE") != "1", reason="slow; set REHEARSE=1")
def test_traced_rehearsal_of_the_committed_cell():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/chip/run.py"), "--workload", CELL,
         "--seed", str(2**31 + 127), "--seconds", "6", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4, "memory_peak_bytes": 0}
    # The program's gauge prints; the two that read the device (its trace, its
    # allocator) find nothing on the CPU backend and are left out, not raised.
    assert 0 < line["metrics"]["engine.weights_init_s"]["value"] < 120
    assert "device.collective_busy_share" not in line["metrics"]
    assert "device.hbm_spread_gb" not in line["metrics"]
    info = next(json.loads(l[len("bench-info "):]) for l in r.stdout.splitlines()
                if l.startswith("bench-info "))
    assert info["devices"] == 4 and info["reference"]["rows"] == 16
