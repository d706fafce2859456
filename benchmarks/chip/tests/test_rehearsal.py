"""End-to-end rehearsals of the command on the CPU (``--rehearse-cpu``:
model=test, interpreted kernel). About a minute each, so they run only when
asked:  REHEARSE=1 python -m pytest benchmarks/chip/tests/test_rehearsal.py -q

They prove the harness — every loop kind and intent source, every cell's
traffic, the four-chip configuration's mesh on four virtual devices — and
nothing about the chip: a rehearsal's line says ``platform: cpu`` and
carries no device metric."""

import json
import os
import subprocess
import sys

import pytest

import spec
from conftest import CHIP_DIR, REPO

pytestmark = pytest.mark.skipif(
    os.environ.get("REHEARSE") != "1", reason="slow; set REHEARSE=1"
)

DEVICE_METRICS = {"kernel.attn_busy_share", "device.copy_busy_share", "device.idle_share",
                  "device.hbm_in_use_gb"}


def rehearse(root, cell, trace, seconds=6):
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks/chip/run.py"), "--workload", cell,
         "--seed", str(2**31 + 77), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert not DEVICE_METRICS & set(line["metrics"])  # no CPU number under a device name
    assert "busy_s" not in line["device"] and "breakdown" not in line
    return line


@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_benchmark(REPO)["workloads"]])
def test_every_committed_cell(cell):
    e2e = rehearse(REPO, cell, trace=0)
    assert set(e2e["metrics"]) == {m.name for m in spec.load_cell(cell, REPO).end_to_end}
    assert {"plans_per_s", "plan_p80_ms", "setup_s"} <= set(e2e["metrics"])
    per_layer = rehearse(REPO, cell, trace=1)
    assert {"engine.segment_ms", "step.tok_per_forward", "gen.late_p99_ms",
            "engine.decode_tok_per_plan"} <= set(per_layer["metrics"])


def test_repeat_intents(tree):
    root = tree(cell={"name": "olmo2-1b.repeat-closed", "config": "olmo2-1b",
                      "traffic": "repeat-closed", "chips": 1, "why": "rehearsal"})
    line = rehearse(root, "olmo2-1b.repeat-closed", trace=1)
    assert 40 < line["metrics"]["planner.cache_hit_share"]["value"] < 95


def test_paced_loop_and_session_intents(tree):
    root = tree(
        cell={"name": "olmo2-1b.session-paced", "config": "olmo2-1b", "traffic": "session-paced",
              "chips": 1, "why": "rehearsal"},
        traffic=("session-paced", {"loop": "paced", "intents": "session", "families": 4,
                                   "rate_per_s": 6.0, "warm_plans": 8}),
    )
    line = rehearse(root, "olmo2-1b.session-paced", trace=1)
    assert "gen.late_p99_ms" in line["metrics"]
    # variants of one task share the services block: the radix cache matches it
    assert line["metrics"]["engine.prefill_tok_per_plan"]["value"] < 30


def test_four_chip_configuration_on_four_virtual_devices(tree):
    base = json.load(open(os.path.join(CHIP_DIR, "configs", "mistral-7b-1chip.json")))
    cfg = {**base, "name": "mistral-7b", "num_hidden_layers": 32, "chips": 4,
           "mesh": {"data": 2, "model": 2}, "slab_rows": 16}
    root = tree(
        cell={"name": "mistral-7b.distinct-closed", "config": "mistral-7b",
              "traffic": "distinct-closed", "chips": 4, "why": "rehearsal"},
        config=("mistral-7b", cfg),
    )
    line = rehearse(root, "mistral-7b.distinct-closed", trace=0)
    assert line["device"]["count"] == 4


def test_a_new_block_arrives_as_files(tree):
    """A configuration naming another block module runs through child.py and
    reference.py with no edit to either: the probe block (tests/models/probe.py:
    its own reference and its own program step) dropped into a checkout."""
    import shutil

    base = json.load(open(os.path.join(CHIP_DIR, "configs", "olmo2-1b.json")))
    root = tree(
        cell={"name": "probe.distinct-closed", "config": "probe", "traffic": "distinct-closed",
              "chips": 1, "why": "rehearsal"},
        config=("probe", {**base, "name": "probe", "module": "probe"}),
    )
    shutil.copy(os.path.join(CHIP_DIR, "tests", "models", "probe.py"),
                os.path.join(root, "benchmarks", "chip", "models", "probe.py"))
    rehearse(root, "probe.distinct-closed", trace=0)
    with open(os.path.join(root, ".chip", "bench", "probe.distinct-closed", "server.log")) as f:
        assert "Traceback" not in f.read()
