"""The four worker-timeline metrics (PR 24) are data files over a reader
that was there: ``span_attr_ratio`` over the flat attributes the engine
writes on ``engine.segment`` and ``engine.queue_wait`` spans.

``worker_traces.json`` holds eight ``GET /traces/{id}`` bodies recorded
from a CPU rehearsal of ``olmo2-1b.distinct-closed`` (``--rehearse-cpu``:
model=test, interpreted kernel): the plans that rode dispatched segments
18, 19 and 20. Its numbers pin the readers' arithmetic; they are not
device numbers."""

import copy
import json
import os
import subprocess
import sys

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

NEW = {
    "step.forward_period_ms": ("model step", "plans_per_s", 5.247128),
    "engine.host_ms_per_forward": ("engine", "plans_per_s", 0.670128),
    "engine.queue_free_row_share": ("engine", "plan_p50_ms", 1.0),
    "engine.queue_unseen_share": ("engine", "plan_p50_ms", 0.931170),
}
CELLS = [w["name"] for w in spec.load_benchmark(REPO)["workloads"]]


def recorded() -> list:
    with open(os.path.join(CHIP_DIR, "tests", "worker_traces.json")) as f:
        return json.load(f)


def evidence(traces: list) -> readers.Evidence:
    return readers.Evidence([], traces, {}, {}, None, None)


def spans(traces: list, name: str) -> list:
    return [sp for tr in traces for sp in tr["tree"] if sp["name"] == name]


@pytest.mark.parametrize("cell", CELLS)
def test_the_four_metrics_load_in_every_cell_and_read_the_recorded_values(cell):
    by_name = {m.name: m for m in spec.load_cell(cell, REPO).per_layer}
    assert set(NEW) <= set(by_name)
    ev = evidence(recorded())
    for name, (layer, moves, want) in NEW.items():
        m = by_name[name]
        assert (m.reader, m.source, m.layer, m.moves) == (
            "span_attr_ratio", "program_span", layer, moves
        )
        assert readers.read_metric(ev, m.reader, m.args) == pytest.approx(want, abs=1e-6)


def test_the_recorded_values_are_what_the_spans_say():
    """The same four numbers by plain arithmetic, a segment found by its
    ``seq`` and not by the readers' clustering of span starts."""
    traces = recorded()
    by_seq = {}
    for sp in spans(traces, "engine.segment"):
        a = sp["attrs"]
        first = by_seq.setdefault(a["seq"], a)
        # One harvest, one timeline on all its rows.
        assert {k: a[k] for k in ("period_ms", "host_ms", "sync_ms", "idle_ms", "forwards")} == {
            k: first[k] for k in ("period_ms", "host_ms", "sync_ms", "idle_ms", "forwards")
        }
    assert sorted(by_seq) == [18, 19, 20]
    forwards = sum(a["forwards"] for a in by_seq.values())
    assert sum(a["period_ms"] for a in by_seq.values()) / forwards == pytest.approx(
        NEW["step.forward_period_ms"][2], abs=1e-6
    )
    assert sum(a["host_ms"] for a in by_seq.values()) / forwards == pytest.approx(
        NEW["engine.host_ms_per_forward"][2], abs=1e-6
    )
    waits = spans(traces, "engine.queue_wait")
    total = sum(sp["duration_ms"] for sp in waits)
    for key, metric in (("free_row_ms", "engine.queue_free_row_share"),
                        ("unseen_ms", "engine.queue_unseen_share")):
        assert all(0.0 <= sp["attrs"][key] <= sp["duration_ms"] for sp in waits)
        assert sum(sp["attrs"][key] for sp in waits) / total == pytest.approx(
            NEW[metric][2], abs=1e-6
        )
    # The period is not the two-deep span: it is shorter per forward.
    ev = evidence(traces)
    two_deep = readers.read_metric(ev, "span_attr_ratio", dict(
        name="engine.segment", num="@duration_ms", den="forwards",
        num_per="segment", den_per="segment"))
    assert NEW["step.forward_period_ms"][2] < two_deep


def test_a_program_without_the_attributes_reads_nothing_and_does_not_raise():
    """The parent commit writes the spans but not the attributes: each of
    the four is then left out of the line, and the metrics that read the
    same spans' older attributes read what they read before."""
    traces = recorded()
    bare = copy.deepcopy(traces)
    added = {"seq", "prefill_rows", "period_ms", "sync_ms", "idle_ms", "host_ms",
             "admit_ms", "dispatch_ms", "harvest_ms", "unseen_ms", "free_row_ms"}
    for tr in bare:
        for sp in tr["tree"]:
            for key in added & set(sp.get("attrs", {})):
                del sp["attrs"][key]
    cell = spec.load_cell(CELLS[0], REPO)
    for m in cell.per_layer:
        if m.source != "program_span":
            continue
        got = readers.read_metric(evidence(bare), m.reader, m.args)
        if m.name in NEW:
            assert got is None
        else:
            assert got is not None
            assert got == readers.read_metric(evidence(traces), m.reader, m.args)


@pytest.mark.skipif(os.environ.get("REHEARSE") != "1", reason="slow; set REHEARSE=1")
def test_a_traced_rehearsal_prints_all_four():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/chip/run.py"), "--workload",
         "olmo2-1b.distinct-closed", "--trace", "1", "--rehearse-cpu", "--seconds", "20",
         "--seed", str(2**31 + 129)],
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert set(NEW) <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < m["step.forward_period_ms"] <= m["step.forward_ms"]
    assert 0.0 <= m["engine.queue_unseen_share"] <= 1.0
    assert 0.0 <= m["engine.queue_free_row_share"] <= 1.0
