"""The files PR 58 added for ``jamba2-3b.wide-shortlist-closed``: the cell's
spec loads and its metrics find their readers; the configuration file keeps
every published width AND the published depth and states what it assumed; the
metric files on a recorded set of the new span attributes; the roofline
reader's bytes against a hand count for one call of each form, never above what
the call's operands hold, and 100 exactly at the HBM's peak; and
(``REHEARSE=1``) the rehearsed cell through ``run.py --rehearse-cpu``. Not a
device number."""

import json
import os
import subprocess
import sys

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "jamba2-3b.wide-shortlist-closed"
NEW = {"kernel.selective_scan_prefill_busy_share", "kernel.selective_scan_window_busy_share",
       "kernel.selective_scan_prefill_roofline", "kernel.selective_scan_window_roofline"}
SHARED = {"ssm.state_bytes_share", "engine.prefix_state_miss_share", "startup.weights_s"}
I, N, LJ = 5120, 16, 26
SLOT = N * I * 4  # one row's state of one J layer, float32


def _config():
    with open(os.path.join(CHIP_DIR, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "jamba2-3b", "wide-shortlist-closed")
    assert cell.config["module"] == "jamba" and spec.block_file("jamba").endswith("models/jamba.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW | SHARED <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    for form, span in (("prefill", "engine.prefill"), ("window", "engine.segment")):
        roof, busy = by_name[f"kernel.selective_scan_{form}_roofline"], by_name[f"kernel.selective_scan_{form}_busy_share"]
        assert roof.reader == "selective_scan_roofline" and busy.reader == "device_op_share"
        assert roof.args == {"regex": f"selective_scan_{form}", "span": span} and busy.args == {"regex": roof.args["regex"]}
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    bm = spec.load_benchmark(REPO)
    assert len(bm["configs"]) >= 11 and len(bm["workloads"]) >= 11
    for w in bm["workloads"]:
        spec.load_cell(w["name"], REPO)
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        if m["name"] in SHARED:
            assert m["workloads"][-1] == CELL or CELL in m["workloads"]
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not NEW & {m.name for m in spec.load_cell(other, REPO).per_layer}
    # the traffic file is a.x-k1's cell's, unedited; the 128-service shortlist is the configuration's
    assert cell.traffic["clients"] == "slab_rows" and cell.traffic["registry_services"] >= 1000
    assert cell.config["mcpx"]["planner"] == {"kind": "llm", "shortlist_top_k": 128}
    # the cohort buckets the issue fixed (a.x-k1's file's), and one tick of forwards a segment
    # pinned, with its readings
    engine = cell.config["mcpx"]["engine"]
    a_x_k1 = spec.load_cell("a.x-k1.wide-shortlist-closed", REPO).config["mcpx"]["engine"]
    assert engine["batch_buckets"] == a_x_k1["batch_buckets"] == [1, 2, 4] and engine["steps_per_dispatch"] == 1
    assert any("decode_steps_per_tick" in d for d in cell.config["departures"])


def test_the_configuration_file_keeps_every_published_width_and_the_depth():
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"AI21-Jamba2-3B"' in l)
        changed = {k for k, v in row["config"].items() if k not in config or config[k] != v}
        assert changed == {"vocab_size"} and config["source"] == row["source_url"]
    bm = spec.load_benchmark(REPO)
    entry = next(c for c in bm["configs"] if c["name"] == "jamba2-3b")
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)
    assert "num_hidden_layers" not in entry["reduced"] and config["num_hidden_layers"] >= 28
    no_width = ("_dim", "_rank", "hidden_size", "intermediate_size", "d_state", "d_conv", "expand",
                "num_experts_per_tok")
    assert not [k for k in entry["reduced"] if any(w in k for w in no_width)]
    assert {"deployment", "layer_order", "dense_everywhere", "mamba_mixer", "mamba_init", "no_positions",
            "state_precision", "norms", "tie_word_embeddings", "dtype"} <= set(config["assumed"])
    assert "ONE chip" in config["assumed"]["deployment"] and "No share" in config["assumed"]["deployment"]
    assert "2,869,429,632" in config["params"] and "5.74 GB" in config["params"]
    assert any("prefix_state_total" in d for d in config["departures"])
    sys.path.insert(0, REPO)
    cfg = spec.load_block("jamba").model_config(spec.model_keys(config), 3072)
    assert cfg.n_params >= 2_869_429_632 and cfg.ssm_slot_bytes == SLOT and cfg.n_scan_layers >= LJ


def _trace(started_at, spans):
    tree = [{"span_id": "root", "parent_id": None, "name": "plan", "start_ms": 0.0,
             "duration_ms": 1000.0, "attrs": {}}]
    for i, (name, start, dur, attrs) in enumerate(spans):
        tree.append({"span_id": f"s{i}", "parent_id": "root", "name": name,
                     "start_ms": start, "duration_ms": dur, "attrs": attrs})
    return {"trace_id": "t", "started_at": started_at, "tree": tree}


def _segment(calls, seq=0, weights=5_740_000_000, kv=1_000_000):
    return {"seq": seq, "forwards": 8, "ssm_row_calls": calls, "ssm_state_bytes": calls * SLOT * 2,
            "ssm_slots": calls * 2, "ssm_tokens": calls, "weight_bytes_read": weights, "kv_bytes_read": kv}


def _prefill(rows, bucket, T=1024):
    return {"cohort_rows": rows, "cohort_bucket": bucket, "scan_tokens": rows * 890 * LJ,
            "scan_slots": bucket * T * LJ, "ssm_state_bytes": bucket * LJ * SLOT, "ssm_prefill_tokens": 890 * LJ}


def _evidence(traces, device=None):
    return readers.Evidence([], traces, {}, {}, device, None, config=_config(), device_kind="TPU v5 lite")


def test_the_metric_files_read_a_recorded_set_of_the_attributes():
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    first, second = _segment(calls=120), _segment(calls=80, seq=1, weights=2_000_000_000)
    ev = _evidence([_trace(100.0, [("engine.segment", 10.0, 50.0, first)]),
                    _trace(100.0005, [("engine.segment", 9.6, 50.0, first), ("engine.segment", 70.0, 40.0, second)])])
    read = lambda name, e=ev: readers.read_metric(e, by_name[name].reader, by_name[name].args)
    state = 200 * SLOT * 2
    assert read("ssm.state_bytes_share") == state / (state + 7_740_000_000 + 2_000_000)
    # a program without the attributes (the parent): nothing to read, and no error
    bare = _evidence([_trace(100.0, [("engine.segment", 10.0, 50.0, {"forwards": 8}),
                                     ("engine.prefill", 0.0, 5.0, {"cohort_rows": 1})])],
                     {"window_s": 1.0, "busy_s": 1.0, "ops": {"fusion.1 bf16[8,4096] fusion": 1.0}})
    for name in NEW:
        want = 0.0 if name.endswith("busy_share") else None
        assert read(name, bare) == want, name
    # a configuration with no selective scan (another cell's file) reads nothing either
    other = readers.Evidence([], ev.traces, {}, {}, {"window_s": 1.0, "busy_s": 1.0, "ops": {"selective_scan_window.1": 0.5}},
                             None, config={"hidden_size": 64}, device_kind="TPU v5 lite")
    assert readers.read_metric(other, "selective_scan_roofline", {"regex": "selective_scan_window"}) is None


def test_the_roofline_readers_bytes_are_a_calls_operands_and_read_100_at_the_peak():
    """One call of each form by hand, from the shapes alone; then ten spans
    over one second of wall with the kernel busy for exactly the time the HBM
    needs: 100; at twice the time, 50."""
    sys.path.insert(0, os.path.join(CHIP_DIR, "reader_files"))
    import selective_scan_roofline as roof

    config = _config()
    # a decode window's call on ONE live row of ONE layer: the state read and written, A, dt and x
    # of 16 tokens, y of 8, B of 16 and C of 8
    got, ops = roof._call_bytes(config, "engine.segment", {"ssm_state_bytes": 2 * SLOT})
    by_hand = 2 * SLOT + N * I * 4 + 2 * 16 * I * 4 + 8 * I * 4 + (16 + 8) * N * 4
    assert got == by_hand and ops == 16 * 6 * N * I
    holds = SLOT * 2 + N * I * 4 + 4 * (2 * 16 * I + 8 * I) + 4 * 2 * 128 * N  # B and C as handed over: a lane block each
    assert got <= holds
    # a prefill call on ONE row of ONE layer at the 1,024 bucket: dt, x, y, B, C, A, the state written
    got, ops = roof._call_bytes(config, "engine.prefill", {"scan_slots": 1024, "ssm_state_bytes": SLOT})
    by_hand = 3 * 1024 * I * 4 + 2 * 1024 * N * 4 + N * I * 4 + SLOT
    assert got == by_hand and ops == 1024 * 6 * N * I
    assert ops / 197e12 < got / 819e9 / 20  # the bytes bind the reading, not the vector operations
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    calls = 2_000
    segments = [("engine.segment", 100.0 * i, 100.0, _segment(calls=calls // 10, seq=i)) for i in range(10)]
    prefills = [("engine.prefill", 100.0 * i, 100.0, _prefill(rows=3, bucket=4)) for i in range(10)]
    window_s = roof._call_bytes(config, "engine.segment", {"ssm_state_bytes": calls * SLOT * 2})[0] / 819e9
    prefill_s = roof._call_bytes(config, "engine.prefill", {
        "scan_slots": 10 * 4 * 1024 * LJ, "ssm_state_bytes": 10 * 4 * LJ * SLOT})[0] / 819e9
    for stretch in (1, 2):
        device = {"window_s": 2.0, "busy_s": 1.9, "ops": {
            "selective_scan_window.61 (tuple) custom-call": 2.0 * window_s * stretch * 0.6,
            "selective_scan_window.62 (tuple) custom-call": 2.0 * window_s * stretch * 0.4,
            "selective_scan_prefill.7 (tuple) custom-call": 2.0 * prefill_s * stretch,
            "ssm_window.3 (tuple) custom-call": 0.1,  # not theirs
            "fusion.1 bf16[8,4096] fusion": 0.5,
        }}
        ev = _evidence([_trace(50.0, segments + prefills)], device)
        for name in ("kernel.selective_scan_window_roofline", "kernel.selective_scan_prefill_roofline"):
            got = readers.read_metric(ev, by_name[name].reader, by_name[name].args)
            assert abs(got - 100.0 / stretch) < 1e-9, (name, got)
        busy = readers.read_metric(ev, "device_op_share", by_name["kernel.selective_scan_prefill_busy_share"].args)
        assert abs(busy - 100.0 * prefill_s * stretch) < 1e-9


def test_the_rehearsed_cell_is_correct_and_its_control_is_not():
    """``REHEARSE=1``: the cell from its committed files through ``run.py
    --rehearse-cpu`` (the served path, POST /plan, interpreted kernels) reads
    ``correct``; the comparison's control (the state moved by the window, not
    by what the row kept) reads not ``correct`` (~4 minutes)."""
    if not os.environ.get("REHEARSE"):
        pytest.skip("REHEARSE=1 runs the rehearsed cell (minutes)")
    run = [sys.executable, os.path.join(CHIP_DIR, "run.py"), "--workload", CELL, "--rehearse-cpu",
           "--seed", str(2**31 + 5801), "--seconds", "10", "--trace", "1"]
    out = subprocess.run(run, cwd=REPO, capture_output=True, text=True, timeout=1500,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"], out.stdout[-2000:]
    assert {"ssm.state_bytes_share", "engine.prefix_state_miss_share", "startup.weights_s"} <= set(line["metrics"])
    sys.path.insert(0, REPO)
    import dataclasses

    import jax

    import reference
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("jamba")
    cfg = block.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    block.CONTROLS["state_moves_by_the_window"] = True
    try:
        wrong = reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 5801, interpret=True,
            page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3)
    finally:
        block.CONTROLS["state_moves_by_the_window"] = False
    assert not wrong["ok"], wrong
