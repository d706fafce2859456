"""The files PR 48 added for ``nemotron-3-super.distinct-closed``: the cell's
spec loads and its metrics find their readers; the configuration file keeps
every published width and states its cut; the five new metric files on a
recorded set of the new ``engine.segment`` attributes; both roofline readers
against a hand count on a synthetic trace, 100 exactly at the peaks; and
(``REHEARSE=1``) the rehearsed cell through ``child.py`` with the control
that moves the state by the window reading not ``correct``. Not a device
number."""

import json
import os
import subprocess
import sys

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "nemotron-3-super.distinct-closed"
NEW = {"kernel.ssm_busy_share", "kernel.ssm_window_roofline", "kernel.routed_experts_roofline",
       "ssm.state_bytes_share", "engine.prefix_state_miss_share"}
# the sparse cells' metrics that list this cell too (its expert layers write what theirs do)
SHARED = {"kernel.moe_busy_share", "moe.experts_touched_share", "moe.tok_per_touched_expert",
          "moe.held_assignment_share", "moe.load_max_over_mean", "moe.touched_per_sparse_layer",
          "moe.prefill_rows_per_assignment", "moe.routed_bytes_share"}
SLOT = 128 * 64 * 128 * 4  # one row's state of one Mamba layer, float32
EXPERT = 2 * 1024 * 2688 * 2  # one routed expert's two matrices, bfloat16


def _config():
    with open(os.path.join(CHIP_DIR, "configs", "nemotron-3-super.json")) as f:
        return json.load(f)


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "nemotron-3-super", "distinct-closed")
    assert cell.config["module"] == "nemotron_h" and spec.block_file("nemotron_h").endswith("models/nemotron_h.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    assert by_name["kernel.ssm_window_roofline"].reader == "ssm_state_roofline"
    assert by_name["kernel.routed_experts_roofline"].reader == "routed_experts_roofline"
    assert by_name["kernel.ssm_busy_share"].args == {"regex": "ssm_window"}
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    bm = spec.load_benchmark(REPO)
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    # the 31 metrics with no list of cells read here too; the five are no other cell's
    assert len([m for m in bm["per_layer"] if "workloads" not in m]) == 31
    assert {m.name for m in cell.per_layer if m.name in SHARED} == SHARED
    assert len(cell.per_layer) == 31 + 5 + 8
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not NEW & {m.name for m in spec.load_cell(other, REPO).per_layer}
    # the traffic file is the other distinct-closed cells', unedited; one tick of 12 forwards a
    # segment is pinned, with the reading that shows why among the file's departures
    assert cell.traffic["clients"] == "slab_rows" and cell.traffic["intents"] == "distinct"
    assert cell.config["mcpx"] == {"model": {"vocab": "bpe"}, "planner": {"kind": "llm"},
                                   "engine": {"warmup_compile": True, "temperature": 0.0,
                                              "decode_steps_per_tick": 12, "steps_per_dispatch": 1}}
    assert any("decode_steps_per_tick 12" in d and "4.9%" in d for d in cell.config["departures"])


def test_the_configuration_file_keeps_every_published_width_and_states_its_cut():
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if "Nemotron-3-Super" in l)
        changed = {k for k, v in row["config"].items() if k not in config or config[k] != v}
        assert changed == {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}
        assert config["source"] == row["source_url"]
        assert row["config"]["hybrid_override_pattern"].startswith(config["hybrid_override_pattern"])
    assert (config["n_routed_experts_published"], config["n_routed_experts"], config["expert_first"]) == (512, 128, 0)
    bm = spec.load_benchmark(REPO)
    entry = next(c for c in bm["configs"] if c["name"] == "nemotron-3-super")
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)
    no_width = ("_dim", "_rank", "hidden_size", "intermediate_size", "latent_size", "state_size",
                "num_experts_per_tok", "expand")
    assert not [k for k in entry["reduced"] if any(w in k for w in no_width)]
    assert {"deployment", "no_rotation", "state_precision", "gated_norm", "router", "mamba_init",
            "dtype"} <= set(config["assumed"])
    assert "4 chips" in config["assumed"]["deployment"] and "128" in config["assumed"]["deployment"]
    assert "rope_theta" in config["assumed"]["no_rotation"] and "partial_rotary_factor" in config["assumed"]["no_rotation"]
    assert "8.81 GB" in config["params"] and "4.405 B" in config["params"]
    assert any("prediction module" in d for d in config["departures"])
    assert any("prefix_state_total" in d for d in config["departures"])
    sys.path.insert(0, REPO)
    cfg = spec.load_block("nemotron_h").model_config(spec.model_keys(config), 3072)
    assert cfg.n_params == 4_404_894_080 and cfg.ssm_slot_bytes == SLOT


def _trace(started_at, segments):
    tree = [{"span_id": "root", "parent_id": None, "name": "plan", "start_ms": 0.0,
             "duration_ms": 1000.0, "attrs": {}}]
    for i, (start, dur, attrs) in enumerate(segments):
        tree.append({"span_id": f"s{i}", "parent_id": "root", "name": "engine.segment",
                     "start_ms": start, "duration_ms": dur, "attrs": attrs})
    return {"trace_id": "t", "started_at": started_at, "tree": tree}


def _segment(calls, slots, tokens, touched, weights=4_000_000_000, kv=1_000_000, seq=0):
    return {"seq": seq, "forwards": 8, "ssm_row_calls": calls, "ssm_state_bytes": calls * SLOT * 2,
            "ssm_slots": slots, "ssm_tokens": tokens, "moe_experts_touched": touched,
            "weight_bytes_read": weights, "kv_bytes_read": kv}


def _evidence(traces, device=None):
    return readers.Evidence([], traces, {}, {}, device, None, config=_config(),
                            device_kind="TPU v5 lite")


def test_the_metric_files_read_a_recorded_set_of_the_new_attributes():
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    first = _segment(calls=120, slots=300, tokens=270, touched=700)
    second = _segment(calls=80, slots=100, tokens=90, touched=300, weights=2_000_000_000, seq=1)
    # two rows' traces see the same first segment; the second row's sees a second one
    ev = _evidence([_trace(100.0, [(10.0, 50.0, first)]),
                    _trace(100.0005, [(9.6, 50.0, first), (70.0, 40.0, second)])])
    read = lambda name: readers.read_metric(ev, by_name[name].reader, by_name[name].args)
    state = 200 * SLOT * 2
    assert read("ssm.state_bytes_share") == state / (state + 6_000_000_000 + 2_000_000)
    # a program without the attributes (the parent): nothing to read, and no error
    bare = _evidence([_trace(100.0, [(10.0, 50.0, {"forwards": 8})])],
                     {"window_s": 1.0, "busy_s": 1.0, "ops": {"fusion.1 bf16[8,4096] fusion": 1.0}})
    for name in NEW - {"kernel.ssm_busy_share"}:
        assert readers.read_metric(bare, by_name[name].reader, by_name[name].args) is None
    assert readers.read_metric(bare, by_name["kernel.ssm_busy_share"].reader,
                               by_name["kernel.ssm_busy_share"].args) == 0.0


def test_both_roofline_readers_read_100_exactly_at_the_peaks():
    """Ten segments back to back over one second of wall. The state kernel is
    busy for exactly the time the HBM needs for the calls' bytes, and so is
    the experts' kernel: both read 100; at twice the time, 50."""
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    calls, touched = 10_000, 7_000
    segments = [(100.0 * i, 100.0, _segment(calls=calls // 10, slots=0, tokens=0, touched=touched // 10, seq=i))
                for i in range(10)]
    ssm_s = calls * SLOT * 2 / 819e9
    moe_s = touched * EXPERT / 819e9
    for stretch in (1, 2):
        device = {"window_s": 2.0, "busy_s": 1.9, "ops": {
            "ssm_window.61 (tuple) custom-call": 2.0 * ssm_s * stretch * 0.6,
            "ssm_window.62 (tuple) custom-call": 2.0 * ssm_s * stretch * 0.4,
            "routed_experts.60 f32[64,1024] custom-call": 2.0 * moe_s * stretch,
            "ragged_paged_attention.3 bf16[8,8,2,16,128] custom-call": 0.1,  # not theirs
            "fusion.1 bf16[8,4096] fusion": 0.5,
        }}
        ev = _evidence([_trace(50.0, segments)], device)
        for name in ("kernel.ssm_window_roofline", "kernel.routed_experts_roofline"):
            got = readers.read_metric(ev, by_name[name].reader, by_name[name].args)
            assert abs(got - 100.0 / stretch) < 1e-9, (name, got)
    # the operations (33.5 MFLOP a call) need a sixtieth of the bytes' time: the bytes bind
    sys.path.insert(0, os.path.join(CHIP_DIR, "reader_files"))
    import ssm_roofline

    n_bytes, n_ops = ssm_roofline._state_call_cost(_config(), calls * SLOT * 2)
    assert n_bytes == calls * SLOT * 2 and n_ops == calls * 2 * 16 * 128 * 64 * 128
    assert n_ops / 197e12 < n_bytes / 819e9 / 50


def test_the_rehearsed_cell_is_correct_and_its_control_is_not():
    """``REHEARSE=1``: the cell from its committed files through ``run.py
    --rehearse-cpu`` (the served path, POST /plan, interpreted kernels) reads
    ``correct``; the comparison's control (the state moved by the window, not
    by what the row kept) reads not ``correct`` (~3 minutes)."""
    if not os.environ.get("REHEARSE"):
        pytest.skip("REHEARSE=1 runs the rehearsed cell (minutes)")
    run = [sys.executable, os.path.join(CHIP_DIR, "run.py"), "--workload", CELL, "--rehearse-cpu",
           "--seed", str(2**31 + 4801), "--seconds", "6", "--trace", "1"]
    out = subprocess.run(run, cwd=REPO, capture_output=True, text=True, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"], out.stdout[-2000:]
    assert {"ssm.state_bytes_share", "engine.prefix_state_miss_share"} | SHARED - {"kernel.moe_busy_share"} <= set(
        line["metrics"])
    sys.path.insert(0, REPO)
    import dataclasses

    import jax

    import reference
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("nemotron_h")
    cfg = block.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    block.CONTROLS["state_moves_by_the_window"] = True
    try:
        wrong = reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 4801, interpret=True,
            page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3)
    finally:
        block.CONTROLS["state_moves_by_the_window"] = False
    assert not wrong["ok"], wrong
