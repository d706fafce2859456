"""The files PR 56 added for ``lfm2-24b-a2b.distinct-closed``: the cell's spec
loads and its metrics find their readers; the configuration file keeps every
published width and states its cut and its deployment; the three new metric
files on a recorded set of the new ``engine.segment`` attributes and counters;
the routed experts' roofline reader's count of an expert's bytes at three
matrices of 2,048 x 1,536; and (``REHEARSE=1``) the rehearsed cell through
``run.py``. Counts are compared with ``>=`` and none is pinned. Not a device
number."""

import json
import os
import subprocess
import sys

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "lfm2-24b-a2b.distinct-closed"
NEW = {"conv.mixer_bytes_share", "conv.tail_bytes_share", "engine.prefix_hit_row_share"}
# the sparse and state cells' metrics that list this cell too
SHARED = {"engine.prefix_state_hit_share", "engine.prefix_state_miss_share", "kernel.moe_busy_share",
          "moe.kernel_step_share", "moe.routed_bytes_share", "moe.touched_per_sparse_layer",
          "moe.load_max_over_mean", "moe.prefill_rows_per_assignment", "moe.experts_touched_share",
          "moe.tok_per_touched_expert", "startup.weights_s", "kernel.routed_experts_roofline"}
EXPERT = 3 * 2048 * 1536 * 2  # one routed expert's three matrices, bfloat16


def _config():
    with open(os.path.join(CHIP_DIR, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "lfm2-24b-a2b", "distinct-closed")
    assert cell.config["module"] == "lfm2" and spec.block_file("lfm2").endswith("models/lfm2.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW | SHARED <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    assert by_name["conv.tail_bytes_share"].reader == "span_attr_share_of"
    assert by_name["engine.prefix_hit_row_share"].reader == "counter_delta_ratio"
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    bm = spec.load_benchmark(REPO)
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        elif m["name"] in SHARED:
            assert m["workloads"][-1] == CELL and len(m["workloads"]) >= 2
    assert len(bm["workloads"]) >= 10 and len(bm["configs"]) >= 10
    for w in bm["workloads"]:  # every cell still loads
        spec.load_cell(w["name"], REPO)
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not NEW & {m.name for m in spec.load_cell(other, REPO).per_layer}
    # the traffic file is the other distinct-closed cells', unedited; no tick is pinned
    assert cell.traffic["clients"] == "slab_rows" and cell.traffic["intents"] == "distinct"
    assert cell.config["mcpx"] == {"model": {"vocab": "bpe"}, "planner": {"kind": "llm"},
                                   "engine": {"warmup_compile": True, "temperature": 0.0}}


def test_the_configuration_file_keeps_every_published_width_and_states_its_cut():
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog) if '"LFM2-24B-A2B"' in line)
        changed = {k for k, v in row["config"].items() if k not in config or config[k] != v}
        assert changed == {"num_hidden_layers", "layer_types", "vocab_size"}
        assert config["source"] == row["source_url"]
        assert row["config"]["layer_types"][:10] == config["layer_types"]
    bm = spec.load_benchmark(REPO)
    entry = next(c for c in bm["configs"] if c["name"] == "lfm2-24b-a2b")
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)
    no_width = ("_dim", "_rank", "hidden_size", "intermediate_size", "latent_size", "state_size",
                "num_experts_per_tok", "expand")
    assert not [k for k in entry["reduced"] if any(w in k for w in no_width)]
    assert {"tie_word_embeddings", "in_proj_thirds", "conv", "qk_norm_then_rope", "router", "tail_dtype",
            "dtype"} <= set(config["assumed"])
    cut = config["reduced"]["num_hidden_layers"]
    assert "FIRST OF FOUR PIPELINE STAGES" in cut and "every one of the 64 experts" in cut
    assert "23.84 B" in cut and "2.33 B" in cut and "5,139,163,904" in cut
    assert "10.28 GB" in config["params"] and "5.139 B" in config["params"]
    sys.path.insert(0, REPO)
    cfg = spec.load_block("lfm2").model_config(spec.model_keys(config), 3072)
    assert cfg.n_params == 5_139_163_904 and cfg.n_active_params == 609_315_584
    assert round(cfg.n_active_params / 1e9, 3) == 0.609 and "0.609 B" in config["params"]


def _trace(started_at, segments):
    tree = [{"span_id": "root", "parent_id": None, "name": "plan", "start_ms": 0.0,
             "duration_ms": 1000.0, "attrs": {}}]
    for i, (start, dur, attrs) in enumerate(segments):
        tree.append({"span_id": f"s{i}", "parent_id": "root", "name": "engine.segment",
                     "start_ms": start, "duration_ms": dur, "attrs": attrs})
    return {"trace_id": "t", "started_at": started_at, "tree": tree}


def test_the_metric_files_read_a_recorded_set_of_the_new_attributes():
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    seg = lambda seq, conv, tail, weights, kv, touched: {
        "seq": seq, "forwards": 8, "conv_weight_bytes": conv, "conv_tail_bytes": tail,
        "weight_bytes_read": weights, "kv_bytes_read": kv, "moe_experts_touched": touched}
    # two segments, each seen from two of its rows' traces (counted once a segment)
    a, b = seg(0, 2_000, 100, 16_000, 900, 700), seg(1, 2_000, 300, 24_000, 700, 900)
    traces = [_trace(10.0, [(0.0, 50.0, a), (100.0, 50.0, b)]), _trace(10.0, [(0.0, 50.0, a)])]
    before = {"mcpx_kv_prefix_hits_total": 4.0, "mcpx_kv_prefix_misses_total": 30.0,
              'mcpx_engine_prefix_state_total{event="hit"}': 4.0,
              'mcpx_engine_prefix_state_total{event="miss"}': 0.0}
    after = {"mcpx_kv_prefix_hits_total": 10.0, "mcpx_kv_prefix_misses_total": 72.0,
             'mcpx_engine_prefix_state_total{event="hit"}': 10.0,
             'mcpx_engine_prefix_state_total{event="miss"}': 0.0}
    ev = readers.Evidence([], traces, {"/metrics": before}, {"/metrics": after}, None, None,
                          config=_config(), device_kind="TPU v5 lite")
    read = lambda name: readers.read_metric(ev, by_name[name].reader, by_name[name].args)
    assert read("conv.mixer_bytes_share") == pytest.approx(4_000 / 40_000)
    assert read("conv.tail_bytes_share") == pytest.approx(400 / (400 + 40_000 + 1_600))
    assert read("engine.prefix_hit_row_share") == pytest.approx(6 / 48)
    assert read("engine.prefix_state_hit_share") == 1.0 and read("engine.prefix_state_miss_share") == 0.0
    # a program from before this block writes none of it: the line leaves the metrics out
    old = readers.Evidence([], [_trace(10.0, [(0.0, 50.0, {"seq": 0, "forwards": 8})])], {"/metrics": {}},
                           {"/metrics": {}}, None, None, config=_config(), device_kind="TPU v5 lite")
    for name in NEW:
        assert readers.read_metric(old, by_name[name].reader, by_name[name].args) is None


def test_the_roofline_readers_count_of_an_experts_bytes_is_three_matrices():
    moe = spec.import_file(os.path.join(CHIP_DIR, "reader_files", "moe_roofline.py"), "chip_reader_t_")
    assert moe._expert_bytes(_config()) == EXPERT == 18_874_368


@pytest.mark.skipif(not os.environ.get("REHEARSE"), reason="REHEARSE=1 runs the rehearsed cell (~3 min)")
def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "run.py"), "--workload", CELL, "--seed", str(2**31 + 56),
         "--seconds", "8", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] >= 1
    for name in ("conv.mixer_bytes_share", "conv.tail_bytes_share", "engine.prefix_hit_row_share"):
        assert name in line["metrics"], sorted(line["metrics"])
