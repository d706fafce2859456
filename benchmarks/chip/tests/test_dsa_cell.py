"""The files PR 44 added for ``deepseek-v3.2-exp.catalogue-closed``: the
cell's spec loads and its metrics find their readers; the configuration file
keeps every published width; the six new metric files on a recorded set of
the new ``engine.segment`` attributes; the two roofline readers against a hand
count on a synthetic trace, pinned at exactly 100 at the chip's peaks; and
(``REHEARSE=1``) the rehearsed child. Not a device number."""

import json
import os

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "deepseek-v3.2-exp.catalogue-closed"
NEW = {"attn.selected_share", "attn.index_tok_per_call", "attn.index_bytes_share",
       "kernel.dsa_busy_share", "kernel.lightning_indexer_roofline",
       "kernel.ragged_paged_attention_selected_roofline"}
INDEX, SELECTED = "lightning_indexer", "ragged_paged_attention_selected"


def _config():
    with open(os.path.join(CHIP_DIR, "configs", "deepseek-v3.2-exp.json")) as f:
        return json.load(f)


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "deepseek-v3.2-exp", "catalogue-closed")
    assert cell.config["module"] == "dsa" and spec.block_file("dsa").endswith("models/dsa.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    assert by_name["kernel.lightning_indexer_roofline"].reader == "index_roofline"
    assert by_name["kernel.ragged_paged_attention_selected_roofline"].reader == "selected_roofline"
    assert by_name["kernel.dsa_busy_share"].args == {"regex": f"{INDEX}|{SELECTED}"}
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    bm = spec.load_benchmark(REPO)
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "plans_per_s"
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not NEW & {m.name for m in spec.load_cell(other, REPO).per_layer}
    # the planner is shown the whole registry: the shortlist is the traffic's registry
    assert cell.config["mcpx"]["planner"]["shortlist_top_k"] == cell.traffic["registry_services"] == 1000
    assert cell.traffic["clients"] == "slab_rows" and cell.traffic["intents"] == "distinct"
    assert cell.config["max_pages_per_seq"] * 16 >= cell.config["warmup_max_len"] + 48
    for key in ("decode_steps_per_tick", "steps_per_dispatch"):
        assert any(d.startswith("mcpx.engine.") and key in d.split(":")[0]
                   for d in cell.config["departures"]), key
    # the accepted attention share reads the selecting kernel; the latent cell's own does not
    import re
    with open(os.path.join(CHIP_DIR, "metrics", "kernel.attn_busy_share.json")) as f:
        assert re.search(json.load(f)["args"]["regex"], SELECTED)
    with open(os.path.join(CHIP_DIR, "metrics", "kernel.mla_busy_share.json")) as f:
        mla = json.load(f)["args"]["regex"]
    assert not re.search(mla, SELECTED) and not re.search(mla, INDEX)


def test_the_configuration_file_states_the_cut_and_the_deployment():
    config = _config()
    assert config["n_routed_experts_published"] == 256 and config["n_routed_experts"] == 16
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) == (6, 1)
    no_width = ("_dim", "_rank", "hidden_size", "intermediate_size", "num_experts_per_tok")
    assert not [k for k in config["reduced"] if any(w in k for w in no_width)]
    assert {"indexer", "dtype", "rope_pairing", "yarn_in_the_scale", "deployment", "router_bias"} <= set(config["assumed"])
    assert "16 chips" in config["assumed"]["deployment"]
    assert "10.80 GB" in config["params"] and "5.399 B" in config["params"]
    said = " ".join(config["departures"])
    for what in ("FP8", "multi-token-prediction", "MASKED", "1/16"):
        assert what in said, what


def _trace(started_at, segments):
    tree = [{"span_id": "root", "parent_id": None, "name": "plan", "start_ms": 0.0,
             "duration_ms": 1000.0, "attrs": {}}]
    for i, (start, dur, attrs) in enumerate(segments):
        tree.append({"span_id": f"s{i}", "parent_id": "root", "name": "engine.segment",
                     "start_ms": start, "duration_ms": dur, "attrs": attrs})
    return {"trace_id": "t", "started_at": started_at, "tree": tree}


def _segment(ctx, calls, seq=0, topk=2048):
    per_call = ctx // max(calls, 1)
    sel = calls * min(per_call, topk)
    scored = ctx if per_call > topk else 0
    return {"seq": seq, "forwards": 8, "attn_ctx_tokens": ctx, "attn_row_calls": calls,
            "attn_sel_tokens": sel, "index_ctx_tokens": scored, "index_bytes_read": scored * 256,
            "kv_bytes_read": ctx * 1152, "weight_bytes_read": 4_000_000_000}


def _evidence(traces, device=None):
    return readers.Evidence([], traces, {}, {}, device, None, config=_config(), device_kind="TPU v5 lite")


def test_the_metric_files_read_a_recorded_set_of_the_new_attributes():
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    first = _segment(ctx=6900 * 240, calls=240)
    second = _segment(ctx=6900 * 96, calls=96, seq=1)
    ev = _evidence([_trace(100.0, [(10.0, 50.0, first)]),
                    _trace(100.0005, [(9.6, 50.0, first), (70.0, 40.0, second)])])
    read = lambda name: readers.read_metric(ev, by_name[name].reader, by_name[name].args)
    assert read("attn.selected_share") == 2048 / 6900
    assert read("attn.index_tok_per_call") == 6900.0
    assert read("attn.index_bytes_share") == 256 / (256 + 1152)
    # a row that holds no more than 2,048 tokens selects everything and scores nothing
    short = _evidence([_trace(100.0, [(10.0, 50.0, _segment(ctx=900 * 48, calls=48))])])
    short_read = lambda name: readers.read_metric(short, by_name[name].reader, by_name[name].args)
    assert short_read("attn.selected_share") == 1.0 and short_read("attn.index_tok_per_call") == 0.0
    # a program without the attributes (the parent): nothing to read, and no error
    bare = _evidence([_trace(100.0, [(10.0, 50.0, {"forwards": 8, "attn_ctx_tokens": 5, "attn_row_calls": 1,
                                                    "kv_bytes_read": 9})])])
    for name in NEW:
        assert readers.read_metric(bare, by_name[name].reader, by_name[name].args) is None


@pytest.mark.parametrize("reader, kernel, attr, row_bytes, row_ops", [
    ("index_roofline", INDEX, "index_ctx_tokens", 256, 2 * 64 * 128),
    ("selected_roofline", SELECTED, "attn_sel_tokens", 1152, 128 * (2 * 576 + 2 * 512)),
])
def test_a_roofline_reader_reads_what_a_hand_count_gives_and_100_at_the_peaks(reader, kernel, attr, row_bytes, row_ops):
    args = {"regex": kernel, "span": "engine.segment"}
    segments = [(100.0 * i, 100.0, _segment(ctx=6900 * 100, calls=100, seq=i)) for i in range(10)]
    keys = sum(s[2][attr] for s in segments)
    assert keys == (6900 if attr == "index_ctx_tokens" else 2048) * 1000
    least_s = max(keys * row_bytes / 819e9, keys * row_ops / 197e12)
    other = {"fusion.1 bf16[8,7168] fusion": 1.0, "ragged_paged_attention_latent.2 custom-call": 0.3,
             (INDEX if kernel == SELECTED else SELECTED) + ".5 custom-call": 0.2}
    # the kernel busy 5% of a 2-second slice, the segments one second of wall
    device = {"window_s": 2.0, "busy_s": 1.9, "ops": {f"{kernel}.7 bf16[8,8,128,512] custom-call": 0.06,
                                                        f"{kernel}.9 f32[4,64,8192] custom-call": 0.04, **other}}
    got = readers.read_metric(_evidence([_trace(50.0, segments)], device), reader, args)
    assert got == pytest.approx(100.0 * least_s / (0.10 / 2.0))
    # a kernel that took exactly the least time its useful work allows reads 100, whatever else ran
    at_peak = {"window_s": 1.0, "busy_s": 1.0, "ops": {f"{kernel}.1 custom-call": least_s, **other}}
    assert readers.read_metric(_evidence([_trace(0.0, segments)], at_peak), reader, args) == pytest.approx(100.0)
    slower = {**at_peak, "ops": {f"{kernel}.1 custom-call": 2 * least_s, **other}}
    assert readers.read_metric(_evidence([_trace(0.0, segments)], slower), reader, args) == pytest.approx(50.0)
    # no device trace (a rehearsal), no such kernel in it, no attribute (the parent): nothing to read
    assert readers.read_metric(_evidence([_trace(50.0, segments)]), reader, args) is None
    assert readers.read_metric(_evidence([_trace(50.0, segments)], {**device, "ops": other}), reader, args) is None
    assert readers.read_metric(_evidence([], device), reader, args) is None
    bare = [(0.0, 100.0, {"seq": 0, "forwards": 8, "attn_ctx_tokens": 9})]
    assert readers.read_metric(_evidence([_trace(0.0, bare)], device), reader, args) is None


def test_what_binds_each_kernel_at_one_query_a_call():
    """The index is bound by its keys' bytes (16,384 operations a 256-byte key);
    the absorbed attention at 128 heads sits AT the chip's ridge with one
    query a call (278,528 operations a 1,152-byte row: 1.414 ns against 1.407),
    and past it with more."""
    import importlib.util
    path = os.path.join(CHIP_DIR, "reader_files", "dsa_roofline.py")
    mod_spec = importlib.util.spec_from_file_location("dsa_roofline_t", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    n_bytes, n_ops = mod._index_call_cost(_config(), 1_000_000)
    assert n_bytes / 819e9 > 3 * n_ops / 197e12
    n_bytes, n_ops = mod._selected_call_cost(_config(), 1_000_000)
    assert 1.0 < (n_ops / 197e12) / (n_bytes / 819e9) < 1.01


@pytest.mark.skipif(os.environ.get("REHEARSE") != "1", reason="minutes; set REHEARSE=1")
def test_the_parent_fails_on_the_cell_at_once(tmp_path):
    """A program from before the block: the child exits with the block
    module's message, in seconds."""
    import subprocess
    import sys
    import time

    parent = tmp_path / "parent"
    parent.mkdir()
    subprocess.run(f"git archive 80e09387af668b63588a747b631a0034d83eaccd | tar -x -C {parent}",
                   shell=True, check=True, cwd=REPO)
    subprocess.run(["cp", os.path.join(REPO, "BENCHMARK.json"), str(parent)], check=True)
    subprocess.run(["cp", "-r", CHIP_DIR + "/.", str(parent / "benchmarks" / "chip")], check=True)
    t0 = time.time()
    r = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", CELL, "--seed", "1",
                        "--seconds", "5", "--trace", "0", "--rehearse-cpu"], cwd=parent,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and time.time() - t0 < 60
    assert "no learned index" in r.stderr + r.stdout
