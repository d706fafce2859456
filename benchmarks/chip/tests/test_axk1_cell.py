"""The files PR 42 added for ``a.x-k1.wide-shortlist-closed``: the cell's
spec loads and its metrics find their readers; the configuration file keeps
every published width; the five new metric files on a recorded set of the new
``engine.segment`` attributes; ``mla_roofline`` against a hand count on a
synthetic trace; and (``REHEARSE=1``) the rehearsed cell through ``child.py``.
Not a device number."""

import json
import os

import pytest

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "a.x-k1.wide-shortlist-closed"
NEW = {"kernel.mla_roofline_share", "kernel.mla_busy_share", "attn.ctx_tok_per_call",
       "attn.latent_bytes_share", "moe.held_assignment_share"}
KERNEL = "ragged_paged_attention_latent"


def _config():
    with open(os.path.join(CHIP_DIR, "configs", "a.x-k1.json")) as f:
        return json.load(f)


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "a.x-k1", "wide-shortlist-closed")
    assert cell.config["module"] == "mla" and spec.block_file("mla").endswith("models/mla.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    assert by_name["kernel.mla_roofline_share"].reader == "mla_roofline"
    assert by_name["kernel.mla_busy_share"].args == {"regex": KERNEL}
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    # each of the five lists this cell alone
    bm = spec.load_benchmark(REPO)
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not NEW & {m.name for m in spec.load_cell(other, REPO).per_layer}
    # the shortlist of 128 is the configuration's: the harness reads server settings only there
    assert cell.config["mcpx"]["planner"] == {"kind": "llm", "shortlist_top_k": 128}
    # every engine setting that shapes the schedule is named, with its reading, under departures
    engine = cell.config["mcpx"]["engine"]
    assert engine["decode_steps_per_tick"] * engine["steps_per_dispatch"] == 8  # one tick: no length to choose
    for key in ("batch_buckets", "decode_steps_per_tick", "steps_per_dispatch"):
        assert any(d.startswith("mcpx.engine.") and key in d.split(":")[0]
                   for d in cell.config["departures"]), key
    assert cell.traffic["clients"] == "slab_rows" and cell.traffic["intents"] == "distinct"
    assert "max_pages_per_seq" not in cell.config["reduced"] and cell.config["max_pages_per_seq"] == 128


def test_the_configuration_file_keeps_every_published_width():
    config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = None
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"A.X-K1"' in l)
    published = row["config"] if row else {
        "hidden_size": 7168, "num_attention_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    }
    changed = {k for k, v in published.items() if k not in config or config[k] != v}
    assert changed == ({"num_hidden_layers", "n_routed_experts", "vocab_size"} if row else set())
    if row:
        assert config["source"] == row["source_url"] and config["rope_scaling"] == published["rope_scaling"]
    assert config["n_routed_experts_published"] == 192 and config["n_routed_experts"] == 12
    bm = spec.load_benchmark(REPO)
    entry = next(c for c in bm["configs"] if c["name"] == "a.x-k1")
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)
    no_width = ("_dim", "_rank", "hidden_size", "intermediate_size", "num_experts_per_tok")
    assert not [k for k in entry["reduced"] if any(w in k for w in no_width)]
    assert {"topk_method", "dtype", "rope_pairing", "yarn_in_the_scale", "deployment"} <= set(config["assumed"])
    assert "16 chips" in config["assumed"]["deployment"] and "12" in config["assumed"]["deployment"]
    assert "10.53 GB" in config["params"] and "5.267 B" in config["params"]


def _trace(started_at, segments):
    """One /traces body holding the given engine.segment spans:
    (start_ms, duration_ms, attrs)."""
    tree = [{"span_id": "root", "parent_id": None, "name": "plan", "start_ms": 0.0,
             "duration_ms": 1000.0, "attrs": {}}]
    for i, (start, dur, attrs) in enumerate(segments):
        tree.append({"span_id": f"s{i}", "parent_id": "root", "name": "engine.segment",
                     "start_ms": start, "duration_ms": dur, "attrs": attrs})
    return {"trace_id": "t", "started_at": started_at, "tree": tree}


def _segment(ctx, calls, weights=4_000_000_000, assigned=6, routed=96, seq=0):
    return {"seq": seq, "forwards": 8, "attn_ctx_tokens": ctx, "attn_row_calls": calls,
            "kv_bytes_read": ctx * 1152, "weight_bytes_read": weights,
            "moe_assignments": assigned, "moe_tokens_routed": routed}


def _evidence(traces, device=None):
    return readers.Evidence([], traces, {}, {}, device, None, config=_config(),
                            device_kind="TPU v5 lite")


def test_the_metric_files_read_a_recorded_set_of_the_new_attributes():
    cell = spec.load_cell(CELL, REPO)
    by_name = {m.name: m for m in cell.per_layer}
    # two rows' traces see the same first segment; the second row's sees a second one
    first = _segment(ctx=460_800, calls=512, assigned=30, routed=448)
    second = _segment(ctx=230_400, calls=256, weights=2_000_000_000, assigned=12, routed=224, seq=1)
    ev = _evidence([_trace(100.0, [(10.0, 50.0, first)]),
                    _trace(100.0005, [(9.6, 50.0, first), (70.0, 40.0, second)])])
    read = lambda name: readers.read_metric(ev, by_name[name].reader, by_name[name].args)
    assert read("attn.ctx_tok_per_call") == (460_800 + 230_400) / (512 + 256) == 900.0
    kv = (460_800 + 230_400) * 1152
    assert read("attn.latent_bytes_share") == kv / (kv + 6_000_000_000)
    assert read("moe.held_assignment_share") == (30 + 12) / (448 + 224) == 0.0625
    # a program without the attributes: nothing to read, and no error
    bare = _evidence([_trace(100.0, [(10.0, 50.0, {"forwards": 8})])])
    for name in NEW:
        assert readers.read_metric(bare, by_name[name].reader, by_name[name].args) is None


def test_mla_roofline_reads_what_a_hand_count_gives():
    """Ten segments back to back over one second of wall read 9,000,000
    context tokens; the kernel is busy 5% of a 2-second profiled slice."""
    args = {"regex": KERNEL, "span": "engine.segment"}
    segments = [(100.0 * i, 100.0, _segment(ctx=900_000, calls=1000, seq=i)) for i in range(10)]
    device = {"window_s": 2.0, "busy_s": 1.9, "ops": {
        f"{KERNEL}.7 bf16[8,8,64,512] custom-call": 0.06,
        f"{KERNEL}.9 bf16[4,1024,64,512] custom-call": 0.04,
        "ragged_paged_attention.3 bf16[8,1,16,1,128] custom-call": 0.5,  # the other kernel: not this one's
        "fusion.1 bf16[8,7168] fusion": 1.0,
    }}
    ev = _evidence([_trace(50.0, segments)], device)
    n_bytes = 9_000_000 * (512 + 64) * 2
    n_ops = 9_000_000 * 64 * (2 * 576 + 2 * 512)
    least_s = max(n_bytes / 819e9, n_ops / 197e12)  # the bytes bind: 12.66 ms against 6.36
    assert least_s == n_bytes / 819e9
    want = 100.0 * (least_s / 1.0) / (0.10 / 2.0)
    got = readers.read_metric(ev, "mla_roofline", args)
    assert got == pytest.approx(want) and 25.0 < got < 25.5
    busy = readers.read_metric(ev, "device_op_share", {"regex": KERNEL})
    assert busy == pytest.approx(5.0)
    # the accepted share counts both kernels
    assert readers.read_metric(ev, "device_op_share", {"regex": "ragged_paged_attention"}) == pytest.approx(30.0)
    # no device trace (a rehearsal), no kernel in it, or no attribute: nothing to read
    assert readers.read_metric(_evidence([_trace(50.0, segments)]), "mla_roofline", args) is None
    quiet = {**device, "ops": {"fusion.1 bf16[8,7168] fusion": 1.0}}
    assert readers.read_metric(_evidence([_trace(50.0, segments)], quiet), "mla_roofline", args) is None
    assert readers.read_metric(_evidence([], device), "mla_roofline", args) is None


def test_mla_roofline_cannot_pass_100_while_the_kernel_runs_at_the_chips_peaks():
    """A kernel that took exactly the least time its useful bytes allow reads
    100; taking any longer, or moving the pools' padding too, reads less."""
    args = {"regex": KERNEL, "span": "engine.segment"}
    ctx = 5_000_000
    least_s = ctx * 1152 / 819e9
    segments = [(0.0, 500.0, _segment(ctx=ctx, calls=5000)), (500.0, 500.0, _segment(ctx=0, calls=0, seq=1))]
    at_peak = {"window_s": 1.0, "busy_s": 1.0, "ops": {f"{KERNEL}.1 bf16[8,8,64,512] custom-call": least_s}}
    assert readers.read_metric(_evidence([_trace(0.0, segments)], at_peak), "mla_roofline", args) == pytest.approx(100.0)
    padded = {**at_peak, "ops": {f"{KERNEL}.1 bf16[8,8,64,512] custom-call": least_s * 640 / 576}}
    assert readers.read_metric(_evidence([_trace(0.0, segments)], padded), "mla_roofline", args) == pytest.approx(90.0)


@pytest.mark.skipif(os.environ.get("REHEARSE") != "1", reason="minutes; set REHEARSE=1")
def test_the_rehearsed_cell_runs_through_the_child():
    from test_rehearsal import rehearse

    line = rehearse(REPO, CELL, trace=1, seconds=20)
    assert {"attn.ctx_tok_per_call", "attn.latent_bytes_share", "moe.held_assignment_share"} <= set(line["metrics"])
    assert 850 < line["metrics"]["attn.ctx_tok_per_call"]["value"] < 1000
    assert not {"kernel.mla_roofline_share", "kernel.mla_busy_share"} & set(line["metrics"])
