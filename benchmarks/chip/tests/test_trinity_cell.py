"""The files PR 36 added for ``trinity-mini.distinct-closed``: the cell's
spec loads and its metrics find their readers, the new reader on a recorded
pair of ``/metrics`` scrapes, and the configuration file's parameter line
against the program's own count. (The rehearsed run of the whole cell is
``test_rehearsal.py::test_every_committed_cell``.) Not a device number."""

import json
import os
import re
import sys

import readers
import spec
from conftest import CHIP_DIR, REPO

CELL = "trinity-mini.distinct-closed"
NEW = {"moe.routed_bytes_share", "moe.touched_per_sparse_layer", "moe.load_max_over_mean"}


def test_the_cell_loads_and_its_metrics_find_their_readers():
    cell = spec.load_cell(CELL, REPO)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "trinity-mini", "distinct-closed")
    assert cell.config["module"] == "afmoe" and spec.block_file("afmoe").endswith("models/afmoe.py")
    found = readers.vocabulary()
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    for m in cell.per_layer:
        readers.reader_named(m.reader, found)
    # PR 33's three list Mellum's cell alone (PERF.md Open questions)
    assert not {"moe.experts_touched_share", "attn.rows_past_window_share"} & set(by_name)
    assert by_name["moe.load_max_over_mean"].reader == "counter_load_max_over_mean"
    assert {m.name for m in cell.end_to_end} == {"plans_per_s", "plan_p50_ms", "plan_p80_ms", "setup_s"}
    other = spec.load_cell("mellum2-12b-a2.5b.distinct-closed", REPO)
    assert not NEW & {m.name for m in other.per_layer}


def _scrape(counts):
    return {f'mcpx_engine_moe_expert_tokens_total{{expert="{e}"}}': float(n) for e, n in counts.items()}


def test_load_max_over_mean_on_a_recorded_pair():
    before = {**_scrape({0: 10, 1: 10, 2: 0, 4: 0}), "mcpx_engine_compiles_total": 17.0}
    after = {**_scrape({0: 30, 1: 20, 2: 0, 3: 10, 4: 0}), "mcpx_engine_compiles_total": 17.0}
    ev = readers.Evidence([], [], {"/metrics": before}, {"/metrics": after}, None, None)
    args = {"endpoint": "/metrics", "counter": "mcpx_engine_moe_expert_tokens_total"}
    # deltas 20, 10, 0, 10, 0 (expert 3 had no sample before; 2 and 4, which the program made
    # at 0 and no token chose, count in the mean): the busiest over the mean of 8
    assert readers.read_metric(ev, "counter_load_max_over_mean", args) == 2.5
    even = readers.Evidence([], [], {"/metrics": {}}, {"/metrics": _scrape({e: 5 for e in range(128)})}, None, None)
    assert readers.read_metric(even, "counter_load_max_over_mean", args) == 1.0
    # nothing routed in the window, a dense block, an endpoint not fetched: nothing to read
    for b, a in ((after, after), ({}, {"mcpx_engine_compiles_total": 1.0}), (None, None)):
        quiet = readers.Evidence([], [], {"/metrics": b} if b is not None else {},
                                 {"/metrics": a} if a is not None else {}, None, None)
        assert readers.read_metric(quiet, "counter_load_max_over_mean", args) is None


def test_the_files_parameter_line_is_the_programs_count():
    sys.path.insert(0, REPO)
    with open(os.path.join(CHIP_DIR, "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    cfg = spec.load_block("afmoe").model_config(spec.model_keys(config), 3072)
    held, read = (float(x) for x in re.findall(r"([\d.]+) B", config["params"]))
    assert round(cfg.n_params / 1e9, 3) == held and round(cfg.n_active_params / 1e9, 3) == read
    assert f"{cfg.n_params * 2 / 1e9:.2f} GB" in config["params"]
    assert "5,177,414,400" in config["reduced"]["num_hidden_layers"] and cfg.n_params == 5_177_414_400
