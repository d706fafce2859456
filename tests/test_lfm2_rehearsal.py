"""The rehearsal child of ``lfm2-24b-a2b.distinct-closed`` (block module
``lfm2``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_lfm2_block.py``. The child
(``serve``), the ``FED*`` lists and everything the children share are
``tests/chip_rehearsal.py``'s. CPU, interpreted kernels: correctness readings,
not device numbers.
"""

import dataclasses
import math
import os
import sys

import pytest

import mcpx.models.gemma.model as model
from tests.chip_rehearsal import (
    CHIP_DIR,
    CONV_CELL,
    FED_CONV,
    PLANNER_SHORTLIST,
    _segments,
    _segments_once,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_lfm2_r", os.path.join(CHIP_DIR, "models", "lfm2.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_lfm2_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_conv(tmp_path_factory):
    # The cell's own shortlist (the planner's default) and the warm-up's first bucket alone:
    # as ``tests/test_afmoe_rehearsal.py::served_mixed``.
    return serve(CONV_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=PLANNER_SHORTLIST)


@pytest.mark.parametrize("metric", FED_CONV, ids=[m["name"] for m in FED_CONV])
def test_the_conv_block_feeds_its_metrics(served_conv, metric):
    """Its three own metrics, and the sparse and state cells' that list it too:
    its routed layers write what every sparse block's do."""
    assert {m["name"] for m in FED_CONV} == {
        "conv.mixer_bytes_share", "conv.tail_bytes_share", "engine.prefix_hit_row_share",
        "engine.prefix_state_hit_share", "engine.prefix_state_miss_share", "moe.experts_touched_share",
        "moe.tok_per_touched_expert", "moe.load_max_over_mean", "moe.touched_per_sparse_layer",
        "moe.prefill_rows_per_assignment", "moe.routed_bytes_share", "moe.kernel_step_share"}
    v = served_conv["read"](metric["reader"], metric["args"])
    counters = served_conv["ev"].counters_after["/metrics"]
    hits = counters['mcpx_engine_prefix_state_total{event="hit"}']
    if metric["name"] == "engine.prefix_state_hit_share":
        # a share of hits + misses: this model has no miss, so 1.0 wherever a row hit in the window
        # (five distinct prompts may share no page: then there is nothing to divide)
        assert v == (1.0 if v is not None else None) and (v is not None or hits == served_conv["hits_before"])
        return
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_miss_share":
        assert v == 0.0  # a page's tail is always there: no row that found pages prefilled whole
    elif metric["name"] == "engine.prefix_hit_row_share":
        assert 0.0 <= v <= 1.0
    elif metric["name"] == "moe.kernel_step_share":
        assert v == 1.0
    elif metric["unit"] == "ratio" and metric["name"] != "moe.load_max_over_mean":
        assert 0 < v < 1


def test_the_conv_blocks_attributes_count_calls_tails_and_weights(served_conv):
    """At the rehearsal size: 8 short convolutions and 2 attention layers among
    10, 8 routed layers, a tail of 2 x 256 float32 a row a layer beside a
    pending window of 8. Every new span attribute, counter, ``pallas.paths``
    entry and /healthz field the cell's metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("lfm2", CHIP_DIR).rehearsal_config(3072)
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_sparse_layers, cfg.kv_pack) == (8, 2, 8, 2)
    segments = _segments(served_conv)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["conv_row_calls"] % 8 == 0 and 0 < a["conv_row_calls"] <= a["forwards"] * 8 * 8
        assert a["conv_tail_bytes"] == a["conv_row_calls"] * (2 + 8) * 256 * 4 * 2  # float32, read and written
        assert a["conv_row_calls"] <= a["conv_tokens"] <= a["conv_slots"] <= a["conv_row_calls"] * 8
        assert a["attn_row_calls"] * 4 == a["conv_row_calls"]  # TWO attention layers
        assert a["moe_layer_forwards"] == a["forwards"] * 8
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (cfg.kv_bytes_per_token // 2)
        assert 0 < a["conv_weight_bytes"] < a["weight_bytes_read"] and a["conv_weight_bytes"] % a["forwards"] == 0
        assert "conv_prefill_tokens" in a and "ssm_row_calls" not in a and "ssm_state_bytes" not in a
    once = _segments_once(served_conv)
    profile = served_conv["health"]["engine_queue"]["worker_profile"]
    for attr in ("conv_row_calls", "conv_tail_bytes", "conv_slots", "conv_tokens", "conv_weight_bytes", "conv_prefill_tokens"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in once) > 0, attr
    # every admitted prompt's own tokens went through each convolution once, and each page it
    # filled to its last slot got its tail: the rest were a matched page's
    prefills = [sp for tr in served_conv["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills
    for sp in prefills:
        a = sp["attrs"]
        assert a["conv_prefill_tokens"] % 8 == 0 and a["conv_prefill_tokens"] > 0
        own = a["conv_prefill_tokens"] // 8
        assert a["tail_pages_written"] == (a["prefix_matched_tokens"] + own) // 16 - a["prefix_matched_tokens"] // 16
    # hits and no miss: the lifetime sums and the counters agree
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_hit", "prefix_state_miss"}
    metrics = served_conv["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="hit"}'] == profile["prefix_state_hit"] >= 0
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] == 0
    assert profile["prefix_state_hit"] == metrics["mcpx_kv_prefix_hits_total"]  # every matched row is a state hit
    # the kernel paths the cell's ``correct`` asks for; no state kernel exists
    assert served_conv["kernel_paths"] == {"decode": 1, "prefill": 0}
    assert "ssm" not in served_conv["paths"] and served_conv["paths"]["prefill"]["engaged"]
    assert served_conv["paths"]["prefill"]["reason"] is None
    # the state pool's bytes where the weights' are
    pool = served_conv["health"]["engine_queue"]["state_pool"]
    n_pages = pool["page_tails_bytes"] // (8 * 2 * 256 * 4)
    assert pool["slots"] == 8 and n_pages > 8 * 16 and pool["bytes"] == pool["page_tails_bytes"] + 8 * 8 * 10 * 256 * 4 + 8 * 4
    model = served_conv["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    assert model["params_held"] - model["params_active_per_token"] == 8 * 6 * 3 * 256 * 128


# -------------------------------------- the comparison that decides ``correct``
def _compare(block, reference, cfg, control="", seed=5, **switch):
    params = params_of(cfg)
    sound = dict(block.CONTROLS)
    block.CONTROLS.update(switch)
    try:
        return reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), one_device(), seed=seed, interpret=True,
            page_size=16, rows=4, pages_per_row=8, prefill_len=64, n_decode=4, control=control,
        )
    finally:
        block.CONTROLS.update(sound)


# Stated float32, the step (whole prefill, a suffix prefill from a page's tail
# for every second row, decode windows with rejected slots, the interpreted
# kernel on packed heads, the routed experts' kernel) reads 4e-6 of a logit's
# spread at its worst position: accumulation order alone. 1e-4 is 25 times
# that and 100 times under what the same step reads with bfloat16 where
# float32 is stated (1.2e-2): the next precision below does not pass.
F32_TOL = 1e-4


def test_the_step_matches_the_reference_to_float32_rounding(block, reference):
    out = _compare(block, reference, dataclasses.replace(block.rehearsal_config(512), dtype="float32"))
    assert out["rms_rel_err"] < F32_TOL and out["max_rel_err"] < 4 * F32_TOL, out
    assert out["positions"] == 20 and out["rows"] == 4


def test_bfloat16_where_float32_is_stated_fails_that_limit(block, reference):
    out = _compare(block, reference, block.rehearsal_config(512))
    assert out["rms_rel_err"] > 50 * F32_TOL, out
    # ... and is the served precision: correct by ``reference.tol`` (0.012 against 0.02 here, at
    # 256 wide; with the conv mixers on the plain bfloat16 recipe it read 0.025-0.030: ssm.py)
    assert out["ok"], out


@pytest.mark.parametrize("switch", [
    {"tail_at_hit": False}, {"state_moves_by_the_window": True}, {"follow_step_routing": False},
], ids=["a_hit_starts_from_zeros", "a_rejected_slots_u_is_kept", "the_reference_keeps_its_own_top_k"])
def test_a_step_that_breaks_the_rule_fails_the_comparison(block, reference, switch):
    """CONTROL: in float32, where a sound step reads 4e-6, a step that drops
    the tail at a hit, or keeps a rejected slot's ``u``, reads not correct by
    the routing check (NaN logits) or by orders of magnitude. (The third
    switch is the routing record's: in float32 the two sides choose alike, so
    it must NOT fail: it is here to show the switch itself is no fault.)"""
    cfg = dataclasses.replace(block.rehearsal_config(512), dtype="float32")
    out = _compare(block, reference, cfg, **switch)
    if "follow_step_routing" in switch:
        assert out["rms_rel_err"] < F32_TOL, out
    else:
        assert not out["ok"] and out["rms_rel_err"] > 1e3 * F32_TOL, out


def test_the_int8_control_fails_the_comparison(block, reference):
    out = _compare(block, reference, block.rehearsal_config(512), control="int8-weights")
    assert not out["ok"], out
