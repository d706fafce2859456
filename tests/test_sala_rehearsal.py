"""The rehearsal child of ``minicpm-sala.catalogue-2k-closed`` (block module
``sala``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_sala_block.py``. The child
(``serve``), the ``FED*`` lists and everything the children share are
``tests/chip_rehearsal.py``'s. CPU, interpreted kernels: correctness readings,
not device numbers.
"""

import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from tests.chip_rehearsal import (
    BLOCK_CELL,
    CHIP_DIR,
    FED_BLOCK,
    INDEX_CELL,
    LATENT_CELL,
    METRICS,
    REPO,
    _CELLS_OF,
    _segments,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_sala_r", os.path.join(CHIP_DIR, "models", "sala.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_sala_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_block(tmp_path_factory):
    # The cell's own shortlist (1,500 >= the 120 services served here: a
    # catalogue of ~800 tokens, past the rehearsal block's 256-token buckets and
    # the 256 tokens of the 4 blocks a query keeps) with a warm-up the CPU can afford: its
    # first bucket alone, the chunks' and the suffixes' compiled by the plans that take them.
    return serve(BLOCK_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=1500)


@pytest.mark.parametrize("metric", FED_BLOCK, ids=[m["name"] for m in FED_BLOCK])
def test_the_block_selecting_cell_feeds_its_metrics(served_block, metric):
    """Its own metrics, and the index cells' that list it too: its sparse
    layers write what a selecting block's do."""
    assert {m["name"] for m in FED_BLOCK} == {
        "attn.selected_share", "attn.index_bytes_share", "attn.slots_per_row_call", "linear.state_bytes_share",
        "attn.gathered_pages_share", "engine.prefix_state_hit_share"}
    v = served_block["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_hit_share":
        assert v == 1.0  # every plan's prompt starts with the declared head
    elif metric["name"] == "attn.slots_per_row_call":
        assert 1 <= v <= 8
    elif metric["name"] in ("attn.selected_share", "attn.gathered_pages_share"):
        assert 0.2 < v < 0.45  # 4 blocks of 64 of a ~850-token context
    else:
        assert 0 < v < 1


def test_the_block_selecting_cells_attributes_count_pages_fetched_and_the_head_state(served_block):
    """At the rehearsal size: 6 linear layers of 4 heads x 32 x 32 float32 a
    row, 2 sparse layers on 2 KV heads that keep 4 blocks of 64; the catalogue
    of 120 services a head of ~800 tokens built in chunks of 256, its end state
    handed to every plan. Every span attribute, counter and ``pallas.paths``
    entry the cell's metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("sala", CHIP_DIR).rehearsal_config(3072)
    segments = _segments(served_block)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] > 0 and a["attn_sel_tokens"] == a["attn_row_calls"] * 256
        assert a["attn_query_slots"] >= a["attn_row_calls"]
        assert 0 < a["attn_gathered_pages"] < a["attn_ctx_pages"]
        assert a["kv_bytes_read"] == a["attn_gathered_pages"] * 16 * 32 * 2 * 2  # pages fetched: keys and values
        assert a["index_bytes_read"] > 0 and a["index_bytes_read"] % (32 * 4) == 0  # float32 rows of a page's key sum
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2 and a["ssm_row_calls"] % 6 == 0
        assert a["weight_bytes_read"] > 0 and a["weight_bytes_routed"] == 0
    counters = served_block["ev"].counters_after["/metrics"]
    assert counters['mcpx_engine_prefix_state_total{event="hit"}'] >= 5
    assert counters['mcpx_engine_prefix_state_total{event="miss"}'] == 0
    builds = [sp for tr in served_block["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.prefix_build"]
    assert len(builds) == 1 and builds[0]["attrs"]["chunks"] >= 3 and 600 < builds[0]["attrs"]["head_tokens"] < 1200
    by_name = {m["name"]: m for m in METRICS}
    per_plan = served_block["read"](by_name["engine.prefill_tok_per_plan"]["reader"],
                                    by_name["engine.prefill_tok_per_plan"]["args"])
    assert 0 < per_plan < 80  # a plan's own prefill is its intent behind the head's state
    assert served_block["kernel_paths"] == {"decode": 1, "prefill": 1, "ssm": 1, "gather": 1}
    for path in served_block["kernel_paths"]:
        assert served_block["paths"][path]["engaged"] and served_block["paths"][path]["dispatches"] > 0
    profile = served_block["health"]["engine_queue"]["worker_profile"]
    assert profile["prefix_state_hit"] >= 5 and profile["prefix_state_miss"] == 0
    assert served_block["costs"]["model"]["params_held"] == cfg.n_params


def test_the_block_selecting_cells_kernels_names_are_what_its_metrics_select():
    """``kernel.block_score_roofline``, ``kernel.attn_gathered_roofline`` and
    the two linear-attention metrics find their kernels by the names Mosaic
    gives the ops; the gathered call is an attention call to
    ``kernel.attn_busy_share`` too, and no state metric reads an attention op."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.block_score import block_score
    from mcpx.engine.kernels.paged_attention import ragged_paged_attention

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct

    def kernel_name(fn, *shapes):
        text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        (name,) = set(re.findall(r'kernel_name = "([^"]+)"', text))
        return name

    score = kernel_name(lambda q, kc, p, l: block_score(q, kc, p, l, stride=16),
                        sd((2, 8, 2, 16, 128), bf), sd((2, 2, 63, 128), f32), sd((2,), i32), sd((2,), i32))
    gathered = kernel_name(
        lambda q, k, v, t, p, l: ragged_paged_attention(q, k, v, t, p, l, 0, name="ragged_paged_attention_gathered"),
        sd((4, 1, 1, 16, 128), bf), sd((1, 1, 64, 16, 128), bf), sd((1, 1, 64, 16, 128), bf), sd((4, 8), i32),
        sd((4,), i32), sd((4,), i32))
    assert re.search(regex["kernel.block_score_roofline"], score)
    assert re.search(regex["kernel.attn_gathered_roofline"], gathered) and re.search(regex["kernel.attn_busy_share"], gathered)
    assert regex["kernel.linear_attn_busy_share"] == regex["kernel.linear_attn_window_roofline"] == regex["kernel.ssm_busy_share"]
    for name in (score, gathered):
        for other in ("kernel.ssm_busy_share", "kernel.moe_busy_share", "kernel.mla_busy_share", "kernel.dsa_busy_share"):
            assert not re.search(regex[other], name)
    assert not re.search(regex["kernel.attn_busy_share"], score)
    assert _CELLS_OF["attn.selected_share"] == [INDEX_CELL, BLOCK_CELL]
    assert _CELLS_OF["attn.slots_per_row_call"] == [LATENT_CELL, INDEX_CELL, BLOCK_CELL]


# ------------------------------------- the comparison that decides ``correct``
def _compare(block, reference, control="", seed=5):
    for k in block.CONTROLS:
        block.CONTROLS[k] = k == "follow_step_selection"
    if control and control != "int8-weights":
        block.CONTROLS[control] = not block.CONTROLS[control]
    try:
        cfg = block.rehearsal_config(512)
        params = params_of(cfg)
        out = reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), one_device(), seed=seed, interpret=True, page_size=16,
            rows=4, pages_per_row=40, prefill_len=512, control=control if control == "int8-weights" else "")
        return out, block.state_readings(), block.selection_readings(params, dataclasses.asdict(cfg))
    finally:
        for k in block.CONTROLS:
            block.CONTROLS[k] = k == "follow_step_selection"


def test_chunked_prefill_then_decode_windows_match_the_reference(block, reference):
    """The benchmark's own comparison at the rehearsal size: 4 rows prefilled
    to 111-430 tokens in chunks of 256 through the suffix route, three decode
    windows of uneven width of which a row keeps one token, kernels
    interpreted; against the token-by-token reference under the step's
    selection, which lies within the margin of the reference's own."""
    out, coarse, selection = _compare(block, reference)
    assert out["ok"] and out["rms_rel_err"] < 0.015 and out["positions"] == 16, out
    assert all(0.002 < c < 0.01 for c in coarse)  # a float32 state: 2^-8 of its values end in eight zeros
    assert sum(r["selection_checked"] for r in selection) > 4000
    assert max(r["selection_distance"] for r in selection) < block.SELECTION_MARGIN / 2


@pytest.mark.parametrize("control", ["wrong_blocks", "state_moves_by_the_window", "state_in_bfloat16", "int8-weights"])
def test_a_step_that_is_wrong_fails_the_comparison(block, reference, control):
    """A step that forces only a query's own block; one whose state moves by
    the window's live slots and not by the token kept; one whose state went
    through bfloat16; one on weights of 256 levels: not ``correct``, each."""
    out, coarse, selection = _compare(block, reference, control)
    assert not out["ok"], out
    if control == "wrong_blocks":
        assert max(r["selection_distance"] for r in selection) > block.SELECTION_MARGIN
    if control == "state_in_bfloat16":
        assert min(coarse) == 1.0
