"""The rehearsal child of ``deepseek-v3.2-exp.catalogue-closed`` (block module
``dsa``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_dsa_block.py``. The child
(``serve``), the ``FED*`` lists and everything the children share are
``tests/chip_rehearsal.py``'s. CPU, interpreted kernels: correctness readings,
not device numbers.
"""

import math
import sys

import pytest

from tests.chip_rehearsal import (
    CHIP_DIR,
    FED_INDEX,
    INDEX_CELL,
    METRICS,
    _segments,
    _segments_once,
    serve,
)


@pytest.fixture(scope="module")
def served_index(tmp_path_factory):
    # The cell's own shortlist (1,000 >= the 120 services served here: a
    # catalogue of ~800 tokens, past the rehearsal block's 256-token buckets,
    # so the head is built in chunks) with a warm-up the CPU can afford: its first
    # bucket alone, the chunks' and the suffixes' compiled by the plans that take them.
    return serve(INDEX_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=1000)


@pytest.mark.parametrize("metric", FED_INDEX, ids=[m["name"] for m in FED_INDEX])
def test_the_index_block_feeds_its_metrics(served_index, metric):
    assert {m["name"] for m in FED_INDEX} == {
        "attn.selected_share", "attn.index_tok_per_call", "attn.index_bytes_share"}
    v = served_index["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "attn.selected_share":
        assert 0.02 < v < 0.06  # the 32 best of a ~800-token catalogue's keys
    if metric["name"] == "attn.index_tok_per_call":
        assert 600 < v < 1200  # every live row decodes behind the whole catalogue
    if metric["name"] == "attn.index_bytes_share":
        assert v == pytest.approx(32 / (32 + 64 + 16))  # an index key beside the latent and the rotated key


def test_the_index_blocks_attributes_count_the_selection_and_the_heads_chunks(served_index):
    """At the rehearsal size: an index of 4 heads x 32 over the 32 best keys,
    two layers; the catalogue of 120 services a head of ~800 tokens, built in
    chunks of the block's largest bucket (256) on the first plan."""
    segments = _segments(served_index)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] > 0 and a["attn_sel_tokens"] == a["attn_row_calls"] * 32
        assert a["index_ctx_tokens"] == a["attn_ctx_tokens"] > a["attn_sel_tokens"]  # every row is past the 32nd key
        assert a["index_bytes_read"] == a["index_ctx_tokens"] * 32 * 2
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (64 + 16) * 2  # the masked form streams every page
        # ~50 pages a row: three whole key blocks and a part of a fourth a call
        assert 3 * a["attn_row_calls"] <= a["attn_key_blocks"] <= 5 * a["attn_row_calls"]
        assert a["attn_run_blocks"] <= a["attn_key_blocks"]
    # the head, built in chunks after the warm-up's rows were freed, lies side by
    # side in the pools: its whole key blocks are fetched as runs
    by_name = {m["name"]: m for m in METRICS}
    run_share = served_index["read"](by_name["attn.page_run_share"]["reader"], by_name["attn.page_run_share"]["args"])
    assert 0.6 <= run_share < 1
    profile = served_index["health"]["engine_queue"]["worker_profile"]
    for attr in ("attn_sel_tokens", "index_ctx_tokens", "index_bytes_read"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_index)) > 0
    # the head: one dense chunk and suffix chunks over the pages before it, counted and spanned once
    chunks = served_index["ev"].counters_after["/metrics"]["mcpx_engine_prefix_build_chunks_total"]
    builds = [sp for tr in served_index["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.prefix_build"]
    assert chunks >= 3 and len(builds) == 1
    assert builds[0]["attrs"]["chunks"] == chunks and 600 < builds[0]["attrs"]["head_tokens"] < 1200
    assert builds[0]["attrs"]["head_tokens"] % 16 == 0 and builds[0]["attrs"]["head_tokens"] > 256 * (chunks - 1)
    # every plan's own prefill is its intent behind the shared head
    per_plan = served_index["read"](by_name["engine.prefill_tok_per_plan"]["reader"],
                                    by_name["engine.prefill_tok_per_plan"]["args"])
    assert 0 < per_plan < 80
    # both kernel paths engaged: the suffix route carries every plan's prompt
    assert served_index["kernel_paths"] == {"decode": 1, "prefill": 1}
    for path in ("decode", "prefill"):
        assert served_index["paths"][path]["engaged"] and served_index["paths"][path]["dispatches"] > 0
    spec = sys.modules["spec"]
    cfg = spec.load_block("dsa", CHIP_DIR).rehearsal_config(3072)
    assert served_index["costs"]["model"]["params_held"] == cfg.n_params
