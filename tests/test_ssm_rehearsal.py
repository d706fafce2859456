"""The rehearsal child of ``nemotron-3-super.distinct-closed`` (block module
``nemotron_h``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_ssm_block.py``. The child
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

import mcpx.engine.paged_decode as paged
import mcpx.models.gemma.model as model
from mcpx.engine.paged_decode import decode_chunk_paged
from tests.chip_rehearsal import (
    CHIP_DIR,
    FED_STATE,
    METRICS,
    PLANNER_SHORTLIST,
    REPO,
    STATE_CELL,
    _segments,
    _segments_once,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_nemotron_h_r", os.path.join(CHIP_DIR, "models", "nemotron_h.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_nemotron_h_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_state(tmp_path_factory):
    # The cell's own shortlist (the planner's default) and the warm-up's first bucket alone:
    # as ``tests/test_afmoe_rehearsal.py::served_mixed``.
    return serve(STATE_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=PLANNER_SHORTLIST)


@pytest.mark.parametrize("metric", FED_STATE, ids=[m["name"] for m in FED_STATE])
def test_the_state_block_feeds_its_metrics(served_state, metric):
    """Its own metric, and the sparse cells' that list it too: its expert
    layers write what every sparse block's do."""
    assert {m["name"] for m in FED_STATE} == {
        "ssm.state_bytes_share", "engine.prefix_state_miss_share", "moe.experts_touched_share", "moe.tok_per_touched_expert",
        "moe.held_assignment_share", "moe.load_max_over_mean", "moe.touched_per_sparse_layer",
        "moe.prefill_rows_per_assignment", "moe.routed_bytes_share", "moe.kernel_step_share"}
    v = served_state["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_miss_share":
        assert v == 0.0  # the cell's prompts differ from their first page on: no row finds pages resident
    elif metric["name"] == "moe.kernel_step_share":
        assert v == 1.0  # the two-matrix experts in the latent, prefill and decode
    elif metric["unit"] == "ratio" and metric["name"] != "moe.load_max_over_mean":
        assert 0 < v < 1


def test_the_state_blocks_attributes_count_calls_slots_and_what_was_kept(served_state):
    """At the rehearsal size: 5 Mamba layers among 11, a state of 16 heads x
    32 x 32 float32 a row a layer. Every new span attribute, counter and
    ``pallas.paths`` entry the cell's five new metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("nemotron_h", CHIP_DIR).rehearsal_config(3072)
    assert (cfg.n_mamba_layers, cfg.n_sparse_layers, cfg.n_attn_layers) == (5, 5, 1)
    segments = _segments(served_state)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["ssm_row_calls"] % 5 == 0 and 0 < a["ssm_row_calls"] <= a["forwards"] * 8 * 5
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
        assert a["ssm_row_calls"] <= a["ssm_tokens"] <= a["ssm_slots"] <= a["ssm_row_calls"] * 8
        assert a["attn_row_calls"] * 5 == a["ssm_row_calls"]  # ONE attention layer
        assert a["moe_layer_forwards"] == a["forwards"] * 5
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * cfg.kv_bytes_per_token
        assert "ssm_prefill_tokens" in a
    once = _segments_once(served_state)
    profile = served_state["health"]["engine_queue"]["worker_profile"]
    for attr in ("ssm_row_calls", "ssm_state_bytes", "ssm_slots", "ssm_tokens", "ssm_prefill_tokens"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in once) > 0, attr
    # every admitted prompt's tokens went through each Mamba layer once
    prefills = [sp for tr in served_state["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills and all(sp["attrs"]["ssm_prefill_tokens"] % 5 == 0 and sp["attrs"]["ssm_prefill_tokens"] > 0
                            for sp in prefills)
    # pages found resident by a row that prefilled whole all the same (no radix node
    # holds a state): the lifetime sum and the counter agree, and the suffix route never ran
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_miss"}
    metrics = served_state["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] >= 0
    assert served_state["paths"]["prefill"]["dispatches"] == 0
    # the kernel paths the cell's ``correct`` asks for
    assert served_state["kernel_paths"] == {"decode": 1, "prefill": 0, "ssm": 1}
    ssm = served_state["paths"]["ssm"]
    assert ssm["engaged"] is True and ssm["dispatches"] == served_state["paths"]["decode"]["dispatches"] > 0
    model = served_state["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    # a token reads 3 of the 8 experts held, of two matrices in the latent, in 5 layers
    assert model["params_held"] - model["params_active_per_token"] == 5 * 5 * 2 * cfg.moe_latent_size * cfg.d_expert


def test_the_state_kernels_name_is_what_its_metrics_select():
    """``kernel.ssm_busy_share`` and ``kernel.ssm_window_roofline`` find the
    state pool's kernel by the name Mosaic gives its op, and no other kernel's
    metric does."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.ssm import ssm_window

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    text = jax.jit(ssm_window, static_argnums=1).trace(
        sd((2, 8, 128, 1024), f32), 1, sd((4,), i32), sd((4,), i32), sd((4, 1024), f32), sd((4, 8, 1024), f32),
        sd((4, 2, 128, 8), f32), sd((4, 2, 8, 128), f32)).lower(lowering_platforms=("tpu",)).as_text()
    (name,) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert re.search(regex["kernel.ssm_busy_share"], name) and re.search(regex["kernel.ssm_window_roofline"], name)
    for other in ("kernel.attn_busy_share", "kernel.moe_busy_share", "kernel.mla_busy_share",
                  "kernel.routed_experts_roofline"):
        assert not re.search(regex[other], name)
    assert regex["kernel.routed_experts_roofline"] == regex["kernel.moe_busy_share"]


# ------------------------------------------------ the comparison, and controls
def _compare(block, reference, control="", **switches):
    mesh = one_device()
    cfg = block.rehearsal_config(3072)
    params = params_of(cfg)
    saved = dict(block.CONTROLS)
    block.CONTROLS.update(switches)
    try:
        out = reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 48, interpret=True,
            page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3, control=control,
        )
    finally:
        block.CONTROLS.update(saved)
    return out, cfg, params


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_prefill_then_decode_windows_match_the_reference(block, reference, path, monkeypatch):
    """The dense prefill into pages and state slots, then decode windows of
    uneven live widths of which every row keeps one token (the interpreted
    kernels; the jnp route beside them), over the pattern's first 11 layers:
    logits against the block's plain float32 reference, whose recurrence runs
    token by token, through the comparison that decides ``correct``, under the
    step's routing."""
    if path == "jnp":
        import mcpx.engine.paged_decode as paged

        monkeypatch.setattr(
            paged, "decode_chunk_paged",
            lambda *a, **kw: decode_chunk_paged(*a, **{**kw, "use_pallas": False}),
        )
    out, cfg, params = _compare(block, reference)
    assert out["ok"] and out["positions"] == 16, out
    assert (out["tol_rms"], out["tol_max"]) == reference.tol(11) == (0.02, 0.12)
    assert 0 < out["rms_rel_err"] < out["max_rel_err"]
    read = block.routing_readings(params, dataclasses.asdict(cfg))
    assert len(read) == 4 and max(r["distance"] for r in read) < block.MARGIN
    # every position the step compared, in each of the 5 expert layers
    assert sum(r["checked"] for r in read) == 5 * (sum(out["prompt_lens"]) + 4 * 3)
    # the rows' stored states carry float32's low bits (about 2^-8 of them read coarse)
    coarse = block.state_readings()
    assert len(coarse) == 4 and 0 < max(coarse) < 0.01 < block.STATE_COARSE


@pytest.mark.parametrize("control", [
    dict(state_moves_by_the_window=True), dict(state_in_bfloat16=True), dict(follow_step_routing=False),
    dict(control="int8-weights"),
])
def test_a_step_that_is_wrong_fails_the_comparison(block, reference, control):
    """A state that moves by the window and not by what the row kept; a
    state kept in bfloat16 where the configuration states float32 (the
    logits cannot see it: the stored values' low bits do); a reference under
    its own routing; a step on weights of 256 levels: the comparison that
    passes the sound step does not pass these."""
    out, _, _ = _compare(block, reference, **control)
    assert not out["ok"], out
