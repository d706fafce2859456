"""The rehearsal child of ``jamba2-3b.wide-shortlist-closed`` (block module
``jamba``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_jamba_block.py``. The child
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
from mcpx.models.gemma.model import prefill
from tests.chip_rehearsal import (
    CHIP_DIR,
    FED_SCAN,
    METRICS,
    REPO,
    SCAN_CELL,
    _segments,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_jamba_r", os.path.join(CHIP_DIR, "models", "jamba.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_jamba_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_scan(tmp_path_factory):
    # As ``served_latent``: the attributes' names depend neither on the cell's
    # 128-service shortlist nor on its 1,024 warm-up bucket, and the warm-up's first
    # bucket is enough: the prompts' own (128) is compiled by the plans that take it.
    return serve(SCAN_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=8)


@pytest.mark.parametrize("metric", FED_SCAN, ids=[m["name"] for m in FED_SCAN])
def test_the_scan_block_feeds_its_metrics(served_scan, metric):
    """The Mamba-2 cell's two metrics that list this cell too: the names are
    the same, so the metric files read here unedited."""
    assert {m["name"] for m in FED_SCAN} == {"ssm.state_bytes_share", "engine.prefix_state_miss_share"}
    v = served_scan["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "ssm.state_bytes_share":
        assert 0 < v < 1


def test_the_scan_blocks_attributes_count_calls_slots_and_what_was_walked(served_scan):
    """At the rehearsal size: 6 selective-scan layers among 8, a state of 16 x
    512 float32 a row a layer. Every span attribute, counter, ``pallas.paths``
    entry and ``/healthz`` field the cell's metric files and its roofline
    reader's two forms read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("jamba", CHIP_DIR).rehearsal_config(3072)
    Lj = cfg.n_scan_layers
    assert (Lj, cfg.n_attn_layers, cfg.q_per_kv, cfg.ssm_slot_bytes) == (6, 2, 5, 16 * 512 * 4)
    segments = _segments(served_scan)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["ssm_row_calls"] % Lj == 0 and 0 < a["ssm_row_calls"] <= a["forwards"] * 8 * Lj
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
        assert a["ssm_row_calls"] <= a["ssm_tokens"] <= a["ssm_slots"] <= a["ssm_row_calls"] * 8
        assert a["attn_row_calls"] * Lj == a["ssm_row_calls"] * 2  # TWO attention layers
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (cfg.kv_bytes_per_token // 2)
        # every leaf read whole a forward, the tied embedding among them
        assert a["weight_bytes_read"] == a["forwards"] * (cfg.n_params * 2 + Lj * (16 * 512 + 2 * 512) * 2)
        assert a["moe_tokens_routed"] == 0 and "conv_row_calls" not in a
    # an admission's prefill WALKS its cohort: live tokens and A x T slots a J layer
    prefills = [sp for tr in served_scan["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills
    for sp in prefills:
        a = sp["attrs"]
        assert a["scan_slots"] == a["cohort_bucket"] * 128 * Lj  # the prompts fit the 128 bucket
        assert 0 < a["ssm_prefill_tokens"] <= a["scan_tokens"] <= a["scan_slots"] and a["scan_tokens"] % Lj == 0
        assert a["ssm_state_bytes"] == a["cohort_bucket"] * Lj * cfg.ssm_slot_bytes
    # what the roofline reader's two forms take from the spans (a device trace apart)
    mod = spec.import_file(os.path.join(CHIP_DIR, "reader_files", "selective_scan_roofline.py"), "chip_reader_t_")
    assert mod._segments(served_scan["ev"], "engine.prefill", ("scan_slots", "ssm_state_bytes"))
    assert mod._segments(served_scan["ev"], "engine.segment", ("ssm_state_bytes",))
    assert served_scan["read"]("selective_scan_roofline", {"regex": "selective_scan_window"}) is None  # no device trace here
    profile = served_scan["health"]["engine_queue"]["worker_profile"]
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_miss"}
    metrics = served_scan["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] >= 0
    assert served_scan["paths"]["prefill"]["dispatches"] == 0  # no suffix route: every row prefills whole
    assert served_scan["kernel_paths"] == {"decode": 1, "prefill": 0, "ssm": 1}
    ssm = served_scan["paths"]["ssm"]
    assert ssm["engaged"] is True and ssm["dispatches"] == served_scan["paths"]["decode"]["dispatches"] > 0
    pool = served_scan["health"]["engine_queue"]["state_pool"]
    assert pool["slots"] == 8 and pool["state_bytes"] == Lj * 8 * cfg.ssm_slot_bytes < pool["bytes"]
    model = served_scan["costs"]["model"]
    assert model["params_held"] == model["params_active_per_token"] == cfg.n_params


def test_the_scan_kernels_names_are_what_their_metrics_select():
    """The four new metrics find the scan's two call forms by the names Mosaic
    gives their ops, each its own form alone, and no other kernel's metric
    finds either."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.selective_scan import selective_scan_prefill, selective_scan_window

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    B, T, I, N = 2, 256, 512, 16
    names = {}
    text = jax.jit(selective_scan_prefill).trace(
        sd((B, T, I), f32), sd((B, T, I), f32), sd((B, T, N), f32), sd((B, T, N), f32), sd((N, I), f32),
        sd((B,), i32)).lower(lowering_platforms=("tpu",)).as_text()
    (names["prefill"],) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    text = jax.jit(selective_scan_window).trace(
        sd((3, 4, N, I), f32), sd((), i32), sd((B,), i32), sd((B,), i32), sd((B, 8, I), f32), sd((B, 8, I), f32),
        sd((B, 8, N), f32), sd((B, 8, I), f32), sd((B, 8, I), f32), sd((B, 8, N), f32), sd((B, 8, N), f32),
        sd((N, I), f32)).lower(lowering_platforms=("tpu",)).as_text()
    (names["window"],) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    for form, other in (("prefill", "window"), ("window", "prefill")):
        for kind in ("busy_share", "roofline"):
            pat = regex[f"kernel.selective_scan_{form}_{kind}"]
            assert re.search(pat, names[form]) and not re.search(pat, names[other])
    for metric, pat in regex.items():
        if "selective_scan" not in metric:
            assert not any(re.search(pat, name) for name in names.values()), metric


# ------------------------------------------------ the comparison, and controls
def _compare(block, reference, control="", **switches):
    mesh = one_device()
    cfg = block.rehearsal_config(3072)
    params = params_of(cfg)
    saved = dict(block.CONTROLS)
    block.CONTROLS.update(switches)
    try:
        out = reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 58, interpret=True,
            page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3, control=control,
        )
    finally:
        block.CONTROLS.update(saved)
    return out, cfg, params


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_prefill_then_decode_windows_match_the_reference(block, reference, path, monkeypatch):
    """The dense prefill into pages and state slots, then decode windows of
    uneven live widths of which every row keeps one token (the interpreted
    kernels; the jnp route beside them), over two periods of ``M^2 A M`` in
    bfloat16 weights: logits against the block's plain float32 reference, whose
    recurrence runs token by token, through the comparison that decides
    ``correct``. The reading (0.012 here, the feed-forwards' bfloat16 operands
    most of it) is held under three quarters of the limit: with the mixer's
    matrices on bfloat16 operands too (``mixer_in_bfloat16`` below) this size
    reads over it."""
    if path == "jnp":
        import mcpx.engine.paged_decode as paged
        import mcpx.models.gemma.model as dense

        monkeypatch.setattr(
            paged, "decode_chunk_paged",
            lambda *a, **kw: decode_chunk_paged(*a, **{**kw, "use_pallas": False}),
        )
        monkeypatch.setattr(dense, "prefill", lambda *a, **kw: prefill(*a, **{**kw, "use_pallas": False}))
    out, cfg, params = _compare(block, reference)
    assert out["ok"] and out["positions"] == 16, out
    assert (out["tol_rms"], out["tol_max"]) == reference.tol(8) == (0.02, 0.12)
    assert 0 < out["rms_rel_err"] < 0.015 and out["rms_rel_err"] < out["max_rel_err"] < 0.08
    # the rows' stored states carry float32's low bits (about 2^-8 of them read coarse)
    coarse = block.state_readings()
    assert len(coarse) == 4 and 0 < max(coarse) < 0.01 < block.STATE_COARSE
    assert reference.tol(28) == pytest.approx((0.02646, 0.15875), rel=1e-3)  # the cell's depth


@pytest.mark.parametrize("control", [
    dict(state_moves_by_the_window=True), dict(pending_commit_twice=True), dict(state_in_bfloat16=True),
    dict(control="int8-weights"),
])
def test_a_step_that_is_wrong_fails_the_comparison(block, reference, control):
    """A rejected slot's token left in ``h`` (the state moved by the window,
    not by what the row kept); the pending commit applied twice; ``h`` through
    bfloat16 where the configuration states float32 (the logits cannot see it:
    the stored values' low bits do); a step on weights of 256 levels: the
    comparison that passes the sound step does not pass these."""
    out, _, _ = _compare(block, reference, **control)
    assert not out["ok"], out


def test_the_mixers_operands_through_bfloat16_are_seen(block, reference):
    """``mixer_in_bfloat16``, the precision below the one the configuration
    states between the mixer's matrices: its four products read their operand
    rounded once, everything else as it was. At this size (8 layers, 6 of them
    mixers) it moves the reading from 0.012 to 0.019-0.022, about the limit;
    at the cell's 28 layers the chip judges it (``benchmarks/chip/tests/
    test_jamba_readings.py``, PERF.md section 6, PR 58)."""
    sound, _, _ = _compare(block, reference)
    low, _, _ = _compare(block, reference, mixer_in_bfloat16=True)
    assert low["rms_rel_err"] > 1.4 * sound["rms_rel_err"] and low["rms_rel_err"] > 0.9 * low["tol_rms"], (sound, low)
    assert not block.CONTROLS["mixer_in_bfloat16"]
