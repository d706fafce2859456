"""The rehearsal child of ``a.x-k1.wide-shortlist-closed`` (block module ``mla``):
what the chip harness reads from the served program for this configuration's
metrics, beside ``tests/test_mla_block.py``. The child (``serve``), the
``FED*`` lists and everything the children share are
``tests/chip_rehearsal.py``'s. CPU, interpreted kernels: correctness readings,
not device numbers.
"""

import dataclasses
import math
import os
import sys

import jax.numpy as jnp
import pytest

import mcpx.engine.paged_decode as paged
import mcpx.models.gemma.model as model
from mcpx.engine.paged_decode import decode_chunk_paged
from tests.chip_rehearsal import (
    CHIP_DIR,
    FED_LATENT,
    LATENT_CELL,
    _segments,
    _segments_once,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_mla_r", os.path.join(CHIP_DIR, "models", "mla.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_mla_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_latent(tmp_path_factory):
    # The cell's 128-service shortlist and its 1,024 warm-up bucket make a
    # rehearsal of minutes; the attributes' names do not depend on either. The
    # warm-up's first bucket alone (64): the prompts' own (128) is compiled at
    # the two cohort sizes they come in, not at the four x two routes ahead of them.
    return serve(LATENT_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=8)


@pytest.mark.parametrize("metric", FED_LATENT, ids=[m["name"] for m in FED_LATENT])
def test_the_latent_block_feeds_its_metrics(served_latent, metric):
    assert {m["name"] for m in FED_LATENT} == {
        "attn.ctx_tok_per_call", "attn.latent_bytes_share", "moe.held_assignment_share",
        "attn.slots_per_row_call", "attn.page_run_share"}
    v = served_latent["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "attn.page_run_share":
        assert v == 0  # a context under 256 tokens has no whole key block to be a run
    if metric["name"] == "attn.slots_per_row_call":
        assert 1 <= v < 2  # a live row decodes a token or two of its window's 8 slots a forward
    if metric["name"] == "attn.ctx_tok_per_call":
        assert 60 < v < 200  # an 8-service shortlist's prompt and what was decoded behind it
    if metric["name"] in ("attn.latent_bytes_share", "moe.held_assignment_share"):
        assert 0 < v < 1


def test_the_latent_blocks_attributes_count_context_and_this_share(served_latent):
    """At the rehearsal size: the dense lead and one sparse layer, experts
    4..7 of 16 held, 2 a token; a cache row of 64 + 16 values a token a layer."""
    segments = _segments(served_latent)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] % 2 == 0 and 0 < a["attn_row_calls"] <= 8 * a["forwards"] * 2
        assert a["attn_ctx_tokens"] > a["attn_row_calls"]
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (64 + 16) * 2
        # a live row's score tile: its rung, from one slot to the window's 8
        assert a["attn_row_calls"] <= a["attn_query_slots"] <= 8 * a["attn_row_calls"]
        assert a["attn_key_blocks"] == a["attn_row_calls"] and a["attn_run_blocks"] == 0  # one part block a call
        assert a["moe_tokens_routed"] % 2 == 0  # 2 experts a live token in the one sparse layer
        assert 0 <= a["moe_assignments"] <= a["moe_tokens_routed"]
        assert a["moe_expert_slots"] == a["forwards"] * 1 * 4  # the 4 experts held
    profile = served_latent["health"]["engine_queue"]["worker_profile"]
    for attr in ("attn_ctx_tokens", "attn_row_calls", "attn_query_slots", "kv_bytes_read", "moe_tokens_routed"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_latent)) > 0
    per_expert = {key for key in served_latent["ev"].counters_after["/metrics"]
                  if key.startswith("mcpx_engine_moe_expert_tokens_total{")}
    assert per_expert == {f'mcpx_engine_moe_expert_tokens_total{{expert="{e}"}}' for e in range(4, 8)}
    # /costs counts the latent attention's leaves: the tree's own count
    spec = sys.modules["spec"]
    cfg = spec.load_block("mla", CHIP_DIR).rehearsal_config(3072)
    assert served_latent["costs"]["model"]["params_held"] == cfg.n_params
    # the latent kernel served the decode path
    assert served_latent["paths"]["decode"]["engaged"] and served_latent["paths"]["decode"]["dispatches"] > 0


# ------------------------------------------------ the comparison, and controls
def _compare(block, reference, prog=None, control=""):
    mesh = one_device()
    cfg = block.rehearsal_config(3072)
    params = params_of(cfg)
    return reference.compare_with_engine_step(
        block, params, prog or cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 42, interpret=True,
        page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3, control=control,
    ), cfg, params


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_prefill_then_paged_decode_matches_the_reference(block, reference, path, monkeypatch):
    """Expanded prefill committed to latent pages, then absorbed paged decode
    one token at a time (the interpreted kernel; the jnp route beside it):
    logits against the block's plain float32 reference (expanded, no cache,
    the same share of the experts), through the comparison that decides
    ``correct``, under the step's routing."""
    if path == "jnp":
        monkeypatch.setattr(
            paged, "decode_chunk_paged",
            lambda *a, **kw: decode_chunk_paged(*a, **{**kw, "use_pallas": False}),
        )
    out, cfg, params = _compare(block, reference)
    assert out["ok"] and out["positions"] == 16, out
    assert (out["tol_rms"], out["tol_max"]) == reference.tol(2) == (0.02, 0.12)
    assert min(out["prompt_lens"]) >= 9 and 0 < out["rms_rel_err"] < out["max_rel_err"]
    read = block.routing_readings(params, dataclasses.asdict(cfg))
    assert len(read) == 4 and max(r["distance"] for r in read) < block.MARGIN
    # every position the step ran, in the SPARSE layer behind the dense lead
    assert sum(r["checked"] for r in read) == sum(out["prompt_lens"]) + 4 * 3


def _skip_norm_of(width):
    rms_norm = model.rms_norm

    def norm(x, scale, *args, **kw):
        return x.astype(args[2] if len(args) > 2 and args[2] else x.dtype) if scale.shape[-1] == width \
            else rms_norm(x, scale, *args, **kw)
    return norm


def _shared_key_unrotated(x, positions, theta, kind=None, rope=model.apply_rope):
    return x if x.shape[-2] == 1 else rope(x, positions, theta, kind)


def _absorbed_through_the_values(q, lp, cfg, *args, attend=paged._latent_attend, **kw):
    """The mistake the absorption's control makes: the query taken into the
    latent's space through W_uv, the output brought back through W_uk."""
    hd = cfg.head_dim
    swapped = jnp.concatenate([lp["w_ukv"][..., hd:], lp["w_ukv"][..., :hd]], axis=-1)
    return attend(q, {**lp, "w_ukv": swapped}, cfg, *args, **kw)


def _dense_lead_without_its_feed_forward(width, mlp=model.gated_mlp):
    def gated_mlp(h, w_gate, *args, **kw):
        out = mlp(h, w_gate, *args, **kw)
        return jnp.zeros_like(out) if w_gate.shape[-1] == width else out
    return gated_mlp


CONTROLS = {
    "no_q_norm": ("rms_norm", lambda cfg: _skip_norm_of(cfg.q_lora_rank)),
    "no_kv_norm": ("rms_norm", lambda cfg: _skip_norm_of(cfg.kv_lora_rank)),
    "shared_key_unrotated": ("apply_rope", lambda cfg: _shared_key_unrotated),
    "no_m2_in_the_scale": dict(attn_score_factor=1.0),
    "absorbed_on_the_wrong_side": ("_latent_attend", lambda cfg: _absorbed_through_the_values),
    "shared_expert_dropped": dict(d_shared_expert=0),
    "dense_lead_dropped": ("gated_mlp", lambda cfg: _dense_lead_without_its_feed_forward(cfg.d_ff)),
    "route_scale_1": dict(router_scale=1.0),
    "int8_weights": "int8-weights",
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_step_that_leaves_a_part_out_fails_the_comparison(block, reference, control, monkeypatch):
    """Each part of the block taken out of (or put wrongly into) the
    PROGRAM's step alone: the reference keeps it, and the comparison that
    passes the sound step does not pass this one."""
    what = CONTROLS[control]
    cfg = block.rehearsal_config(3072)
    if isinstance(what, tuple):
        name, make = what
        for module in (model, paged):
            if hasattr(module, name):
                monkeypatch.setattr(module, name, make(cfg))
        out, _, _ = _compare(block, reference)
    elif isinstance(what, str):
        out, _, _ = _compare(block, reference, control=what)
    else:
        out, _, _ = _compare(block, reference, prog=dataclasses.replace(cfg, **what))
    assert not out["ok"], out
