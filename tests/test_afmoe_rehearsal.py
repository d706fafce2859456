"""The rehearsal child of ``trinity-mini.distinct-closed`` (block module
``afmoe``): what the chip harness reads from the served program for this
configuration's metrics, beside ``tests/test_afmoe_block.py``. The child
(``serve``), the ``FED*`` lists and everything the children share are
``tests/chip_rehearsal.py``'s. CPU, interpreted kernels: correctness readings,
not device numbers.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mcpx.engine.paged_decode as paged
import mcpx.models.gemma.model as model
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma import moe
from tests.chip_rehearsal import (
    CHIP_DIR,
    FED_MIXED,
    MIXED_CELL,
    PLANNER_SHORTLIST,
    REPO,
    _segments,
    _segments_once,
    serve,
)
from tests.helpers import by_path, one_device, params_of


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_afmoe_r", os.path.join(CHIP_DIR, "models", "afmoe.py"))


@pytest.fixture(scope="module")
def reference():
    return by_path("chip_harness_reference_afmoe_r", os.path.join(CHIP_DIR, "reference.py"))


@pytest.fixture(scope="module")
def served_mixed(tmp_path_factory):
    # The cell's own shortlist (the planner's default) and the warm-up's first bucket alone: a
    # longer prompt's bucket is compiled by the plan that needs it, at the cohort size it came in,
    # and not at every size ahead of it. The attributes' names depend on neither.
    return serve(MIXED_CELL, tmp_path_factory, warmup_max_len=64, shortlist_top_k=PLANNER_SHORTLIST)


@pytest.mark.parametrize("metric", FED_MIXED, ids=[m["name"] for m in FED_MIXED])
def test_the_mixed_block_feeds_its_metrics(served_mixed, metric):
    assert {m["name"] for m in FED_MIXED} == {
        "moe.routed_bytes_share", "moe.touched_per_sparse_layer", "moe.load_max_over_mean",
        "moe.prefill_rows_per_assignment", "moe.kernel_step_share"}
    v = served_mixed["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "moe.kernel_step_share":
        assert v == 1.0  # decode windows, cohorts of one and grouped tiles: every step a kernel's
    if metric["name"] == "moe.routed_bytes_share":
        assert 0 < v < 1
    if metric["name"] == "moe.touched_per_sparse_layer":
        assert 2 <= v <= 8  # a live token touches its 2 experts; a layer has 8
    if metric["name"] == "moe.load_max_over_mean":
        assert 1 <= v <= 8  # even routing reads 1, one expert taking all reads 8
    if metric["name"] == "moe.prefill_rows_per_assignment":
        # grouped (the rehearsal's cohort prefill is 8 x 128 slots, past the
        # ridge): whole tiles of 64 rows, so at least 1; the loop over its 8
        # experts would read 1,024 x 8 rows for a cohort's few hundred assignments
        assert 1 <= v < 64


def test_the_mixed_blocks_attributes_count_sparse_layers_and_bytes(served_mixed):
    """At the rehearsal size: 2 dense layers, then 6 sparse ones of 8 experts
    held, 2 a token, beside a shared expert. The bytes are those of the
    leaves a forward reads, reckoned here from the tree's shapes."""
    sys.path.insert(0, REPO)
    import jax
    import numpy as np

    spec = sys.modules["spec"]
    cfg = spec.load_block("afmoe", CHIP_DIR).rehearsal_config(3072)
    from mcpx.models.gemma.model import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))
    stacks = {k: shapes["layers"][k] for k in ("w_gate", "w_up", "w_down")}
    expert = nbytes(stacks) // (6 * 8)
    assert expert == 3 * cfg.d_model * cfg.d_expert * 2
    rest = nbytes(shapes) - nbytes(stacks) - nbytes(shapes["embed"])
    segments = _segments(served_mixed)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["moe_layer_forwards"] == a["forwards"] * 6
        assert a["moe_expert_slots"] == a["forwards"] * 6 * 8
        assert 0 < a["moe_experts_touched"] <= min(a["moe_expert_slots"], a["moe_assignments"])
        assert a["moe_assignments"] % (2 * 6) == 0  # 2 experts a live token in each SPARSE layer
        assert a["weight_bytes_routed"] == a["moe_experts_touched"] * expert
        assert a["weight_bytes_read"] == a["weight_bytes_routed"] + a["forwards"] * rest
    profile = served_mixed["health"]["engine_queue"]["worker_profile"]
    for attr in ("moe_layer_forwards", "moe_expert_slots", "weight_bytes_routed", "weight_bytes_read"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_mixed)) > 0
    per_expert = {key: v for key, v in served_mixed["ev"].counters_after["/metrics"].items()
                  if key.startswith("mcpx_engine_moe_expert_tokens_total{")}
    assert len(per_expert) == 8 and sum(per_expert.values()) <= profile["moe_assignments"]
    # /costs: a token reads 2 + 1 of the 8 + 1 experts of a sparse layer, and all of a dense one
    model = served_mixed["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    assert model["params_held"] - model["params_active_per_token"] == 6 * 6 * 3 * cfg.d_model * cfg.d_expert


# ------------------------------------------------ the comparison, and controls
def _compare(block, reference, prog=None, control="", **kw):
    mesh = one_device()
    cfg = block.rehearsal_config(3072)
    params = params_of(cfg)
    return reference.compare_with_engine_step(
        block, params, prog or cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 36, interpret=True,
        page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3, control=control, **kw,
    ), cfg, params


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_prefill_then_paged_decode_matches_the_reference(block, reference, path, monkeypatch):
    """Dense prefill committed to pages, then paged decode one token at a
    time (the interpreted kernel; the jnp route beside it), over two periods
    of the layer pattern behind the two dense layers, contexts past the
    window of 8: logits against the block's plain float32 reference, through
    the comparison that decides ``correct``, under the step's routing."""
    if path == "jnp":
        import mcpx.engine.paged_decode as paged

        monkeypatch.setattr(
            paged, "decode_chunk_paged",
            lambda *a, **kw: decode_chunk_paged(*a, **{**kw, "use_pallas": False}),
        )
    out, cfg, params = _compare(block, reference)
    assert out["ok"] and out["positions"] == 16, out
    assert (out["tol_rms"], out["tol_max"]) == reference.tol(8) == (0.02, 0.12)
    assert min(out["prompt_lens"]) >= 9 and 0 < out["rms_rel_err"] < out["max_rel_err"]
    read = block.routing_readings(params, dataclasses.asdict(cfg))
    assert len(read) == 4 and max(r["distance"] for r in read) < block.MARGIN
    # every position the step ran, in each of the 6 SPARSE layers
    assert sum(r["checked"] for r in read) == 6 * (sum(out["prompt_lens"]) + 4 * 3)


def _bias_in_the_weights(x, router, cfg, bias=None):
    """The mistake the bias's control makes: weights from s + b."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, router, preferred_element_type=jnp.float32))
    w, chosen = jax.lax.top_k(scores + bias, cfg.n_experts_per_tok)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * cfg.router_scale


CONTROLS = {
    "no_output_gate": dict(attn_gate=False),
    "no_qk_norm": dict(qk_norm=False),
    "full_layers_rotated": dict(rope_full_layers=True),
    "post_branch_norms_dropped": dict(post_norms=False),
    "softmax_for_sigmoid": dict(router_scoring="softmax", router_bias_scale=0.0, router_scale=1.0),
    "bias_in_the_weights": "route",
    "route_scale_1": dict(router_scale=1.0),
    "shared_expert_dropped": dict(d_shared_expert=0),
    "embeddings_unscaled": dict(scale_embeddings=False),
    "int8_weights": "int8-weights",
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_step_that_leaves_a_part_out_fails_the_comparison(block, reference, control, monkeypatch):
    """Each part of the block taken out of (or put wrongly into) the
    PROGRAM's step alone: the reference keeps it, and the comparison that
    passes the sound step does not pass this one."""
    what = CONTROLS[control]
    cfg = block.rehearsal_config(3072)
    if what == "route":
        monkeypatch.setattr(moe, "route", _bias_in_the_weights)
        out, _, _ = _compare(block, reference)
    elif isinstance(what, str):
        out, _, _ = _compare(block, reference, control=what)
    else:
        out, _, _ = _compare(block, reference, prog=dataclasses.replace(cfg, **what))
    assert not out["ok"], out


def test_without_the_steps_routing_a_sound_step_fails(block, reference, monkeypatch):
    monkeypatch.setitem(block.CONTROLS, "follow_step_routing", False)
    out, _, _ = _compare(block, reference)
    assert not out["ok"]
