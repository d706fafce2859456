"""The block beyond the default one: sparse feed-forward (router + experts
held), per-layer attention kinds (window, YaRN rope), untied head, plain
gain. CPU, small sizes; the plain reference is the benchmark's block module
(``benchmarks/chip/models/mellum.py``), imported by path."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, init_params, prefill
from mcpx.models.gemma import moe
from mcpx.models.gemma.moe import moe_forward, route
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, grouped_against_loop, one_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_mellum_t", os.path.join(CHIP_DIR, "models", "mellum.py"))


def small(**kw):
    base = dict(
        vocab_size=384, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128,
        rope_theta=500000.0, layer_types=PERIOD, sliding_window=8, yarn_factor=16.0,
        yarn_original_max_pos=64, yarn_attention_factor=1.2772588722239782,
        n_experts=8, n_experts_per_tok=2, d_expert=32, activation="silu",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False, dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


# ------------------------------------------------------------ configuration
def test_defaults_are_todays_block_and_carry_no_layer_data():
    cfg = GemmaConfig()
    assert cfg.is_default_block and cfg.rope_tables() is None and cfg.layer_windows() is None
    assert cfg.n_active_params == cfg.n_params
    assert set(init_params(cfg, jax.random.PRNGKey(0))) == {"embed", "layers", "final_norm"}
    assert not small().is_default_block


def test_published_yarn_frequencies_and_factor():
    """Mellum 2's numbers: dim 128, base 500000, factor 16, original 8192,
    beta_fast 32, beta_slow 1: corr(32) = 18.08, corr(1) = 34.98, so the ramp
    runs over k = 18..35."""
    cfg = small(head_dim=128, n_heads=2, n_kv_heads=1, yarn_original_max_pos=8192)
    inv_freq, factor = cfg.rope_tables()
    plain = np.asarray([500000.0 ** (-2 * k / 128) for k in range(64)])
    assert inv_freq.shape == (4, 64) and factor.tolist() == pytest.approx([1, 1, 1, 1.2772588722239782])
    assert 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    for layer in range(3):  # sliding layers: the plain rope
        np.testing.assert_allclose(inv_freq[layer], plain, rtol=1e-6)
    ratio = inv_freq[3] / plain
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)  # k <= low = 18: untouched
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)  # k >= high = 35: stretched
    np.testing.assert_allclose(ratio[18:36], 1 - (np.arange(18, 36) - 18) / 17 * (15 / 16), rtol=1e-5)
    assert cfg.layer_windows().tolist() == [8, 8, 8, 2**30]


def test_the_benchmarks_reference_derives_the_same_rope(block):
    cfg = small(head_dim=128, n_heads=2, n_kv_heads=1, yarn_original_max_pos=8192)
    ours, theirs = cfg.rope_tables(), block._rope_tables(dataclasses.asdict(cfg))
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-6)
    np.testing.assert_allclose(ours[1], theirs[1], rtol=1e-6)


@pytest.mark.parametrize("bad", [
    dict(layer_types=PERIOD[:3]),
    dict(layer_types=("windowed",) * 4),
    dict(sliding_window=0),
    dict(n_experts_per_tok=9),
    dict(expert_first=6, experts_held=4),
    dict(activation="relu"),
], ids=lambda d: next(iter(d)))
def test_a_configuration_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


def test_params_read_a_token_are_not_all_params_held():
    cfg = small()
    D, F = cfg.d_model, cfg.d_expert
    assert cfg.n_params - cfg.n_active_params == cfg.n_layers * (8 - 2) * 3 * D * F
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(init_params(cfg, jax.random.PRNGKey(0))))
    assert held == cfg.n_params
    share = dataclasses.replace(cfg, expert_first=2, experts_held=2)
    assert cfg.n_params - share.n_params == cfg.n_layers * 6 * 3 * D * F


@pytest.mark.parametrize("feature, cfg_json", [
    ("quantize", {"model": {"quantize": "int8"}}),
    ("speculative", {"engine": {"speculative": {"enabled": True}, "hetero_batch": True}}),
])
def test_what_the_block_does_not_do_yet_is_an_error_at_construction(feature, cfg_json):
    from mcpx.engine.engine import InferenceEngine

    cfg = MCPXConfig.from_dict(cfg_json)
    with pytest.raises(ConfigError, match="departs from the default"):
        InferenceEngine(cfg, model_cfg=small(vocab_size=384))
    InferenceEngine(MCPXConfig(), model_cfg=small(vocab_size=384))  # without it: built


# ------------------------------------------------------------ expert layer
def _layer_inputs(cfg, seed=0, B=3, S=5):
    params = init_params(cfg, jax.random.PRNGKey(seed))["layers"]
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.d_model), jnp.float32)
    experts = {k: params[k] for k in ("w_gate", "w_up", "w_down")}
    return h, params["router"], experts


def _plain_moe(h, router, experts, layer, chosen, w, cfg):
    """Every expert densely, weighted by the given routing."""
    x = np.asarray(h, np.float64).reshape(-1, cfg.d_model)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, wt in zip(np.asarray(chosen)[t], np.asarray(w, np.float64)[t]):
            loc = e - cfg.expert_first
            if not 0 <= loc < cfg.n_experts_held:
                continue
            g = x[t] @ np.asarray(experts["w_gate"][layer, loc], np.float64)
            u = x[t] @ np.asarray(experts["w_up"][layer, loc], np.float64)
            out[t] += wt * ((g / (1 + np.exp(-g)) * u) @ np.asarray(experts["w_down"][layer, loc], np.float64))
    return out.reshape(h.shape)


@pytest.mark.parametrize("layer", [0, 3])
def test_expert_layer_matches_the_plain_one_under_the_same_routing(layer):
    cfg = small()
    h, router, experts = _layer_inputs(cfg)
    out, stats, chosen = moe_forward(h, router[layer], experts, jnp.int32(layer), cfg)
    _, w = route(h.reshape(-1, cfg.d_model), router[layer], cfg)
    want = _plain_moe(h, router, experts, layer, chosen.reshape(-1, 2), w, cfg)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)
    assert int(stats[:8].sum()) == 3 * 5 * 2 and int(stats[8]) == int((stats[:8] > 0).sum())


def test_routing_is_the_softmaxs_top_k_outside_a_margin():
    """The chosen set is the float64 softmax's top-k wherever the k-th and
    (k+1)-th probabilities lie more than 1e-5 of the k-th apart, and the
    weights are the chosen probabilities renormalised."""
    cfg = small()
    h, router, _ = _layer_inputs(cfg, seed=3, B=8, S=16)
    x = h.reshape(-1, cfg.d_model)
    chosen, w = route(x, router[1], cfg)
    logits = np.asarray(x, np.float64) @ np.asarray(router[1], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)
    clear = (np.take_along_axis(p, order[:, 1:2], 1) - np.take_along_axis(p, order[:, 2:3], 1))[:, 0] > 1e-5 * p.max(-1)
    assert clear.mean() > 0.9
    assert (np.sort(np.asarray(chosen), -1) == np.sort(order[:, :2], -1))[clear].all()
    top = np.take_along_axis(p, np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(w), top / top.sum(-1, keepdims=True), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)


def test_the_shares_add_up():
    """model-configs section 4: the 8 experts held 2 + 2 + 4 by three shares,
    each routing over all 8: the three partial results sum to the uncut
    layer's, and so do their counters."""
    cfg = small()
    h, router, experts = _layer_inputs(cfg, seed=5)
    whole, stats, chosen = moe_forward(h, router[2], experts, jnp.int32(2), cfg)
    parts, counts = [], []
    for first, held in ((0, 2), (2, 2), (4, 4)):
        share = dataclasses.replace(cfg, expert_first=first, experts_held=held)
        mine = {k: v[:, first : first + held] for k, v in experts.items()}
        out, st, ch = moe_forward(h, router[2], mine, jnp.int32(2), share)
        assert (np.asarray(ch) == np.asarray(chosen)).all()  # every share routes over all 8
        parts.append(np.asarray(out))
        counts.append(np.asarray(st[:held]))
    np.testing.assert_allclose(sum(parts), np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert np.concatenate(counts).tolist() == np.asarray(stats[:8]).tolist()
    assert all(np.abs(p).max() > 0 for p in parts)


def test_pad_slots_and_idle_rows_are_routed_nowhere():
    cfg = small()
    h, router, experts = _layer_inputs(cfg, seed=7, B=4, S=6)
    q_lens = jnp.asarray([6, 2, 0, 1])
    live = jnp.arange(6)[None, :] < q_lens[:, None]
    out, stats, _ = moe_forward(h, router[0], experts, jnp.int32(0), cfg, live)
    assert int(stats[:8].sum()) == 2 * int(q_lens.sum())  # pads count in no counter
    assert (np.asarray(out)[~np.asarray(live)] == 0).all()  # and get nothing
    noise = jnp.where(live[..., None], h, 100.0 * jax.random.normal(jax.random.PRNGKey(9), h.shape))
    out2, stats2, _ = moe_forward(noise, router[0], experts, jnp.int32(0), cfg, live)
    np.testing.assert_array_equal(np.asarray(out2)[np.asarray(live)], np.asarray(out)[np.asarray(live)])
    assert np.asarray(stats2).tolist() == np.asarray(stats).tolist()
    nothing, stats0, _ = moe_forward(h, router[0], experts, jnp.int32(0), cfg, jnp.zeros((4, 6), bool))
    assert not np.asarray(nothing).any() and not np.asarray(stats0).any()  # no expert is read


# ----------------------------------- past the ridge: rows grouped by expert
def _steered(h, router, onto=None, away=None):
    """One feature held at 5 in every slot, and the router's row for it: a
    logit of +50 for the expert every token is to choose, -50 for the one
    none is to."""
    h = h.at[..., 0].set(5.0)
    router = router.at[0].set(0.0)
    if onto is not None:
        router = router.at[0, onto].set(10.0)
    if away is not None:
        router = router.at[0, away].set(-10.0)
    return h, router


# case -> the rung of ``GROUP_RUNGS`` its assignments take.
RUNG = {
    "every_slot_live": 2, "pads_and_idle_rows": 2, "every_token_crowds_one_expert": 2,
    "an_expert_nobody_chose": 2, "a_strict_share_held": 2, "a_strict_share_of_pads_and_idle_rows": 1,
    "a_tail_on_the_next_groups_rows": 1, "two_short_prompts_and_idle_rows": 0,
}


def _ridge_case(case):
    """4 x 96 = 384 slots, past the ridge of 256, softmax scoring -> (cfg, h,
    this layer's router, the expert stacks, live or None, experts held,
    live tokens)."""
    cfg = small()
    h, router, experts = _layer_inputs(cfg, seed=11, B=4, S=96)
    router, live, E, n_live = router[1], None, 8, 384
    if case.endswith("pads_and_idle_rows"):
        live, n_live = jnp.arange(96)[None, :] < jnp.asarray([90, 3, 0, 96])[:, None], 189
    if case == "two_short_prompts_and_idle_rows":
        live, n_live = jnp.arange(96)[None, :] < jnp.asarray([0, 17, 0, 6])[:, None], 23
    if case == "every_token_crowds_one_expert":
        h, router = _steered(h, router, onto=3)
    if case == "an_expert_nobody_chose":
        h, router = _steered(h, router, away=5)
    if case.startswith("a_strict_share"):
        cfg, E = dataclasses.replace(cfg, expert_first=2, experts_held=4), 4
        experts = {k: v[:, 2:6] for k, v in experts.items()}
    if case == "a_tail_on_the_next_groups_rows":
        # One row's 65 live tokens all choose expert 2; the last three of
        # them expert 3 beside it, the others expert 7. Expert 2's second
        # tile holds one row of its own, and its tail lies on expert 3's
        # three rows: the same tokens, under another expert's weight.
        live, n_live = jnp.arange(96)[None, :] < jnp.asarray([65, 0, 0, 0])[:, None], 65
        h, router = _steered(h, router, onto=2)
        h = h.at[..., 1].set(jnp.where(jnp.arange(96) >= 62, 5.0, -5.0))
        router = router.at[1].set(0.0).at[1, 3].set(10.0).at[1, 7].set(-10.0)
    return cfg, h, router, experts, live, E, n_live


def _count_switches(monkeypatch):
    took, switch = [], lax.switch
    monkeypatch.setattr(moe.lax, "switch", lambda i, *a: took.append(int(i)) or switch(i, *a))
    return took


@pytest.mark.parametrize("case", list(RUNG))
def test_past_the_ridge_the_grouped_form_computes_what_the_loop_does(case, monkeypatch):
    cfg, h, router, experts, live, E, n_live = _ridge_case(case)
    took = _count_switches(monkeypatch)
    grouped, loop = grouped_against_loop(cfg, h, router, experts, jnp.int32(1), live)
    # 768 (slot, choice) pairs: 48 sorted rows hold the assignments that
    # count here, or 192, or every pair has its row.
    assert took == [sum(grouped[:E].sum() > 768 // share for share in moe.GROUP_RUNGS[:-1])] == [RUNG[case]]
    if case.startswith("a_strict_share"):
        assert 0 < grouped[:E].sum() < 2 * n_live  # an assignment held elsewhere is in no group
    else:
        assert grouped[:E].sum() == 2 * n_live
    if case == "every_token_crowds_one_expert":
        assert grouped[3] == 384  # no capacity: six tiles of 64, and the other choice's
        assert grouped[E + 1] >= 384 + moe.GROUP_TILE
    if case == "an_expert_nobody_chose":
        assert grouped[5] == 0 and grouped[E] == 7
    if case == "a_tail_on_the_next_groups_rows":
        assert grouped[:E].tolist() == [0, 0, 65, 3, 0, 0, 0, 62] and grouped[E + 2] == 4


def _poisoned_tiles(monkeypatch):
    """``prefill_expert_tiles`` with NaN in every row of its result that no
    tile of the call wrote: what uninitialised memory may hold on the chip."""
    from mcpx.engine.kernels import routed_experts as kernel

    real = kernel.prefill_expert_tiles

    def poisoned(x, w_gate, w_up, w_down, step_e, step_row, tok, n_steps, layer, *, tile, **kw):
        ys = real(x, w_gate, w_up, w_down, step_e, step_row, tok, n_steps, layer, tile=tile, **kw)
        rows, live_step = jnp.arange(ys.shape[0]), jnp.arange(step_row.shape[0]) < n_steps
        wrote = (rows[:, None] >= step_row[None, :]) & (rows[:, None] < step_row[None, :] + tile) & live_step[None, :]
        return jnp.where(jnp.any(wrote, axis=1)[:, None], ys, jnp.nan)

    monkeypatch.setattr(kernel, "prefill_expert_tiles", poisoned)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(RUNG))
def test_past_the_ridge_the_tile_kernel_computes_what_the_tile_loop_does(case, dtype, monkeypatch):
    """The same cases through ``use_pallas`` (interpreted): ONE kernel call
    walks the jnp form's tiles, takes each one's 64 rows out of ``x`` itself
    and has no rung. Counters and choices equal exactly, every step counted
    as the kernel's; the outputs within the float32 sum's order (float32
    rows) or the kernel's one rounding of ``act(gate) * up`` against the
    CPU's three (bfloat16). What no tile wrote is NaN here: a row that counts
    nowhere is selected away, never multiplied by its weight of 0."""
    cfg, h, router, experts, live, E, _ = _ridge_case(case)
    h, router, experts = jax.tree.map(lambda a: a.astype(dtype), (h, router, experts))
    args = (router, experts, jnp.int32(1), cfg, live)
    out_j, stats_j, chosen_j = jax.jit(lambda h: moe_forward(h, *args))(h)
    took = _count_switches(monkeypatch)
    _poisoned_tiles(monkeypatch)
    out_k, stats_k, chosen_k = jax.jit(lambda h: moe_forward(h, *args, use_pallas=True, interpret=True))(h)
    assert took == []  # no rung: the kernel call is the one form
    out_j, out_k, stats_j, stats_k = (np.asarray(a) for a in (out_j, out_k, stats_j, stats_k))
    assert (np.asarray(chosen_k) == np.asarray(chosen_j)).all()
    assert stats_k[: E + 3].tolist() == stats_j[: E + 3].tolist()
    assert stats_j[E + 3] == 0 and stats_k[E + 3] == stats_k[E + 2] > 0
    assert np.isfinite(out_k).all() and np.abs(out_j).max() > 0
    if live is not None:
        assert (out_k[~np.asarray(live)] == 0).all()
    tol = 1e-5 if dtype == "float32" else 2.0**-7
    np.testing.assert_allclose(out_k, out_j, rtol=0, atol=tol * np.abs(out_j).max())


def _pallas_calls(jaxpr, inside=()):
    """[(the call's name, the primitives it lies inside)] of every kernel
    call a jaxpr holds, its loops' and branches' bodies included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], inside))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub, inside + (eqn.primitive.name,))
    return found


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names |= _primitives(sub)
    return names


@pytest.mark.parametrize("width", ["the_models_rows", "a_latents_rows"])
def test_past_the_ridge_the_kernel_path_is_one_call_and_no_rung(width):
    """What keeps the arms from coming back: with ``use_pallas`` the cohort
    prefill's 8 x 128 slots trace to exactly ONE kernel call, outside every
    ``switch`` / ``cond`` / loop, so its operands' shapes are the window's and
    the stacks' alone; nothing but its result has more than the window's T
    rows of the experts' row width (no sorted copy of a rung's rows, no
    float32 zero-fill, no gather-back of a row a pair), and that result has a
    row a pair plus the groups' alignment and a tile."""
    cfg = small()
    _, router, experts = _layer_inputs(cfg, B=1, S=1)
    T, k, E, C = 8 * 128, cfg.n_experts_per_tok, cfg.n_experts_held, moe.GROUP_TILE
    D = 48 if width == "a_latents_rows" else cfg.d_model
    if width == "a_latents_rows":
        experts = {"w_up": experts["w_up"][:, :, :D], "w_down": experts["w_down"][:, :, :, :D]}
    h = jax.ShapeDtypeStruct((8, 128, cfg.d_model), jnp.float32)
    rows = jax.ShapeDtypeStruct((8, 128, D), jnp.float32) if width == "a_latents_rows" else None
    live = jax.ShapeDtypeStruct((8, 128), jnp.bool_)
    traced = jax.make_jaxpr(
        lambda h, live, rows: moe_forward(
            h, router[0], experts, jnp.int32(0), cfg, live, rows=rows, use_pallas=True, interpret=True)
    )(h, live, rows).jaxpr
    assert _pallas_calls(traced) == [("prefill_expert_tiles", ())]
    assert "cond" not in _primitives(traced)  # no ``lax.switch`` over GROUP_RUNGS
    ys = T * k + E * (moe.TILE_ALIGN - 1) + C
    ys += -(ys - C) % moe.TILE_ALIGN
    wide = _rows_of_width(traced, D, skip=("pallas_call",))
    assert ys in wide and max(wide - {ys}) == T


def test_under_the_ridge_the_dense_prefill_takes_the_window_kernel():
    """``prefill(use_pallas=True)``: a cohort of one's 128-slot window takes
    the decode's kernel under the prefill's own name, one call a sparse layer
    (the scan's body holds it once), and no loop over the touched experts is
    left in the traced program; without ``use_pallas`` that loop is there."""
    cfg = small()
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens, lens = jax.ShapeDtypeStruct((1, 128), jnp.int32), jax.ShapeDtypeStruct((1,), jnp.int32)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 1, 128))

    def traced(**kw):
        return jax.make_jaxpr(
            lambda p, t, n, c: prefill(p, cfg, t, n, c, last_only=True, moe_stats=True, **kw)
        )(params, tokens, lens, cache).jaxpr

    kernel, loop = traced(use_pallas=True, interpret=True), traced()
    assert _pallas_calls(kernel) == [("prefill_expert_window", ("scan",))]
    assert "while" not in _primitives(kernel)
    assert _pallas_calls(loop) == [] and "while" in _primitives(loop)


def _rows_of_width(jaxpr, D, skip=()):
    """{rows} of every value a jaxpr binds that is ``D`` wide, its loops' and
    branches' bodies included (but the primitives in ``skip``)."""
    avals = [v.aval for v in jaxpr.invars]
    rows = set()
    for eqn in jaxpr.eqns:
        avals += [v.aval for v in eqn.outvars]
        if eqn.primitive.name not in skip:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                rows |= _rows_of_width(sub, D)
    return rows | {math.prod(a.shape[:-1]) for a in avals if getattr(a, "ndim", 0) >= 2 and a.shape[-1] == D}


@pytest.mark.parametrize("width", ["the_models_rows", "a_latents_rows"])
def test_past_the_ridge_only_the_widest_rung_has_a_row_a_slot_choice_pair(width):
    """The cohort prefill's 8 x 128 slots, top-2, so T x k = 2,048 pairs:
    outside the rungs nothing of the traced layer has more than the window's
    T rows of the experts' row width (the pairs exist as ints and weights),
    and a rung's sorted copy, its float32 twin and what it adds back have its
    own R (+ a tile's) rows: a sixteenth, a quarter, and only in the last
    every pair's."""
    cfg = small()
    _, router, experts = _layer_inputs(cfg, B=1, S=1)
    T, k, C, D = 8 * 128, cfg.n_experts_per_tok, moe.GROUP_TILE, 48 if width == "a_latents_rows" else cfg.d_model
    if width == "a_latents_rows":
        experts = {"w_up": experts["w_up"][:, :, :D], "w_down": experts["w_down"][:, :, :, :D]}
    h = jax.ShapeDtypeStruct((8, 128, cfg.d_model), jnp.float32)
    rows = jax.ShapeDtypeStruct((8, 128, D), jnp.float32) if width == "a_latents_rows" else None
    live = jax.ShapeDtypeStruct((8, 128), jnp.bool_)
    traced = jax.make_jaxpr(
        lambda h, live, rows: moe_forward(h, router[0], experts, jnp.int32(0), cfg, live, rows=rows)
    )(h, live, rows).jaxpr
    assert max(_rows_of_width(traced, D, skip=("cond",))) == T
    (switch,) = [e for e in traced.eqns if e.primitive.name == "cond"]
    rungs = [_rows_of_width(b.jaxpr, D) - {T} for b in switch.params["branches"]]
    assert [max(r) for r in rungs] == [T * k // share + C for share in moe.GROUP_RUNGS]
    assert all(C in r for r in rungs)  # a tile's rows, in and out


@pytest.mark.parametrize("slots, grouped", [
    ((8, 8), False), ((1, 128), False), ((2, 128), False), ((3, 96), True), ((8, 128), True),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else ("grouped" if v else "loop"))
def test_the_form_is_read_off_the_windows_width_alone(slots, grouped, monkeypatch):
    """A decode segment's 64 slots, a cohort of one's 128 and anything up to
    the ridge trace to the loop; past it, and so the cohort prefill's 1,024,
    to the grouped form. Nothing but B x S decides."""
    cfg = small()
    _, router, experts = _layer_inputs(cfg, B=1, S=1)
    took, real = [], moe._grouped_experts
    monkeypatch.setattr(moe, "_grouped_experts", lambda *a, **kw: took.append(a[1].shape) or real(*a, **kw))
    h = jax.ShapeDtypeStruct(slots + (cfg.d_model,), jnp.float32)
    out, stats, _ = jax.eval_shape(
        lambda h: moe_forward(h, router[0], experts, jnp.int32(0), cfg), h)
    assert took == ([(slots[0] * slots[1], cfg.d_model)] if grouped else [])
    assert out.shape == h.shape and stats.shape == (8 + moe.LAYER_STATS,)


# -------------------------------------------------------- the whole forward
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "kernel"])
def test_pads_and_idle_rows_change_no_live_rows_logits(use_pallas):
    """A decode window of S = 8 slots with ragged q_lens: what the pad slots
    and the idle row hold moves neither a live row's logits nor a counter."""
    cfg = small()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, T, lens = 3, 32, jnp.asarray([20, 9, 14])
    n_pages = 1 + B * 4  # four pages of 16 a row, page 0 the null page
    table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 384, (B, T)), jnp.int32)
    _, dense = prefill(params, cfg, toks, lens, init_kv_cache(cfg, B, T), last_only=True)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, n_pages, 16), dense, table, lens, 16)
    mesh = one_device()
    q_lens = jnp.asarray([3, 0, 8])

    def run(window_toks):
        return decode_chunk_paged(
            params, cfg, window_toks, lens, table, pools, use_pallas=use_pallas, interpret=True,
            mesh=mesh, logits_at=jnp.maximum(q_lens - 1, 0), q_lens=q_lens, moe_stats=True, routing=True,
        )

    w1 = jnp.asarray(rng.integers(0, 384, (B, 8)), jnp.int32)
    live = np.arange(8)[None, :] < np.asarray(q_lens)[:, None]
    w2 = jnp.where(live, w1, jnp.asarray(rng.integers(0, 384, (B, 8)), jnp.int32))
    (l1, _, s1, c1), (l2, _, s2, c2) = run(w1), run(w2)
    np.testing.assert_array_equal(np.asarray(l1)[[0, 2]], np.asarray(l2)[[0, 2]])
    assert np.asarray(s1).tolist() == np.asarray(s2).tolist()
    assert int(s1[:8].sum()) == 4 * 2 * int(q_lens.sum())  # layers x top-k x live tokens
    assert c1.shape == (4, B, 8, 2)
    assert (np.asarray(c1)[:, live] == np.asarray(c2)[:, live]).all()


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_prefill_then_paged_decode_through_one_period_matches_the_reference(block, mesh_shape):
    """Dense prefill committed to pages, then paged decode one token at a time
    through the windowed kernel (interpreted), over one period of the layer
    pattern (3 window layers of 8 + 1 full with YaRN), contexts 9-47: logits
    against the block's plain float32 reference under the step's routing. On
    the 2 x 2 mesh the expert leaves stay whole on every device."""
    reference = by_path("chip_harness_reference_t", os.path.join(CHIP_DIR, "reference.py"))
    from mcpx.models.gemma.params import load_or_init

    data, model = mesh_shape
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    cfg = dataclasses.replace(block.rehearsal_config(3072), n_kv_heads=4 if model > 1 else 2)
    params, _ = load_or_init(cfg, "", mesh)
    specs = param_pspecs(cfg, mesh)
    assert all(ax is None for k in ("router", "w_gate", "w_up", "w_down") for ax in specs["layers"][k])
    out = reference.compare_with_engine_step(
        block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 33, interpret=True,
        page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3,
    )
    assert out["ok"] and out["positions"] == 16, out
    assert min(out["prompt_lens"]) >= 9 and max(out["prompt_lens"]) + 3 > 8  # past the window of 8
    read = block.routing_readings(params, dataclasses.asdict(cfg))
    assert len(read) == 4 and max(r["distance"] for r in read) < block.DELTA
    assert sum(r["checked"] for r in read) == 4 * (sum(out["prompt_lens"]) + 4 * 3)


def test_the_window_is_really_applied_in_prefill_and_decode(block):
    """The same step with the window taken out of the program's config alone
    (the reference keeps it) fails the comparison: contexts pass 8."""
    reference = by_path("chip_harness_reference_t2", os.path.join(CHIP_DIR, "reference.py"))
    mesh = one_device()
    cfg = block.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    no_window = dataclasses.replace(cfg, layer_types=(), sliding_window=0)
    out = reference.compare_with_engine_step(
        block, params, no_window, dataclasses.asdict(cfg), mesh, seed=2**31 + 33, interpret=True,
        page_size=16, rows=2, pages_per_row=4, prefill_len=48, n_decode=2,
    )
    assert not out["ok"]


def test_the_configuration_file_keeps_every_published_width():
    with open(os.path.join(CHIP_DIR, "configs", "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"Mellum2-12B-A2.5B-Instruct"' in l)
    published = row["config"] if row else {
        "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896, "sliding_window": 1024,
    }
    changed = {k for k, v in published.items() if config.get(k) != v}
    assert changed == ({"num_hidden_layers", "vocab_size"} if row else set())
    assert set(config["reduced"]) == {"num_hidden_layers", "vocab_size", "max_batch_size",
                                      "max_pages_per_seq", "max_decode_len", "warmup_max_len"}
    assert config["num_hidden_layers"] == 12 and config["num_hidden_layers"] % 4 == 0
