"""The AFMoE block (Trinity-Mini): leading dense layers before sparse ones
with a shared expert, a sigmoid router whose bias enters the choice alone, a
gated, QK-normed attention that rotates only in its window layers, a norm on
each branch's output. CPU, small sizes; the plain reference is the
benchmark's block module (``benchmarks/chip/models/afmoe.py``), imported by
path, and the comparison is the one that decides a benchmark run's
``correct`` (``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_afmoe_rehearsal.py`` beside the rehearsal child."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma import moe
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import (
    apply_rope, feed_forward_residual, gated_mlp, init_kv_cache, layer_kinds, prefill,
)
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, grouped_against_loop, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_afmoe_t", os.path.join(CHIP_DIR, "models", "afmoe.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared."""
    base = dict(
        vocab_size=384, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128,
        layer_types=PERIOD * 2, sliding_window=8, rope_full_layers=False, qk_norm=True,
        attn_gate=True, post_norms=True, n_experts=8, n_experts_per_tok=2, d_expert=32,
        n_dense_layers=2, d_shared_expert=32, router_scoring="sigmoid", router_bias_scale=0.1,
        router_scale=2.826, activation="silu", tie_embeddings=False, norm_plus_one=False,
        dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


# ------------------------------------------------------------ configuration
def test_the_tree_has_two_stacks_and_the_count_is_the_trees():
    cfg = small()
    params = params_of(cfg)
    assert set(params) == {"embed", "dense_layers", "layers", "final_norm", "head"}
    dense, sparse = params["dense_layers"], params["layers"]
    assert dense["w_gate"].shape == (2, 64, 128) and sparse["w_gate"].shape == (6, 8, 64, 32)
    assert sparse["router_bias"].shape == (6, 8) and sparse["router_bias"].dtype == jnp.float32
    assert sparse["shared_down"].shape == (6, 32, 64) and dense["q_norm"].shape == (2, 32)
    assert "router" not in dense and dense["w_attn_gate"].shape[1:] == sparse["w_attn_gate"].shape[1:]
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert held == cfg.n_params
    # a token reads 2 of the 8 routed experts of each of the 6 sparse layers, and the shared one
    assert cfg.n_params - cfg.n_active_params == 6 * 6 * 3 * 64 * 32
    share = dataclasses.replace(cfg, expert_first=2, experts_held=2)
    assert cfg.n_params - share.n_params == 6 * 6 * 3 * 64 * 32
    # the bias is drawn at its stated scale, and differently in every layer
    b = np.asarray(sparse["router_bias"])
    assert 0.05 < b.std() < 0.15 and not np.allclose(b[0], b[1])
    # the two stacks' attention leaves are not one draw cut in two
    assert not np.allclose(np.asarray(dense["wq"][0]), np.asarray(sparse["wq"][0]))


def test_published_counts_of_trinity_mini():
    """The configuration file's arithmetic, from the published widths."""
    cfg = GemmaConfig(
        vocab_size=3072, d_model=2048, n_layers=8, n_heads=32, n_kv_heads=4, head_dim=128,
        d_ff=6144, layer_types=PERIOD * 2, sliding_window=2048, rope_full_layers=False,
        qk_norm=True, attn_gate=True, post_norms=True, n_experts=128, n_experts_per_tok=8,
        d_expert=1024, n_dense_layers=2, d_shared_expert=1024, router_scoring="sigmoid",
        router_bias_scale=0.1, router_scale=2.826, activation="silu", tie_embeddings=False,
        norm_plus_one=False,
    )
    assert cfg.n_params == 2 * 65_020_160 + 6 * 839_131_520 + 2 * 3072 * 2048 + 2048 == 5_177_414_400
    assert cfg.n_active_params == cfg.n_params - 6 * 120 * 6_291_456
    half = dataclasses.replace(cfg, experts_held=64)
    assert cfg.n_params - half.n_params == 6 * 64 * 6_291_456


@pytest.mark.parametrize("bad", [
    dict(n_dense_layers=8),
    dict(router_scoring="tanh"),
    dict(router_scoring="softmax"),  # with a bias and a scale: sigmoid's
    dict(n_experts=0, n_experts_per_tok=0, d_expert=0),  # a dense lead and a shared expert of nothing
], ids=["no_sparse_layer", "unknown_scoring", "softmax_with_bias", "lead_without_experts"])
def test_a_configuration_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


def test_a_full_layer_is_not_rotated_and_a_window_layer_is():
    cfg = small()
    inv_freq, factor = cfg.rope_tables()
    assert inv_freq.shape == (8, 16) and (factor == 1).all()
    assert (inv_freq[[3, 7]] == 0).all() and (inv_freq[[0, 1, 2, 4, 5, 6]] > 0).all()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4, 32), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(5) + 7, (2, 5))
    full = {k: v[3] for k, v in layer_kinds(cfg).items()}
    window = {k: v[0] for k, v in layer_kinds(cfg, 0, 2).items()}
    np.testing.assert_array_equal(np.asarray(apply_rope(x, positions, cfg.rope_theta, full)), np.asarray(x))
    assert not np.allclose(np.asarray(apply_rope(x, positions, cfg.rope_theta, window), np.float32),
                           np.asarray(x, np.float32))
    assert GemmaConfig().rope_tables() is None  # the default block still carries no table


@pytest.mark.parametrize("feature, cfg_json", [
    ("quantize", {"model": {"quantize": "int8"}}),
    ("speculative", {"engine": {"speculative": {"enabled": True}, "hetero_batch": True}}),
])
def test_what_the_block_does_not_do_yet_is_an_error_at_construction(feature, cfg_json):
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match="departs from the default"):
        InferenceEngine(MCPXConfig.from_dict(cfg_json), model_cfg=small())
    # each new part alone is a departure too, with no expert anywhere
    dense = dict(vocab_size=384, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128)
    for part in (dict(qk_norm=True), dict(attn_gate=True), dict(post_norms=True)):
        assert not GemmaConfig(**dense, **part).is_default_block
    assert GemmaConfig(**dense).is_default_block


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_has_a_spec_and_the_new_ones_stay_whole(mesh_shape):
    from mcpx.models.gemma.params import load_or_init

    data, model = mesh_shape
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    cfg = small(n_kv_heads=4)
    specs = param_pspecs(cfg, mesh)
    params, _ = load_or_init(cfg, "", mesh)
    assert jax.tree.structure(params) == jax.tree.structure(jax.tree.map(lambda a: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))
    for name in ("q_norm", "k_norm", "w_attn_gate", "post_attn_norm", "post_mlp_norm", "router",
                 "router_bias", "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down"):
        assert all(ax is None for ax in specs["layers"][name]), name
    assert specs["dense_layers"]["wq"] == specs["layers"]["wq"]
    # the same bits whatever the mesh
    alone = params_of(cfg)
    for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ the router
def test_the_bias_chooses_and_weighs_nothing():
    """chosen = the k largest of sigmoid(x Wr) + b; weights = the chosen
    SCORES over their sum, times the scale: the bias is in none of them. At
    this scale the bias changes a stated share of the choices."""
    cfg = small()
    x = jax.random.normal(jax.random.PRNGKey(2), (256, 64), jnp.float32)
    params = params_of(cfg)["layers"]
    router, bias = params["router"][1], params["router_bias"][1]
    chosen, w = moe.route(x, router, cfg, bias)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(router, np.float64))))
    pick = s + np.asarray(bias, np.float64)
    order = np.argsort(-pick, axis=-1)
    gap = np.take_along_axis(pick, order[:, 1:2], 1) - np.take_along_axis(pick, order[:, 2:3], 1)
    clear = gap[:, 0] > 1e-5
    assert clear.mean() > 0.95
    assert (np.sort(np.asarray(chosen), -1) == np.sort(order[:, :2], -1))[clear].all()
    top = np.take_along_axis(s, np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(w), 2.826 * top / top.sum(-1, keepdims=True), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, rtol=1e-5)
    unbiased, _ = moe.route(x, router, cfg, None)
    changed = (np.sort(np.asarray(unbiased), -1) != np.sort(np.asarray(chosen), -1)).any(-1).mean()
    assert 0.15 < changed < 0.7, changed


@pytest.mark.parametrize("case", [
    "every_slot_live", "pads_and_idle_rows", "the_bias_crowds_one_expert",
    "the_bias_keeps_every_token_off_one", "a_strict_share_held",
])
def test_past_the_ridge_the_grouped_form_computes_what_the_loop_does(case):
    """4 x 96 = 384 slots, past the ridge of 256: sigmoid scoring, the bias
    in the choice alone, so a bias of +-10 steers every token's choice and
    weighs nothing."""
    cfg = small()
    layers = params_of(cfg)["layers"]
    experts = {k: layers[k] for k in moe.EXPERT_LEAVES}
    router, bias = layers["router"][2], layers["router_bias"][2]
    h = jax.random.normal(jax.random.PRNGKey(12), (4, 96, 64), jnp.float32)
    live, E = None, 8
    if case == "pads_and_idle_rows":
        live = jnp.arange(96)[None, :] < jnp.asarray([90, 3, 0, 96])[:, None]
    if case == "the_bias_crowds_one_expert":
        bias = bias.at[3].set(10.0)
    if case == "the_bias_keeps_every_token_off_one":
        bias = bias.at[5].set(-10.0)
    if case == "a_strict_share_held":
        cfg, E = dataclasses.replace(cfg, expert_first=2, experts_held=4), 4
        experts = {k: v[:, 2:6] for k, v in experts.items()}
    grouped, _ = grouped_against_loop(cfg, h, router, experts, jnp.int32(2), live, bias)
    n_live = 384 if live is None else 189
    if case == "a_strict_share_held":
        assert 0 < grouped[:E].sum() < 2 * n_live
    else:
        assert grouped[:E].sum() == 2 * n_live
    if case == "the_bias_crowds_one_expert":
        assert grouped[3] == 384 and grouped[E + 1] >= 384 + moe.GROUP_TILE
    if case == "the_bias_keeps_every_token_off_one":
        assert grouped[5] == 0 and grouped[E] == 7


def _ff_branch(cfg, lp, experts, h):
    """The feed-forward branch of sparse layer 1 at ``h`` (no norm on it)."""
    x = jnp.zeros_like(h)
    lp = {**lp, "pre_mlp_norm": jnp.ones_like(lp["pre_mlp_norm"])}
    out, stats, chosen = feed_forward_residual(
        x + h, lp, cfg, moe=(experts, jnp.int32(1), None))
    return np.asarray(out - h), np.asarray(stats), np.asarray(chosen)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """model-configs section 4: the 8 routed experts held 2 + 2 + 4 by three
    shares, each routing over all 8 and each computing the shared expert:
    the three partial results, less the shared expert's twice, sum to the
    uncut layer's; the counters add up as they are."""
    cfg = small(post_norms=False)
    layers = params_of(cfg)["layers"]
    lp = {k: v[1] for k, v in layers.items() if k not in moe.EXPERT_LEAVES}
    experts = {k: layers[k] for k in moe.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 64), jnp.float32)
    whole, stats, chosen = _ff_branch(cfg, lp, experts, h)
    n = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + cfg.norm_eps)
    shared = np.asarray(gated_mlp(n, lp["shared_gate"], lp["shared_up"], lp["shared_down"], cfg))
    assert np.abs(shared).max() > 0
    parts, counts = [], []
    for first, held in ((0, 2), (2, 2), (4, 4)):
        share = dataclasses.replace(cfg, expert_first=first, experts_held=held)
        mine = {k: v[:, first : first + held] for k, v in experts.items()}
        out, st, ch = _ff_branch(share, lp, mine, h)
        assert (ch == chosen).all()  # every share routes over all 8
        assert np.abs(out - shared).max() > 0  # and has a routed part of its own
        parts.append(out)
        counts.append(st[:held])
    np.testing.assert_allclose(sum(parts) - 2 * shared, whole, rtol=1e-4, atol=1e-5)
    assert np.concatenate(counts).tolist() == stats[:8].tolist()


def test_weight_bytes_are_the_leaves_a_forward_reads():
    cfg = small(dtype="bfloat16")
    params = params_of(cfg)
    expert, rest = moe.forward_weight_bytes(cfg, params)
    assert expert == 3 * 64 * 32 * 2
    every = sum(a.nbytes for a in jax.tree.leaves(params))
    assert rest == every - 6 * 8 * expert - params["embed"].nbytes  # untied: the table is gathered by row
    assert rest > params["head"].nbytes + sum(a.nbytes for a in jax.tree.leaves(params["dense_layers"]))


# ------------------------------------------- the served path, at every length
def test_the_engine_serves_the_same_tokens_at_every_segment_length():
    """The pacer asks for 4, 8, 12 or 16 forwards a segment (whole ticks of 4
    up to the window): the same greedy, grammar-constrained requests, with
    budgets that retire rows mid-segment, decode byte-identical tokens at
    each length on one engine of this block, and nothing compiles between
    them. Every expert held has a sample on the per-expert counter from the
    weights' binding on, before a token is routed. The admission prefills'
    expert counters come back in the harvest's own fetch: the worker makes
    one ``device_get`` a harvested segment, as before them."""
    import asyncio
    import threading

    from mcpx.engine.engine import InferenceEngine
    from mcpx.engine.pacing import SegmentPacer

    class Fixed(SegmentPacer):
        def __init__(self, n):
            super().__init__()
            self.n, self.lengths = n, []

        def window(self, tick, ceiling):
            return min(ceiling, self.n)

        def dispatched(self, t0, t1, forwards):
            self.lengths.append(forwards)
            super().dispatched(t0, t1, forwards)

    config = MCPXConfig.from_dict({
        "model": {"max_seq_len": 256},
        "engine": {"max_batch_size": 4, "max_decode_len": 40, "kv_page_size": 16, "max_pages_per_seq": 16,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": False,
                   "warmup_compile": True, "warmup_max_len": 64},
    })

    fetches, device_get = [], jax.device_get

    def counted(tree):
        fetches.append(threading.current_thread().name)
        return device_get(tree)

    async def go():
        eng = InferenceEngine(config, model_cfg=small(max_seq_len=256))
        await eng.start()
        jax.device_get = counted
        try:
            samples = eng.metrics.moe_expert_tokens._metrics
            assert len(samples) == 8 and all(c._value.get() == 0 for c in samples.values())
            prompts = [eng.tokenizer.encode(f"Length parity.\nintent {i}: compose. JSON:") for i in range(5)]
            budgets = [3, 38, 9, 21, 14]

            async def serve():
                rs = await asyncio.gather(*(
                    eng.generate(p, max_new_tokens=b, constrained=True, temperature=0.0)
                    for p, b in zip(prompts, budgets)))
                return [r.token_ids for r in rs]

            compiles = lambda: {name: e["compiles"] for name, e in
                                eng.costs.snapshot(materialize=False)["executables"].items()}
            snap, got = compiles(), {}
            for n in (4, 8, 12, 16):
                pacer = eng._pacer = Fixed(n)
                got[n] = await serve()
                assert set(pacer.lengths) == {n} and compiles() == snap, (n, pacer.lengths)
            assert all(got[4]) and got[4] == got[8] == got[12] == got[16]
            assert sum(c._value.get() for c in samples.values()) > 0
            # 20 prompts were admitted: 2 experts a live token in each of 6
            # sparse layers, and under the ridge every slot of a cohort's
            # window by every touched expert.
            totals = eng._layer_kind_totals
            assert totals["moe_prefill_assignments"] == 2 * 6 * 4 * sum(map(len, prompts))
            assert totals["moe_prefill_rows"] > totals["moe_prefill_assignments"]
            for _ in range(200):  # the worker harvests the last segment in its own time
                if not eng._inflight:
                    break
                await asyncio.sleep(0.05)
            assert not eng._inflight and not eng._prefill_moe
            assert fetches.count("mcpx-engine") == eng._dispatch_seq > 0
        finally:
            jax.device_get = device_get
            await eng.aclose()

    asyncio.run(go())


# --------------------------------------- the programs that were there before
PERIOD4 = dict(
    vocab_size=384, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128,
    rope_theta=500000.0, layer_types=PERIOD, sliding_window=8, yarn_factor=16.0,
    yarn_original_max_pos=64, yarn_attention_factor=1.2772588722239782,
    n_experts=8, n_experts_per_tok=2, d_expert=32, activation="silu",
    tie_embeddings=False, scale_embeddings=False, norm_plus_one=False, dtype="float32",
)
# (layer scans in decode_chunk_paged's jaxpr, sha256[:16] of its logits, of
# the K pool, of the V pool), recorded at the parent commit (acd5feb, before
# any field of this block existed) by the function below.
PINNED = {
    "test": (GemmaConfig.named("test"), (1, "4c0d664216684567", "246f0baf5423a5f6", "dd29ffa422938f40")),
    "mellum": (GemmaConfig(**PERIOD4), (1, "2bac6acfa6e7bd52", "95ce76eb0f8c5298", "8672c372aafb4f7e")),
}


def _pinned_step(cfg):
    params = params_of(cfg)
    B, T, lens = 3, 32, jnp.asarray([20, 9, 14])
    table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    rng = np.random.default_rng(36)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    _, dense = prefill(params, cfg, toks, lens, init_kv_cache(cfg, B, T), last_only=True)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, 1 + B * 4, 16), dense, table, lens, 16)
    q_lens = jnp.asarray([3, 0, 8])
    window = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 8)), jnp.int32)

    def fn(p, w, kv):
        return decode_chunk_paged(p, cfg, w, lens, table, kv, use_pallas=False,
                                  logits_at=jnp.maximum(q_lens - 1, 0), q_lens=q_lens)

    jaxpr = jax.make_jaxpr(fn)(params, window, pools)
    scans = sum(e.primitive.name == "scan" for e in jaxpr.jaxpr.eqns)
    logits, kv = jax.jit(fn)(params, window, pools)
    digest = lambda a: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
    return scans, digest(logits), digest(kv["k"]), digest(kv["v"])


@pytest.mark.parametrize("name", list(PINNED))
def test_a_configuration_at_the_defaults_traces_to_the_program_it_always_did(name):
    """The fields this block added default to what was there: one layer scan,
    and logits and pools bit for bit the parent commit's."""
    cfg, pinned = PINNED[name]
    assert _pinned_step(cfg) == pinned


def test_the_mixed_block_runs_one_scan_a_stack():
    assert _pinned_step(small())[0] == 2


# -------------------------------------------------- the configuration file
def test_the_configuration_file_keeps_every_published_width(block):
    with open(os.path.join(CHIP_DIR, "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = None
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"Trinity-Mini"' in l)
    published = row["config"] if row else {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 6144, "num_experts": 128, "num_experts_per_tok": 8,
        "moe_intermediate_size": 1024, "num_shared_experts": 1, "num_dense_layers": 2,
        "sliding_window": 2048, "route_scale": 2.826,
    }
    changed = {k for k, v in published.items() if k not in config or config[k] != v}
    assert changed == ({"num_hidden_layers", "vocab_size"} if row else set())
    assert set(config["reduced"]) == {"num_hidden_layers", "vocab_size", "max_batch_size",
                                      "max_pages_per_seq", "max_decode_len", "warmup_max_len"}
    # the regime is the other one-chip cells': the pacer chooses the segment's length
    assert config["mcpx"]["engine"] == {"warmup_compile": True, "temperature": 0.0}
    model_keys = {k: v for k, v in config.items() if k not in (
        "name", "source", "module", "chips", "mesh", "slab_rows", "mcpx", "reduced", "assumed",
        "departures", "params", "max_batch_size", "max_pages_per_seq", "max_decode_len", "warmup_max_len")}
    cfg = block.model_config(model_keys, 3072)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_sparse_layers) == (8, 2, 6)
    assert cfg.layer_types == PERIOD * 2 and cfg.d_shared_expert == 1024 and cfg.n_experts_held == 128
    assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norms, cfg.rope_full_layers) == (True, True, True, False)
    assert "5.177 B" in config["params"] and round(cfg.n_params / 1e9, 3) == 5.177
    assert "0.648 B" in config["params"] and round(cfg.n_active_params / 1e9, 3) == 0.648
    with pytest.raises(ValueError, match="consumed by nothing"):
        block.model_config({**model_keys, "attention_bias": False}, 3072)
    with pytest.raises(ValueError, match="has no other"):
        block.model_config({**model_keys, "score_func": "softmax"}, 3072)
