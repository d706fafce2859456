"""The latent-attention block (A.X-K1): a cache of one latent and one rotated
key a token, written once and read two ways (expanded in the dense prefill,
absorbed against the pages), beside a sigmoid router of which this device
holds a share, a shared expert and a dense lead. CPU, small sizes; the plain
reference is the benchmark's block module (``benchmarks/chip/models/mla.py``),
imported by path, and the comparison is the one that decides a benchmark
run's ``correct`` (``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_mla_rehearsal.py`` beside the rehearsal child."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
from mcpx.engine.paged_decode import _kv_window, _write_kv_window
from mcpx.models.gemma import moe
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import feed_forward_residual, gated_mlp, init_kv_cache
from mcpx.parallel.mesh import kv_cache_pspecs, make_mesh, param_pspecs
from mcpx.telemetry.costs import model_cost
from tests.helpers import by_path, compiled, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
jit_prefill, jit_chunk = compiled()  # one executable a (configuration, route, shapes): tests/helpers.py


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_mla_t", os.path.join(CHIP_DIR, "models", "mla.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared:
    one dense layer, then three sparse ones holding experts 4..7 of 16."""
    base = dict(
        vocab_size=384, d_model=64, n_layers=4, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128,
        attention="latent", q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        yarn_factor=32.0, yarn_original_max_pos=16, attn_score_factor=1.8133,
        n_experts=16, n_experts_per_tok=2, d_expert=32, expert_first=4, experts_held=4,
        n_dense_layers=1, d_shared_expert=32, router_scoring="sigmoid", router_scale=2.5,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
        dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


def published(**kw):
    """A.X-K1 as ``configs/a.x-k1.json`` cuts it, by the program's own fields."""
    base = dict(
        vocab_size=3072, d_model=7168, n_layers=8, n_heads=64, n_kv_heads=1, head_dim=128,
        d_ff=18432, attention="latent", q_lora_rank=1536, kv_lora_rank=512, qk_rope_head_dim=64,
        v_head_dim=128, yarn_factor=32.0, yarn_original_max_pos=4096, attn_score_factor=1.8133,
        n_experts=192, n_experts_per_tok=8, d_expert=2048, experts_held=12, n_dense_layers=1,
        d_shared_expert=2048, router_scoring="sigmoid", router_scale=2.5, activation="silu",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )
    return GemmaConfig(**{**base, **kw})


# ------------------------------------------------------------ configuration
def test_published_counts_of_a_x_k1():
    """ISSUE 42's arithmetic, from the published widths."""
    cfg = published()
    attention = 7168 * 1536 + 1536 + 1536 * 12288 + 7168 * 576 + 512 + 512 * 16384 + 8192 * 7168
    expert, router, norms = 3 * 7168 * 2048, 7168 * 192, 2 * 7168
    assert attention == 101_124_096 and expert == 44_040_192
    sparse = attention + norms + expert + router + 12 * expert
    dense = attention + norms + 3 * 7168 * 18432
    assert (sparse, dense) == (675_037_184, 497_500_160)
    assert cfg.n_params == dense + 7 * sparse + 2 * 3072 * 7168 + 7168 == 5_266_807_808
    assert round(cfg.n_params * 2 / 1e9, 2) == 10.53
    whole = dataclasses.replace(cfg, experts_held=0)
    assert whole.n_params - cfg.n_params == 7 * 180 * expert  # a layer whole: 8.60 B
    assert round((sparse + 180 * expert) / 1e9, 2) == 8.60
    # a token reads its 8 experts where they are all held
    assert cfg.n_params - cfg.n_active_params == 7 * 4 * expert
    assert model_cost(cfg) == {"params_held": cfg.n_params,
                               "params_active_per_token": cfg.n_active_params,
                               "flops_per_token": 2 * cfg.n_active_params}
    # the cache: 576 values a token a layer, held in a 512 and a 128-lane pool
    assert cfg.kv_bytes_per_token == 8 * 1152 and cfg.kv_widths == (128, 512) and cfg.kernel_lanes_ok
    pools = jax.eval_shape(lambda: init_paged_kv(cfg, 8 * 128 + 1, 16))
    assert pools["k"].shape == (1, 8, 1025, 16, 128) and pools["v"].shape == (1, 8, 1025, 16, 512)
    useful = 1025 * 16 * cfg.kv_bytes_per_token
    assert round(useful / 1e6) == 151 and round(sum(a.size * 2 for a in pools.values()) / 1e6) == 168
    # the expanded keys (128 + 64) and values (128) of its 64 heads would be 40,960 B a token a layer
    assert 64 * (cfg.head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim) * 2 == 40_960
    assert 40_960 // (cfg.kv_bytes_per_token // 8) == 35
    # YaRN over the 64 rotated values only, cos and sin unscaled
    inv_freq, factor = cfg.rope_tables()
    assert inv_freq.shape == (8, 32) and (factor == 1.0).all()
    assert inv_freq[0, 0] == 1.0 and np.isclose(inv_freq[0, -1], 10000.0 ** (-62 / 64) / 32)


def test_the_tree_is_the_counts_and_weight_bytes_are_the_leaves_a_forward_reads():
    cfg = small(dtype="bfloat16")
    params = params_of(cfg)
    assert set(params["layers"]) >= {"w_dq", "q_lora_norm", "w_uq", "w_dkv", "kv_lora_norm", "w_ukv", "wo"}
    assert not {"wq", "wk", "wv"} & set(params["layers"])
    assert params["layers"]["w_uq"].shape == (3, 24, 4, 24) and params["layers"]["w_dkv"].shape == (3, 64, 40)
    assert params["dense_layers"]["w_ukv"].shape == (1, 32, 4, 32) and params["layers"]["wo"].shape == (3, 4, 16, 64)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) == cfg.n_params
    expert, rest = moe.forward_weight_bytes(cfg, params)
    assert expert == 3 * 64 * 32 * 2
    every = sum(a.nbytes for a in jax.tree.leaves(params))
    assert rest == every - 3 * 4 * expert - params["embed"].nbytes
    attention = sum(params["layers"][k][0].nbytes for k in
                    ("w_dq", "q_lora_norm", "w_uq", "w_dkv", "kv_lora_norm", "w_ukv", "wo"))
    assert attention == 2 * (64 * 24 + 24 + 24 * 4 * 24 + 64 * 40 + 32 + 32 * 4 * 32 + 64 * 64)
    # the query's expansion is drawn for unit-variance scores under the block's scale
    w_uq = np.asarray(params["layers"]["w_uq"], np.float32)
    assert np.isclose(w_uq.std() * np.sqrt(24) * cfg.attn_score_factor, 1.0, rtol=0.05)


@pytest.mark.parametrize("bad", [
    dict(attention="ring"),
    dict(kv_lora_rank=0),
    dict(qk_rope_head_dim=7),
    dict(n_kv_heads=2, n_heads=4),
    dict(qk_norm=True),
    dict(attention="heads"),  # the ranks without the kind
], ids=["unknown_kind", "no_latent", "odd_rope", "kv_heads", "qk_norm", "ranks_alone"])
def test_a_configuration_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


@pytest.mark.parametrize("feature, cfg_json", [
    ("quantize", {"model": {"quantize": "int8"}}),
    ("speculative", {"engine": {"speculative": {"enabled": True}, "hetero_batch": True}}),
])
def test_what_the_block_does_not_do_yet_is_an_error_at_construction(feature, cfg_json):
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match="departs from the default"):
        InferenceEngine(MCPXConfig.from_dict(cfg_json), model_cfg=small())
    dense = dict(vocab_size=384, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128)
    latent = dict(attention="latent", q_lora_rank=8, kv_lora_rank=8, qk_rope_head_dim=4, v_head_dim=8)
    assert not GemmaConfig(**dense, **latent).is_default_block and GemmaConfig(**dense).is_default_block


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_and_the_pools_have_a_spec(mesh_shape):
    from mcpx.models.gemma.params import load_or_init

    data, model_axis = mesh_shape
    mesh = make_mesh(data=data, model=model_axis, devices=jax.devices()[: data * model_axis])
    cfg = small()
    specs = param_pspecs(cfg, mesh)
    params, _ = load_or_init(cfg, "", mesh)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.tree.map(lambda a: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))
    for name in ("w_dq", "q_lora_norm", "w_dkv", "kv_lora_norm"):  # the bottlenecks stay whole
        assert all(ax is None for ax in specs["layers"][name]), name
    heads = "model" if model_axis > 1 else None
    assert specs["layers"]["w_uq"][2] == specs["layers"]["w_ukv"][2] == specs["layers"]["wo"][1] == heads
    assert specs["dense_layers"]["w_uq"] == specs["layers"]["w_uq"]
    # a latent has no head axis: the cache is whole over model, rows over data
    assert all(spec[3] is None for spec in kv_cache_pspecs(cfg, mesh, 4).values())
    alone = params_of(cfg)
    for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ the two forms
def _prefilled(cfg, params, seq, lens, pages=4):
    B, T = seq.shape
    table = jnp.asarray(1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))
    padded = jnp.where(jnp.arange(T)[None] < lens[:, None], seq, 0)
    last, dense = jit_prefill(params, cfg, padded, lens, init_kv_cache(cfg, B, T), last_only=True)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, 1 + B * pages, 16), dense, table, lens, 16)
    return last, dense, pools, table


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_absorbed_against_pages_is_expanded_against_the_dense_cache(path):
    """The same positions through both forms: a whole expanded forward over
    each sequence, and the prompt's expanded prefill committed to latent
    pages followed by ABSORBED paged windows (one token, then a ragged window
    of up to five) through the kernel and through its jnp reference."""
    cfg = small()
    params = params_of(cfg)
    rng = np.random.default_rng(42)
    B, T = 3, 48
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([20, 9, 33])
    whole, _ = jit_prefill(params, cfg, seq, jnp.full((B,), T), init_kv_cache(cfg, B, T))
    last, _, pools, table = _prefilled(cfg, params, seq, lens)
    rows = jnp.arange(B)
    np.testing.assert_allclose(last, whole[rows, lens - 1], rtol=1e-4, atol=1e-4)
    mesh = one_device()
    step = dict(use_pallas=path == "kernel", interpret=True, mesh=mesh)
    logits, pools = jit_chunk(
        params, cfg, seq[rows, lens][:, None], lens, table, pools,
        q_lens=jnp.ones((B,), jnp.int32), **step)
    np.testing.assert_allclose(logits[:, 0], whole[rows, lens], rtol=1e-4, atol=1e-4)
    q_lens = jnp.asarray([5, 0, 3])
    window = jnp.stack([seq[b, int(lens[b]) + 1 : int(lens[b]) + 6] for b in range(B)])
    logits, _ = jit_chunk(params, cfg, window, lens + 1, table, pools, q_lens=q_lens, **step)
    for b, n in enumerate([5, 0, 3]):
        start = int(lens[b]) + 1
        np.testing.assert_allclose(logits[b, :n], whole[b, start : start + n], rtol=1e-4, atol=1e-4)


def test_latent_pages_written_both_ways_read_back_equal():
    """A token's cache row is written once, by the prefill's commit or by the
    paged window's write: the same prompt through each leaves the same pages."""
    cfg = small()
    params = params_of(cfg)
    rng = np.random.default_rng(3)
    B, T = 2, 32
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([32, 32])
    _, dense, committed, table = _prefilled(cfg, params, seq, lens, pages=3)
    assert committed["k"].shape[-1] == 128 and committed["v"].shape[-1] == 32
    assert not np.asarray(committed["k"][..., 8:]).any()  # the rotated key's row is padded with zeros
    empty = init_paged_kv(cfg, 1 + B * 3, 16)
    window = _kv_window(jnp.zeros((B,), jnp.int32), table, T, 16, empty["k"].shape[2])
    for name in ("k", "v"):
        pool = empty[name]
        for layer in range(cfg.n_layers):
            pool = _write_kv_window(pool, jnp.int32(layer), dense[name][layer], window)
        np.testing.assert_array_equal(np.asarray(pool[:, :, 1:5]), np.asarray(committed[name][:, :, 1:5]))
    # and the paged forward's own write: a window forward over empty pages
    mesh = one_device()
    _, written = jit_chunk(params, cfg, seq, jnp.zeros((B,), jnp.int32), table, empty,
                           q_lens=lens, use_pallas=False, mesh=mesh)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(written[name][:, :, 1:5]),
                                   np.asarray(committed[name][:, :, 1:5]), rtol=2e-4, atol=2e-5)


def test_the_suffix_prefill_route_over_latent_pages_equals_a_whole_prefill():
    """The radix cache's route: a prompt's first 16 tokens resident as latent
    pages (another request's prefill), its suffix prefilled as one ragged
    window against them through the kernel: the last logits and the pages
    are a whole prefill's."""
    cfg = small()
    params = params_of(cfg)
    rng = np.random.default_rng(9)
    B, T = 2, 48
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([41, 30])
    want, _, whole_pools, table = _prefilled(cfg, params, seq, lens)
    _, _, pools, _ = _prefilled(cfg, params, seq, jnp.asarray([16, 16]))
    mesh = one_device()
    suffix = jnp.where(jnp.arange(32)[None] < (lens - 16)[:, None], seq[:, 16:], 0)
    got, pools = jit_chunk(
        params, cfg, suffix, jnp.full((B,), 16), table, pools, use_pallas=True, interpret=True,
        mesh=mesh, logits_at=lens - 17, q_lens=lens - 16)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for b, n in enumerate([41, 30]):
        for name in ("k", "v"):
            a = np.asarray(pools[name][0][:, table[b]]).reshape(cfg.n_layers, -1, pools[name].shape[-1])
            w = np.asarray(whole_pools[name][0][:, table[b]]).reshape(a.shape)
            np.testing.assert_allclose(a[:, :n], w[:, :n], rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------ the router's share
def _ff_branch(cfg, lp, experts, h):
    """The feed-forward branch of sparse layer 1 at ``h`` (no norm on it)."""
    lp = {**lp, "pre_mlp_norm": jnp.ones_like(lp["pre_mlp_norm"])}
    out, stats, chosen = feed_forward_residual(h, lp, cfg, moe=(experts, jnp.int32(1), None))
    return np.asarray(out - h), np.asarray(stats), np.asarray(chosen)


def test_four_shares_of_sixteen_experts_add_up_with_the_shared_expert_counted_once():
    """model-configs section 4: 16 experts in 4 shares of 4, each routing over
    all 16 and each computing the shared expert: the four partial results,
    less the shared expert's three times, sum to the uncut layer's."""
    cfg = small(expert_first=0, experts_held=0)
    layers = params_of(cfg)["layers"]
    lp = {k: v[1] for k, v in layers.items() if k not in moe.EXPERT_LEAVES}
    experts = {k: layers[k] for k in moe.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 64), jnp.float32)
    whole, stats, chosen = _ff_branch(cfg, lp, experts, h)
    n = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + cfg.norm_eps)
    shared = np.asarray(gated_mlp(n, lp["shared_gate"], lp["shared_up"], lp["shared_down"], cfg))
    assert np.abs(shared).max() > 0
    parts, counts = [], []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, expert_first=first, experts_held=4)
        mine = {k: v[:, first : first + 4] for k, v in experts.items()}
        out, st, ch = _ff_branch(share, lp, mine, h)
        assert (ch == chosen).all()  # every share routes over all 16
        parts.append(out)
        counts.append(st[:4])
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=1e-4, atol=1e-5)
    assert np.concatenate(counts).tolist() == stats[:16].tolist() and stats[:16].sum() == 15 * 2
    # the weights: the 2 chosen scores renormalised, times 2.5, no bias anywhere
    _, w = moe.route(n.reshape(15, 64), lp["router"], cfg)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-5)
    assert "router_bias" not in lp


def test_a_forward_counts_what_it_routed_and_what_its_attention_read():
    """The forward's own counters after the layers' (``moe.add_forward_stats``)."""
    cfg = small()
    params = params_of(cfg)
    rng = np.random.default_rng(1)
    B, T = 2, 32
    seq = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([20, 9])
    _, _, stats = jit_prefill(params, cfg, seq, lens, init_kv_cache(cfg, B, T), last_only=True, moe_stats=True)
    held = cfg.n_experts_held
    own = held + moe.LAYER_STATS
    assert stats.shape == (own + moe.FORWARD_STATS + moe.LATENT_STATS,)
    # the expanded prefill reads no page: no slot of the absorbed kernel's
    assert stats[own:].tolist() == [29 * 2 * 3, 29 * 4, 2 * 4, 0, 0, 0]
    assert 0 < int(stats[:held].sum()) < 29 * 2 * 3  # this share's part of the routing
    _, _, pools, table = _prefilled(cfg, params, seq, lens)
    mesh = one_device()
    _, _, stats = jit_chunk(
        params, cfg, seq[:, :8], lens, table, pools, use_pallas=False, mesh=mesh,
        q_lens=jnp.asarray([3, 0]), moe_stats=True)
    # the idle row reads and routes nothing; the live row's 3 queries take the rung of 4 slots
    # and fetch ONE key block a layer, a part of one: no run
    assert stats[own:].tolist() == [3 * 2 * 3, 23 * 4, 1 * 4, 4 * 4, 1 * 4, 0]


def test_the_block_module_reads_the_published_keys(block):
    import json

    with open(os.path.join(CHIP_DIR, "configs", "a.x-k1.json")) as f:
        config = json.load(f)
    harness = ("name", "source", "module", "chips", "mesh", "slab_rows", "mcpx", "reduced", "assumed",
               "departures", "params", "max_batch_size", "max_pages_per_seq", "max_decode_len", "warmup_max_len")
    keys = {k: v for k, v in config.items() if k not in harness}
    cfg = block.model_config(keys, 3072)
    assert cfg == published(attn_score_factor=cfg.attn_score_factor, max_seq_len=131072)
    assert np.isclose(cfg.attn_score_factor ** 0.5, 1.3466, atol=1e-4)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_first) == (192, 12, 0)
    with pytest.raises(ValueError, match="consumed by nothing"):
        block.model_config({**keys, "index_topk": 2048}, 3072)
    with pytest.raises(ValueError, match="has no other"):
        block.model_config({**keys, "topk_method": "noaux_tc"}, 3072)
    with pytest.raises(ValueError, match="mscale"):
        block.model_config({**keys, "rope_scaling": {**keys["rope_scaling"], "mscale": 0.707}}, 3072)


# ------------------------------------------- the served path, at every length
def test_the_engine_serves_the_same_tokens_at_every_segment_length():
    """The pacer asks for 4, 8, 12 or 16 forwards a segment: the same greedy,
    grammar-constrained requests decode byte-identical tokens at each length
    on one engine of this block, nothing compiles between them, and the
    segments' attributes count what the latent kernel read and what this
    share of the experts was routed."""
    import asyncio

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

    async def go():
        eng = InferenceEngine(config, model_cfg=small(max_seq_len=256))
        await eng.start()
        try:
            assert eng.pallas_paths()["enabled"] is True
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
            for _ in range(200):  # the worker harvests the last segment in its own time
                if not eng._inflight:
                    break
                await asyncio.sleep(0.05)
            totals = eng._layer_kind_totals
            assert totals["attn_row_calls"] > 0 and totals["attn_row_calls"] % cfg_layers == 0
            ctx = totals["attn_ctx_tokens"] / totals["attn_row_calls"]
            assert min(map(len, prompts)) < ctx < max(map(len, prompts)) + 40
            assert totals["kv_bytes_read"] == totals["attn_ctx_tokens"] * (32 + 8) * 4
            # this share holds 4 of the 16 experts: some of the routing, not all
            assert 0 < totals["moe_assignments"] < totals["moe_tokens_routed"]
            assert totals["moe_tokens_routed"] % (2 * 3) == 0
        finally:
            await eng.aclose()

    cfg_layers = 4
    asyncio.run(go())


def test_the_segments_count_the_key_blocks_fetched_and_those_fetched_as_runs():
    """``attn_key_blocks`` / ``attn_run_blocks`` are what the engine's page
    tables imply: a row of 17..20 pages fetches two key blocks a layer a
    forward, the first whole; off a fresh pool its pages ascend (share 1/2:
    every WHOLE block is a run), off a pool whose free pages are the odd ids
    alone no block is a run, and the tokens are the same (a page id enters no
    arithmetic). A direct forward over a table of whole blocks reads share 1,
    the same pages shuffled 0."""
    import asyncio

    from mcpx.engine.engine import InferenceEngine

    cfg = small(max_seq_len=512)
    config = MCPXConfig.from_dict({
        "model": {"max_seq_len": 512},
        "engine": {"max_batch_size": 2, "max_decode_len": 16, "kv_page_size": 16, "max_pages_per_seq": 32,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": False,
                   "warmup_compile": False},
    })

    async def go():
        eng = InferenceEngine(config, model_cfg=cfg)
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("Key blocks of sixteen pages. " * 40)[:280]
            assert len(prompt) == 280

            async def serve():
                before = dict(eng._layer_kind_totals)
                r = await eng.generate(prompt, max_new_tokens=12, constrained=True, temperature=0.0)
                for _ in range(200):  # the worker harvests the last segment in its own time
                    if not eng._inflight:
                        break
                    await asyncio.sleep(0.05)
                now = eng._layer_kind_totals
                return r.token_ids, {k: now[k] - before.get(k, 0) for k in now}

            tokens, fresh = await serve()
            assert fresh["attn_row_calls"] > 0
            assert fresh["attn_key_blocks"] == 2 * fresh["attn_row_calls"]
            assert fresh["attn_run_blocks"] == fresh["attn_row_calls"]  # the whole block of the two
            alloc = eng._allocator
            singles = [alloc.allocate(("frag", i), 1)[0] for i in range(alloc.stats().free_pages)]
            for i, page in enumerate(singles):
                if page % 2:
                    alloc.free(("frag", i))
            again, odd = await serve()
            assert again == tokens
            assert odd["attn_key_blocks"] == fresh["attn_key_blocks"] and odd["attn_run_blocks"] == 0
            # their Prometheus twins, over both servings
            assert eng.metrics.attn_key_blocks._value.get() == 2 * fresh["attn_key_blocks"]
            assert eng.metrics.attn_run_blocks._value.get() == fresh["attn_run_blocks"]
            text = eng.metrics.render().decode()
            assert "mcpx_engine_attn_key_blocks_total" in text and "mcpx_engine_attn_run_blocks_total" in text
        finally:
            await eng.aclose()

    asyncio.run(go())

    # one forward over whole blocks: every block a run, or none
    B, S, pages = 2, 8, 32
    params = params_of(cfg)
    pools = init_paged_kv(cfg, B * pages + 1, 16)
    mesh = one_device()
    ids = 1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages)
    shuffled = np.random.default_rng(0).permuted(ids, axis=1)
    first = cfg.n_experts_held + moe.LAYER_STATS + moe.FORWARD_STATS
    for table, share in ((ids, 1.0), (shuffled, 0.0)):
        out = jit_chunk(
            params, cfg, jnp.ones((B, S), jnp.int32), jnp.full((B,), pages * 16 - S, jnp.int32),
            jnp.asarray(table), pools, use_pallas=True, interpret=True, mesh=mesh,
            q_lens=jnp.asarray([S, 1], jnp.int32), moe_stats=True,
        )
        slots, blocks, runs = (int(c) for c in out[2][first : first + moe.LATENT_STATS])
        # both rows read through their 32nd page: two whole blocks a row a layer
        assert (blocks, runs) == (4 * cfg.n_layers, round(4 * cfg.n_layers * share)) and slots > 0


def test_spilled_latent_pages_come_back_as_they_left():
    """The host tier moves page runs of BOTH pools (their widths differ) to
    host RAM and back: generations served from re-admitted latent pages are
    byte-identical to a fresh engine's, and the snapshot's meta names the
    pools' widths."""
    import asyncio

    from mcpx.engine.engine import InferenceEngine

    def config(tier):
        return MCPXConfig.from_dict({
            "model": {"max_seq_len": 256},
            "engine": {"max_batch_size": 4, "max_pages_per_seq": 16, "kv_page_size": 16,
                       "max_decode_len": 16, "prefix_cache_entries": 64,
                       "kv_tier": {"enabled": tier, "host_mb": 64.0}},
        })

    async def go():
        eng = InferenceEngine(config(True), model_cfg=small(max_seq_len=256))
        ref = InferenceEngine(config(False), model_cfg=small(max_seq_len=256))
        await eng.start()
        await ref.start()
        try:
            assert eng._snapshot_meta()["kv_widths"] == [128, 32]
            prompts = [eng.tokenizer.encode(f"latent probe {i}: " + "wxyz " * 28)[:128] for i in range(8)]
            outs = {}
            for rnd in range(2):
                for i, p in enumerate(prompts):
                    r = await eng.generate(p, max_new_tokens=8, constrained=False, temperature=0.0)
                    outs[(rnd, i)] = r.token_ids
            tier = eng.prefix_cache_stats()["tier"]
            assert tier["spills"] > 0 and tier["readmits"] > 0
            for i, p in enumerate(prompts):
                r = await ref.generate(p, max_new_tokens=8, constrained=False, temperature=0.0)
                assert outs[(0, i)] == outs[(1, i)] == r.token_ids, i
            eng._prefix_cache.check_invariants()
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()
            await ref.aclose()

    asyncio.run(go())
