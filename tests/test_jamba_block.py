"""The Jamba block at one expert (AI21-Jamba2-3B): layers that are a Mamba-1
SELECTIVE SCAN or unrotated multi-query attention, each followed by the dense
feed-forward; a recurrent state a row beside the KV pages that moves by exactly
what a decode window accepted; the walk over the layers SCANNED over each run
of like layers. CPU, small sizes, the scan's kernel interpreted AND the jnp
walk in lockstep; the plain reference is the benchmark's block module
(``benchmarks/chip/models/jamba.py``), imported by path, and the comparison is
the one that decides a benchmark run's ``correct``
(``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_jamba_rehearsal.py`` beside the rehearsal child."""

import asyncio
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import (
    commit_prefill_to_pages, init_paged_kv, init_state_pool, write_prefill_state,
)
from mcpx.engine.paged_decode import decode_chunk_paged, keep_window
from mcpx.models.gemma import model as gemma_model
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, init_params, pattern_runs, prefill
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, compiled, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
W = 8  # the decode window's slots
jit_prefill, jit_chunk = compiled()  # one executable a (configuration, route, shapes): tests/helpers.py


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_jamba_t", os.path.join(CHIP_DIR, "models", "jamba.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared: 5
    query heads on ONE KV head (a group that is no power of two)."""
    base = dict(
        vocab_size=384, d_model=64, n_layers=4, n_heads=5, n_kv_heads=1, head_dim=32, d_ff=128,
        norm_eps=1e-6, max_seq_len=256, layer_pattern="JJQJ", mamba_expand=2, mamba_dt_rank=8,
        ssm_state_size=16, conv_kernel=4, activation="silu", rope_full_layers=False,
        tie_embeddings=True, scale_embeddings=False, norm_plus_one=False, dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


def _small_params():
    cfg = small()
    return cfg, params_of(cfg)


# ------------------------------------------------------------ configuration
def test_the_tree_has_two_stacks_and_the_count_is_the_trees():
    cfg, params = _small_params()
    j, q = params["scan_layers"], params["attn_layers"]
    I, N, R = 128, 16, 8
    assert j["w_in"].shape == (3, 64, 2 * I) and j["conv_w"].shape == (3, I, 4) and j["conv_b"].shape == (3, I)
    assert j["w_x"].shape == (3, I, R + 2 * N) and j["w_dt"].shape == (3, R, I) and j["w_out"].shape == (3, I, 64)
    assert j["dt_norm"].shape == (3, R) and j["b_norm"].shape == j["c_norm"].shape == (3, N)
    # the state's N on the leading axis, the channels on the last: the pool's layout
    assert j["A_log"].shape == (3, N, I) and j["D_skip"].shape == j["dt_bias"].shape == (3, I)
    assert j["A_log"].dtype == j["dt_bias"].dtype == j["D_skip"].dtype == jnp.float32
    assert q["wq"].shape == (1, 64, 160) and q["wk"].shape == q["wv"].shape == (1, 64, 32)
    for stack, n in ((j, 3), (q, 1)):  # every layer its two norms and the dense feed-forward
        assert stack["w_gate"].shape == (n, 64, 128) and stack["w_down"].shape == (n, 128, 64)
        assert stack["norm"].shape == stack["mlp_norm"].shape == (n, 64)
    assert "head" not in params  # tied
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == cfg.n_params == cfg.n_active_params
    # the family's draw: A = 1..16 a channel, D 1, steps log-uniform in [0.001, 0.1]
    np.testing.assert_allclose(np.exp(np.asarray(j["A_log"][1, :, 7])), np.arange(1, 17), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(j["dt_bias"]))
    assert 0.00099 < step.min() and step.max() < 0.1001 and (np.asarray(j["D_skip"]) == 1).all()
    assert float(jnp.abs(j["conv_w"]).max()) <= 0.5 and not np.asarray(j["conv_b"]).any()


def test_published_counts_of_jamba2_3b(block):
    """The arithmetic that says the structure is right: a Mamba layer is
    104,161,472 parameters (its mixer 41,241,792), an attention layer
    76,682,240; the published 28 layers and vocabulary come to the model's own
    name, 3B; nothing is cut but the vocabulary: 2,869,429,632 held."""
    with open(os.path.join(CHIP_DIR, "configs", "jamba2-3b.json")) as f:
        config = json.load(f)
    harness = {"name", "source", "module", "chips", "mesh", "slab_rows", "mcpx", "reduced",
               "assumed", "departures", "params", "max_batch_size", "max_pages_per_seq",
               "max_decode_len", "warmup_max_len"}
    keys = {k: v for k, v in config.items() if k not in harness}
    cut = block.model_config(keys, 3072)
    assert cut.n_params == 2_869_429_632 and cut.n_layers == 28
    assert cut.layer_pattern == "J" * 7 + "Q" + "J" * 13 + "Q" + "J" * 6
    assert pattern_runs(cut) == [("J", 0, 7), ("Q", 0, 1), ("J", 7, 20), ("Q", 1, 2), ("J", 20, 26)]
    assert (cut.n_scan_layers, cut.n_attn_layers, cut.n_recurrent_layers) == (26, 2, 26)
    assert (cut.n_heads, cut.n_kv_heads, cut.head_dim, cut.q_per_kv) == (20, 1, 128, 20)
    assert cut.scan_inner == 5120 and cut.ssm_slot_bytes == 16 * 5120 * 4 == 327_680
    assert cut.kv_bytes_per_token == 2 * 2 * 128 * 2 == 1024  # 1 KB a token: the architecture's point
    assert not cut.suffix_route and not cut.head_state and not cut.page_state
    one = lambda pattern: dataclasses.replace(cut, n_layers=len(pattern), layer_pattern=pattern)
    base = 3072 * 2560 + 2560
    assert one("J").n_params - base == 104_161_472 == 41_241_792 + 3 * 2560 * 8192 + 5120
    assert one("Q").n_params - base == 76_682_240
    assert dataclasses.replace(cut, vocab_size=65536).n_params == 3_029_337_472  # 3.029 B published
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"AI21-Jamba2-3B"' in l)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key == "vocab_size" or config[key] == value, key  # no width, and no depth, changed


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="JJXJ"),  # an unknown letter
    dict(layer_pattern="JJQ"),  # one letter a layer
    dict(layer_pattern="JJSJ"),  # another alphabet's letter
    dict(mamba_dt_rank=0),
    dict(mamba_expand=0),
    dict(ssm_state_size=0),
    dict(conv_kernel=1),
    dict(rope_full_layers=True),  # the pattern's attention is unrotated
    dict(qk_norm=True),
    dict(attn_gate=True),
    dict(norm_plus_one=True),
    dict(scale_embeddings=True),
    dict(n_experts=4, n_experts_per_tok=2, d_expert=32),
    dict(mamba_n_heads=8),
])
def test_a_pattern_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


def test_the_new_fields_belong_to_the_pattern():
    for field in (dict(mamba_dt_rank=8), dict(mamba_expand=2)):
        with pytest.raises(ConfigError):
            GemmaConfig(**field)
        with pytest.raises(ConfigError):  # nor to another alphabet
            GemmaConfig(layer_pattern="CA", n_layers=2, norm_plus_one=False, **field)


@pytest.mark.parametrize("feature, cfg_json", [
    ("hetero_batch", {"engine": {"hetero_batch": True}}),
    ("kv_tier", {"engine": {"kv_tier": {"enabled": True, "host_mb": 8}}}),
    ("kv_tier", {"engine": {"kv_tier": {"snapshot_path": "/tmp/never-written.snap"}}}),
    ("int8", {"model": {"quantize": "int8"}}),
    ("speculative", {"engine": {"hetero_batch": False, "speculative": {"enabled": True}}}),
])
def test_what_does_not_carry_the_state_is_an_error_at_construction(feature, cfg_json):
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match=feature):
        InferenceEngine(MCPXConfig.from_dict(cfg_json), model_cfg=small())


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_has_a_spec_and_the_pool_its_shapes(mesh_shape):
    cfg, params = _small_params()
    data, model = mesh_shape
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    specs = param_pspecs(cfg, mesh)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, dict))
    )
    sharded = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the fourth kind: no tuple of per-layer dicts, every array stacked a layer
    pool = init_state_pool(cfg, 5, W)
    assert set(pool) == {"ssm", "conv", "dt", "pre", "x", "b", "n"}
    assert pool["ssm"].shape == (3, 5, 16, 128) and pool["conv"].shape == (3, 5, 3, 128)
    assert pool["dt"].shape == pool["pre"].shape == pool["x"].shape == (3, 5, W, 128)
    assert pool["b"].shape == (3, 5, W, 16) and pool["n"].shape == (5,)
    assert all(a.dtype == jnp.float32 for k, a in pool.items() if k != "n")
    assert pool["ssm"][0, 0].nbytes == cfg.ssm_slot_bytes


# --------------------------------------------------------------- the kernel
def _scan_operands(rng, B, T, I, N):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, I)))
    return jax.nn.softplus(f(B, T, I) - 2.0), f(B, T, I), f(B, T, N), f(B, T, N), a_log


@pytest.mark.parametrize("T, lens", [(40, [40, 17, 0]), (600, [600, 300, 5]), (16, [1, 16, 9])])
def test_the_prefill_form_walks_each_row_to_its_length(T, lens):
    """``selective_scan_prefill`` (interpreted) against the jnp walk: one
    time block and several, a row that ends inside a group, on a block's edge
    and a padding row of length 0; a pad slot's ``y`` is finite."""
    from mcpx.engine.kernels.selective_scan import selective_scan_prefill
    from mcpx.models.gemma.ssm import selective_walk

    B, I, N = 3, 64, 16
    dt, x, b, c, a_log = _scan_operands(np.random.default_rng(T), B, T, I, N)
    lens = jnp.asarray(lens, jnp.int32)
    live = jnp.arange(T)[None, :, None] < lens[:, None, None]
    dt = jnp.where(live, dt, 0.0)
    want_y, want_h = selective_walk(jnp.zeros((B, N, I)), dt, x, b, c, a_log)
    got_y, got_h = selective_scan_prefill(dt, x, b, c, a_log, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.where(live, got_y, 0)), np.asarray(jnp.where(live, want_y, 0)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h), atol=2e-5)
    assert np.isfinite(np.asarray(got_y)).all() and not np.asarray(got_h[lens == 0]).any()


@pytest.mark.parametrize("q", [[0, 3, 0, 8, 0], [2, 2, 2, 2, 2], [0, 0, 0, 0, 1], [0, 0, 0, 0, 0]])
def test_the_window_form_moves_the_pool_as_the_jnp_walk_does(q):
    """``selective_scan_window`` (interpreted) on a pool of three layers, the
    second one's states moved, the layer a TRACED number: live rows in any
    order of slots, idle rows first, last and between, no live row at all. NaN
    planted in every slot no live row owns stays where it was and reaches
    nothing; an idle row's ``y`` is zeros."""
    from mcpx.engine.kernels.selective_scan import selective_scan_window
    from mcpx.models.gemma.ssm import selective_walk

    rng = np.random.default_rng(2)
    B, S, I, N, n_slots = 5, 8, 64, 16, 9
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    slots = jnp.asarray([7, 2, 0, 5, 3], jnp.int32)
    q = jnp.asarray(q, jnp.int32)
    kept = jnp.asarray([2, 0, 8, 5, 1], jnp.int32)
    live = np.asarray(q) > 0
    pool = f(3, n_slots, N, I)
    owned = set(np.asarray(slots)[live].tolist())
    for s in range(n_slots):
        if s not in owned:
            pool = pool.at[1, s].set(jnp.nan)
    p_dt, p_x, p_b, _, a_log = _scan_operands(rng, B, W, I, N)
    p_dt = jnp.where(jnp.arange(W)[None, :, None] < kept[:, None, None], p_dt, 0.0)
    dt, x, b, c, _ = _scan_operands(rng, B, S, I, N)
    dt = jnp.where(jnp.arange(S)[None, :, None] < q[:, None, None], dt, 0.0)
    run = jax.jit(functools.partial(selective_scan_window, interpret=True))
    got_pool, got_y = run(pool, jnp.asarray(1), slots, q, p_dt, p_x, p_b, dt, x, b, c, a_log)
    _, h = selective_walk(pool[1, slots], p_dt, p_x, p_b, None, a_log)
    want_y, _ = selective_walk(h, dt, x, b, c, a_log)
    got_pool, got_y = np.asarray(got_pool), np.asarray(got_y)
    for r in range(B):
        if live[r]:
            np.testing.assert_allclose(got_y[r], np.asarray(want_y[r]), atol=2e-5)
            np.testing.assert_allclose(got_pool[1, int(slots[r])], np.asarray(h[r]), atol=2e-5)
        else:
            assert not got_y[r].any()
    np.testing.assert_array_equal(got_pool[[0, 2]], np.asarray(pool)[[0, 2]])
    others = [s for s in range(n_slots) if s not in owned]
    assert np.isnan(got_pool[1, others]).all()  # as planted: bit-unchanged


def test_the_kernels_blocks_are_whole_lane_widths_that_divide_the_channels():
    from mcpx.engine.kernels.selective_scan import M_BLOCK, _blocking

    assert _blocking(5120) == M_BLOCK == 512 and _blocking(640) == 128 and _blocking(1536) == 512
    assert _blocking(64) == 64  # narrower than a lane width: the tests' sizes


def test_one_kv_head_under_a_group_that_is_no_power_of_two_through_the_ragged_kernel():
    """``K`` = 1 with ``G`` = 5 and 20 (no cell had one KV head under a group
    that is not 1, 4, 8 or 16): the ragged kernel, interpreted, against the
    jnp gather, uneven live widths and an idle row."""
    from mcpx.engine.kernels.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference,
    )

    rng = np.random.default_rng(3)
    B, S, K, hd, L, psz, p_max = 3, 8, 1, 32, 2, 16, 4
    n_pages = 1 + B * p_max
    table = jnp.asarray(1 + np.arange(B * p_max, dtype=np.int32).reshape(B, p_max))
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    k_all, v_all = f(K, L, n_pages, psz, hd), f(K, L, n_pages, psz, hd)
    pos, q_lens = jnp.asarray([20, 3, 40], jnp.int32), jnp.asarray([8, 0, 3], jnp.int32)
    for G in (5, 20):
        qg = f(B, S, K, G, hd)
        got = ragged_paged_attention(qg, k_all, v_all, table, pos, q_lens, 1, interpret=True)
        want = ragged_paged_attention_reference(qg, k_all, v_all, table, pos, q_lens, 1, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        assert float(jnp.abs(want[0]).max()) > 0.1 and not np.asarray(got[1]).any()


# ------------------------------------------------------------ the scanned walk
SCAN_PATTERN = "QJJQJJJQJQ"  # attention first, last and between; J runs of 2, 3 and 1


def test_the_scanned_walk_is_the_layers_walked_one_by_one(monkeypatch):
    """Runs of 1, 2 and 3 like layers, attention first, last and between: the
    dense prefill and a paged window, each run ONE ``lax.scan`` over its rows,
    give what the same body gives walked a layer at a time with its row a
    static number; and a run is one loop in the traced program, not one body a
    layer."""
    cfg = small(n_layers=len(SCAN_PATTERN), layer_pattern=SCAN_PATTERN)
    assert [(k, hi - lo) for k, lo, hi in pattern_runs(cfg)] == [
        ("Q", 1), ("J", 2), ("Q", 1), ("J", 3), ("Q", 1), ("J", 1), ("Q", 1)]
    params = init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    B, T = 2, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + W)), jnp.int32)
    lens = jnp.asarray([32, 19])

    def both():
        logits, dense = prefill(params, cfg, toks[:, :T], lens, init_kv_cache(cfg, B, T))
        table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
        pools = commit_prefill_to_pages(init_paged_kv(cfg, 1 + B * 4, 16), dense, table, lens, 16)
        pools["state"] = write_prefill_state(init_state_pool(cfg, B, W), jnp.arange(B), dense["ssm"])
        window = jnp.stack([jax.lax.dynamic_slice(toks[b], (lens[b],), (W,)) for b in range(B)])
        out, pools = decode_chunk_paged(params, cfg, window, lens, table, pools, use_pallas=False,
                                        q_lens=jnp.asarray([8, 5]))
        return logits, dense["ssm"], out, pools["state"]["ssm"]

    scanned = both()
    traced = jax.make_jaxpr(lambda t: prefill(params, cfg, t, lens, init_kv_cache(cfg, B, T))[0])(toks[:, :T])

    def loops(jaxpr):  # the scans of the program itself, not those inside a scan's body
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                n += 1
            else:
                n += sum(loops(getattr(sub, "jaxpr", sub)) for sub in jax.core.jaxprs_in_params(eqn.params))
        return n

    assert loops(traced.jaxpr) == 7  # a scan a RUN (10 layers)

    def walked_one_by_one(cfg, bodies, carry):  # ``walk_runs`` as a Python loop, each row a static number
        out = {kind: [] for kind in bodies}
        for kind, lo, hi in pattern_runs(cfg):
            each = []
            for j in range(lo, hi):
                carry, y = bodies[kind](carry, jnp.asarray(j, jnp.int32))
                each.append(y)
            out[kind].append(jax.tree.map(lambda *a: jnp.stack(a), *each))
        return carry, out

    import mcpx.engine.paged_decode as paged

    monkeypatch.setattr(gemma_model, "walk_runs", walked_one_by_one)
    monkeypatch.setattr(paged, "walk_runs", walked_one_by_one)
    one_by_one = both()
    unrolled = jax.make_jaxpr(lambda t: prefill(params, cfg, t, lens, init_kv_cache(cfg, B, T))[0])(toks[:, :T])
    assert loops(unrolled.jaxpr) == 6  # (the walk's own loops gone: what is left is a J layer's walk over time)
    for got, want in zip(jax.tree.leaves(scanned), jax.tree.leaves(one_by_one)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(scanned[0]).max()) > 0.1


# ------------------------------------------ the state, at the model's level
def _prefilled(cfg, params, toks, lens, T, n_slots, use_pallas=False):
    B = toks.shape[0]
    last, dense = jit_prefill(params, cfg, toks[:, :T], lens, init_kv_cache(cfg, B, T), last_only=True,
                              use_pallas=use_pallas, interpret=True)
    table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    pools = commit_prefill_to_pages(init_paged_kv(cfg, 1 + B * 4, 16), dense, table, lens, 16)
    pools["state"] = write_prefill_state(init_state_pool(cfg, n_slots, W), jnp.arange(B), dense["ssm"])
    return last, pools, table, dense


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_a_padded_prefills_state_is_the_unpadded_ones(path):
    """A prefill at a bucket of a shorter prompt gives the state AT the
    prompt's length (a pad position has dt 0; the tail is taken at the
    length), on the kernel route and the jnp route."""
    cfg, params = _small_params()
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 48)), jnp.int32)
    lens = [20, 16, 37]
    kw = dict(last_only=True, use_pallas=path == "kernel", interpret=True)
    _, padded = jit_prefill(params, cfg, toks, jnp.asarray(lens), init_kv_cache(cfg, 3, 48), **kw)
    assert padded["ssm"][0].shape == (3, 3, 16, 128) and padded["ssm"][1].shape == (3, 3, 3, 128)
    for b, n in enumerate(lens):
        _, alone = jit_prefill(params, cfg, toks[b : b + 1, :n], jnp.asarray([n]), init_kv_cache(cfg, 1, n), **kw)
        for got, want in zip(padded["ssm"], alone["ssm"]):
            np.testing.assert_allclose(np.asarray(got[:, b]), np.asarray(want[:, 0]), atol=1e-5)
        assert float(jnp.abs(alone["ssm"][0]).max()) > 1e-3


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_windows_with_rejected_proposals_equal_token_by_token_decode(path):
    """Decode windows ``[the token, proposals]`` of which a row keeps 0..8 (the
    rest WRONG tokens), uneven ``q_lens``, an idle row: every kept position's
    logits are the whole sequence's own (the dense forward over all of it),
    window after window, so the state moved by what was kept and by nothing
    else. An idle row's slots, and the slots no row owns, are bit-unchanged."""
    cfg, params = _small_params()
    rng = np.random.default_rng(0)
    B, T = 3, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 64)), jnp.int32)
    lens = jnp.asarray([20, 9, 14])
    full, _ = jit_prefill(params, cfg, toks, jnp.asarray([64] * B), init_kv_cache(cfg, B, 64))
    last, pools, table, _ = _prefilled(cfg, params, toks, lens, T, B + 2, use_pallas=path == "kernel")
    for b in range(B):
        np.testing.assert_allclose(np.asarray(last[b]), np.asarray(full[b, lens[b] - 1]), atol=2e-4)
    mesh = one_device()
    step = functools.partial(jit_chunk, use_pallas=path == "kernel", interpret=True, mesh=mesh)
    pos = lens
    plan = [([3, 0, 8], [1, 0, 5]), ([8, 4, 1], [8, 2, 1]), ([5, 5, 5], [1, 1, 1]), ([0, 8, 2], [0, 3, 2])]
    for q, keep in plan:
        q, keep = jnp.asarray(q), jnp.asarray(keep)
        window = jnp.stack([jax.lax.dynamic_slice(toks[b], (pos[b],), (W,)) for b in range(B)])
        wrong = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, W)), jnp.int32)
        window = jnp.where(jnp.arange(W)[None, :] < keep[:, None], window, wrong)
        before = jax.tree.map(np.asarray, pools["state"])
        logits, pools = step(params, cfg, window, pos, table, pools, q_lens=q)
        for b in range(B):
            for s in range(int(keep[b])):
                np.testing.assert_allclose(
                    np.asarray(logits[b, s]), np.asarray(full[b, pos[b] + s]), atol=3e-4
                )
        after = jax.tree.map(np.asarray, pools["state"])
        idle = [b for b in range(B) if int(q[b]) == 0] + [B, B + 1]
        for name in ("ssm", "conv", "dt", "pre", "x", "b"):  # [layers, slots, ...]
            np.testing.assert_array_equal(before[name][:, idle], after[name][:, idle])
        pools["state"] = keep_window(pools["state"], jnp.arange(B), keep, q > 0)
        pos = pos + keep


def test_a_window_wider_than_the_pending_one_has_no_route():
    cfg, params = _small_params()
    toks = jnp.zeros((2, 64), jnp.int32)
    lens = jnp.asarray([16, 11])
    _, pools, table, _ = _prefilled(cfg, params, toks, lens, 16, 2)
    with pytest.raises(ValueError, match="the state pool keeps 8 pending"):
        jit_chunk(params, cfg, toks[:, :32], lens, table, pools, use_pallas=False,
                  q_lens=jnp.asarray([24, 19]))


def test_a_missing_inner_norm_is_seen(block):
    """The float32 block against the reference, to rounding: what a missing
    inner norm, a swapped ``x | z`` or a forgotten skip moves by orders of
    magnitude more (the gain on B doubled here: 0.02 and up)."""
    cfg, params = _small_params()
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 40)), jnp.int32)
    got, _ = jit_prefill(params, cfg, toks, jnp.asarray([40]), init_kv_cache(cfg, 1, 40))
    dims = dataclasses.asdict(cfg)
    want = block._reference(params, dims, toks[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-4)
    bent = {**params, "scan_layers": {**params["scan_layers"], "b_norm": params["scan_layers"]["b_norm"] * 2}}
    assert float(jnp.abs(block._reference(bent, dims, toks[0]) - want).max()) > 0.02


# ------------------------------------------- the served path, at every length
def _engine_config(**engine):
    return MCPXConfig.from_dict({
        "model": {"max_seq_len": 256},
        "engine": {"max_batch_size": 4, "max_decode_len": 40, "kv_page_size": 16, "max_pages_per_seq": 16,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": False,
                   "warmup_compile": True, "warmup_max_len": 64, **engine},
    })


PROMPTS = [f"Length parity.\nintent {i}: compose. JSON:" for i in range(5)]
BUDGETS = [3, 38, 9, 21, 14]


async def _serve(eng, prompts=PROMPTS, budgets=BUDGETS):
    ids = [eng.tokenizer.encode(p) for p in prompts]
    rs = await asyncio.gather(*(
        eng.generate(p, max_new_tokens=b, constrained=True, temperature=0.0) for p, b in zip(ids, budgets)))
    return [r.token_ids for r in rs]


def _at_every_length(config, one_device=True):
    """The tokens one engine serves at 4, 8, 12 and 16 forwards a segment
    (equal, and nothing compiled between), and its lifetime counters."""
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

    async def go():
        mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1]) if one_device else None
        eng = InferenceEngine(config, model_cfg=small(), mesh=mesh)
        await eng.start()
        try:
            compiles = lambda: {name: e["compiles"] for name, e in
                                eng.costs.snapshot(materialize=False)["executables"].items()}
            snap, got = compiles(), {}
            for n in (4, 8, 12, 16):
                pacer = eng._pacer = Fixed(n)
                got[n] = await _serve(eng)
                assert set(pacer.lengths) == {n} and compiles() == snap, (n, pacer.lengths)
            assert all(got[4]) and got[4] == got[8] == got[12] == got[16]
            for _ in range(200):  # the worker harvests the last segment in its own time
                if not eng._inflight:
                    break
                await asyncio.sleep(0.05)
            return (got[4], dict(eng._layer_kind_totals), eng._prefix_state_misses, eng.pallas_paths(),
                    dict(eng._placement.get("state_pool", {})))
        finally:
            await eng.aclose()

    return asyncio.run(go())


@pytest.fixture(scope="module")
def served():
    """One engine a way of serving: the prompt draft on at the decode window
    of 8 (the cell's: its proposals are rejected), the jnp walk, the engine's
    own mesh over the tests' 8 devices, and the radix cache on."""
    return {
        "draft": _at_every_length(_engine_config()),
        "jnp": _at_every_length(_engine_config(use_pallas=False, interpret=False, draft_mode="off")),
        "mesh": _at_every_length(_engine_config(speculate_k=4), one_device=False),
        "radix": _at_every_length(_engine_config(prefix_cache=True)),
    }


def test_the_engine_serves_the_same_tokens_at_every_segment_length(served):
    """The pacer asks for 4, 8, 12 or 16 forwards a segment: the same greedy,
    grammar-constrained requests, with budgets that retire rows mid-segment
    (dead slots) and rows reused by later plans, decode byte-identical tokens
    at each length, with the prompt draft on (rejected slots) and off, at a
    window of 8 and of 4, through the interpreted kernels and the jnp walk: a
    state that moved by the window, or by a segment's length, could not."""
    want = served["draft"][0]
    assert [len(t) for t in want] == BUDGETS
    for way, (tokens, *_) in served.items():
        assert tokens == want, way


def test_the_counters_say_what_the_state_kept(served):
    _, totals, _, paths, placed = served["draft"]
    cfg = small()
    assert paths["paths"]["ssm"]["engaged"] and paths["paths"]["ssm"]["dispatches"] > 0
    assert totals["ssm_row_calls"] > 0 and totals["ssm_row_calls"] % cfg.n_scan_layers == 0
    assert totals["ssm_state_bytes"] == totals["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
    assert totals["attn_row_calls"] * cfg.n_scan_layers == totals["ssm_row_calls"]  # ONE attention layer
    # some proposal was rejected and not kept; every kept token was a live slot
    assert 0 < totals["ssm_tokens"] < totals["ssm_slots"]
    from mcpx.models.tokenizer import make_tokenizer

    n_prompt = sum(len(make_tokenizer("byte").encode(p)) for p in PROMPTS)
    assert totals["ssm_prefill_tokens"] == 4 * n_prompt * cfg.n_scan_layers
    assert totals["ssm_tokens"] == 4 * sum(BUDGETS) * cfg.n_scan_layers
    # with no draft every live slot is kept
    _, plain, _, jnp_paths, _ = served["jnp"]
    assert plain["ssm_tokens"] == plain["ssm_slots"] == totals["ssm_tokens"]
    # every leaf is read whole a forward, the tied embedding among them
    _, params = _small_params()
    held = sum(a.nbytes for a in jax.tree.leaves(params))
    assert totals["weight_bytes_read"] % held == 0 and totals["weight_bytes_read"] > 0
    assert not jnp_paths["paths"]["ssm"]["engaged"]
    assert not served["mesh"][3]["paths"]["ssm"]["engaged"] and "one device" in served["mesh"][3]["paths"]["ssm"]["reason"]
    # /healthz: the pool's bytes, the states' share of them, a slot a slab row
    pool = init_state_pool(cfg, 4, W)
    assert placed == {"bytes": sum(a.nbytes for a in jax.tree.leaves(pool)),
                      "state_bytes": pool["ssm"].nbytes, "slots": 4}


def test_with_the_radix_cache_on_every_row_prefills_whole_and_resident_pages_are_a_counted_miss(served):
    tokens, totals, misses, _, _ = served["radix"]
    assert tokens == served["draft"][0]
    assert totals["ssm_prefill_tokens"] == served["draft"][1]["ssm_prefill_tokens"]
    assert 15 <= misses <= 19  # as the Mamba-2 block's: tests/test_ssm_block.py
    assert served["draft"][2] == 0


# ------------------------------------------- compiled for a described v5e
@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a DESCRIBED v5e:2x2 (nothing attached, nothing runs): the
    TPU compiler is installed beside jax."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    return mesh, NamedSharding(mesh, PartitionSpec())


def _compile_uncached(fn, *args, **jit_kw):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, **jit_kw).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_compiled_for_v5e_the_scan_and_the_one_head_attention_at_the_published_widths(one_v5e):
    """Mosaic takes what interpret mode cannot show it refusing: both forms of
    the scan at 5,120 channels x 16 (a cohort of 4 at the 1,024 bucket; 8 rows'
    windows against a pool of 26 layers x 8 slots, the layer a traced number,
    the pool updated in place and nowhere copied), and the ragged kernel at ONE
    KV head under a group of 20."""
    from mcpx.engine.kernels.paged_attention import ragged_paged_attention
    from mcpx.engine.kernels.selective_scan import selective_scan_prefill, selective_scan_window

    _, replicated = one_v5e
    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=replicated)
    I, N = 5120, 16
    A, T = 4, 1024
    text = _compile_uncached(
        selective_scan_prefill, sd((A, T, I), f32), sd((A, T, I), f32), sd((A, T, N), f32),
        sd((A, T, N), f32), sd((N, I), f32), sd((A,), i32),
    ).as_text()
    assert "tpu_custom_call" in text and "selective_scan_prefill" in text
    B, S = 8, 8
    shapes = [sd((26, 8, N, I), f32), sd((), i32), sd((B,), i32), sd((B,), i32), sd((B, W, I), f32),
              sd((B, W, I), f32), sd((B, W, N), f32), sd((B, S, I), f32), sd((B, S, I), f32),
              sd((B, S, N), f32), sd((B, S, N), f32), sd((N, I), f32)]

    def forwards(pool, layer, *window):
        def body(c):  # as the segment holds it: the donated pool carried through a loop of calls
            pool, y = selective_scan_window(c[1], (layer + c[0]) % 26, *window)
            return c[0] + 1, pool, c[2] + y

        return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, pool, jnp.zeros((B, S, I), f32)))[1:]

    compiled = _compile_uncached(forwards, *shapes, donate_argnums=(0,))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "selective_scan_window" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 26 * 8 * N * I * 4 // 8  # no second pool
    assert not re.search(rf"f32\[26,8,{N},{I}\][^\n]* copy(-start|-done)?\(", text)
    K, G, hd, L, n_pages, psz, p_max = 1, 20, 128, 2, 1025, 16, 128
    attn = _compile_uncached(
        ragged_paged_attention, sd((B, S, K, G, hd), bf), sd((K, L, n_pages, psz, hd), bf),
        sd((K, L, n_pages, psz, hd), bf), sd((B, p_max), i32), sd((B,), i32), sd((B,), i32), sd((), i32),
    )
    assert "tpu_custom_call" in attn.as_text()


def _published(block, replicated):
    """The cell's configuration as shapes on the described chip -> (cfg, the
    parameter tree, a leaf's shape there, an int32 shape there)."""
    with open(os.path.join(CHIP_DIR, "configs", "jamba2-3b.json")) as f:
        config = json.load(f)
    spec = by_path("chip_harness_spec_jamba_t", os.path.join(CHIP_DIR, "spec.py"))
    cfg = block.model_config(spec.model_keys(config), 3072)
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
    params = jax.tree.map(sd, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    return cfg, params, sd, lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)


def test_compiled_for_v5e_the_segments_forwards_copy_no_stacked_pool_array(one_v5e, block):
    """The program the chip runs, not a loop of the kernel alone: a segment's
    forwards (``decode_chunk_paged`` at the published widths, all 28 layers, 8
    rows, the donated pools carried through a loop of four forwards) compiled
    for a described v5e. What the chip's trace showed before the pool's output
    was held to the HBM (``copy-start`` / ``copy-done f32[26,8,16,5120]`` at a
    scanned run's entry: XLA staged the WHOLE 68 MB pool through fast memory,
    PERF.md section 6, PR 58) is a copy in any spelling, so every spelling is
    looked for, over every stacked array of the pool: the states and the three
    pending arrays 5,120 wide are copied nowhere; ``conv`` and ``b`` change
    layout where the executable is entered and left (13 MB a SEGMENT), and
    nowhere inside a forward."""
    mesh, replicated = one_v5e
    cfg, params, sd, ints = _published(block, replicated)
    B, S, n_forwards = 8, W, 4
    pools = jax.tree.map(sd, jax.eval_shape(
        lambda: {**init_paged_kv(cfg, 1025, 16), "state": init_state_pool(cfg, B, W)}))

    def segment(params, window, pos, table, pools, q_lens):
        def forward(carry, _):
            pools, seen = carry
            logits, pools = decode_chunk_paged(
                params, cfg, window, pos, table, pools, use_pallas=True, mesh=mesh,
                logits_at=jnp.zeros((B,), jnp.int32), q_lens=q_lens,
            )
            return (pools, seen + logits), None

        return jax.lax.scan(forward, (pools, jnp.zeros((B, cfg.vocab_size), jnp.float32)), None, length=n_forwards)[0]

    text = _compile_uncached(
        segment, params, ints(B, S), ints(B), ints(B, 128), pools, ints(B), donate_argnums=(4,)
    ).as_text()
    assert text.count("selective_scan_window") >= 3 and "ragged" in text  # a call a Mamba RUN, not a layer
    # 8 x 8 rows: a split product stacks its halves, the weights stream once (ssm.SPLIT_STACK_ROWS)
    assert re.search(rf"f32\[{B},{2 * S},{2 * cfg.scan_inner}\]", text)
    copied = lambda shape: re.findall(rf"^.*f32\[{shape}\][^\n]* copy(?:-start|-done)?\(.*$", text, re.M)
    state = pools["state"]
    dims = lambda a: ",".join(str(n) for n in a.shape)
    for name in ("ssm", "dt", "pre", "x"):
        assert state[name].shape[0] == 26 and not copied(dims(state[name])), (name, copied(dims(state[name]))[:2])
    entry = text[text.index("\nENTRY "):]
    for name in ("conv", "b"):
        moved = copied(dims(state[name]))
        assert moved and all(line in entry for line in moved), (name, [m[:120] for m in moved if m not in entry])


@pytest.mark.parametrize("A", [1, 4])
def test_compiled_for_v5e_the_prefill_writes_nothing_of_twice_its_rows(one_v5e, block, A):
    """The admission prefill at the published widths and the cell's bucket
    (one row and a cohort of four at 1,024 slots), compiled for a described
    v5e: a split product past ``ssm.SPLIT_STACK_ROWS`` rows is two products
    whose sum XLA makes where the second accumulates. What the chip's trace
    showed of the stacked form there (PERF.md section 6, PR 59: ``fusion
    f32[4,2048,10240]``, the ``slice_add_fusion`` that read it back to add its
    halves, 84 MB written a row a layer for ``W_in``'s 42 MB of result) is in
    no operation of the optimised program: no array of 2,048 rows, float32 or
    bfloat16, and no fusion that slices to add."""
    cfg, params, _, ints = _published(block, one_v5e[1])
    T = 1024

    def admit(params, tokens, lens):
        return prefill(params, cfg, tokens, lens, init_kv_cache(cfg, A, T), last_only=True, use_pallas=True)

    text = _compile_uncached(admit, params, ints(A, T), ints(A)).as_text()
    assert text.count("selective_scan_prefill") >= 3  # a call a Mamba RUN
    assert not re.findall(rf"(?:f32|bf16)\[(?:{A},)?{2 * T},\d+\]", text) and "slice_add_fusion" not in text
    assert re.search(rf"f32\[(?:{A},)?{T},{2 * cfg.scan_inner}\]", text)  # W_in's product, T rows
