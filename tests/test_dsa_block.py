"""The latent block with a learned index (DeepSeek-V3.2-Exp): an index key a
token beside the latent and the rotated key, a score for every cached key, the
``index_topk`` best read and no others, in the dense prefill (a mask), in the
suffix route and in decode (the index kernel, then the selecting attention
kernel); a head longer than a prefill bucket built in chunks; group-limited
routing. CPU, small sizes; the plain reference is the benchmark's block module
(``benchmarks/chip/models/dsa.py``), imported by path."""

import asyncio
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mcpx.engine.paged_decode as paged
from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kernels.paged_attention import (
    NEG_INF, index_select_reference, lightning_indexer,
)
from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma import moe
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import (
    feed_forward_residual, gated_mlp, index_scores, init_kv_cache, init_params, prefill, select_top,
)
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, compiled, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
jit_prefill, jit_chunk = compiled()  # one executable a (configuration, route, shapes): tests/helpers.py
TOPK = 32


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_dsa_t", os.path.join(CHIP_DIR, "models", "dsa.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared: one
    dense layer, then two sparse ones (16 experts in 4 groups, 2 kept, all
    held), an index of 4 heads x 32 over the 32 best keys."""
    base = dict(
        vocab_size=384, d_model=64, n_layers=3, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128,
        attention="latent", q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        yarn_factor=40.0, yarn_original_max_pos=16, attn_score_factor=1.8739,
        index_n_heads=4, index_head_dim=32, index_topk=TOPK,
        n_experts=16, n_experts_per_tok=2, d_expert=32, n_dense_layers=1, d_shared_expert=32,
        router_scoring="sigmoid", router_scale=2.5, router_bias_scale=0.1,
        router_groups=4, router_groups_kept=2,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
        dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


# ------------------------------------------------------------ configuration
def test_published_counts_of_the_cut(block):
    with open(os.path.join(CHIP_DIR, "configs", "deepseek-v3.2-exp.json")) as f:
        config = json.load(f)
    spec = by_path("chip_harness_spec_dsa_t", os.path.join(CHIP_DIR, "spec.py"))
    cfg = block.model_config(spec.model_keys(config), 3072)
    assert cfg.n_params == 5_399_488_256 and "5.399 B" in config["params"]
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_experts, cfg.n_experts_held) == (6, 1, 256, 16)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (64, 128, 2048)
    assert (cfg.router_groups, cfg.router_groups_kept, cfg.router_bias_scale) == (8, 4, 0.1)
    # one page id addresses the latent, the rotated key (64 values in a lane row) and the index key
    assert cfg.kv_widths == (256, 512) and cfg.index_key_offset == 128 and cfg.kernel_lanes_ok
    assert cfg.kv_bytes_per_token == 6 * 576 * 2 and cfg.index_bytes_per_token == 6 * 128 * 2
    assert round(cfg.attn_score_factor, 4) == 1.8739
    assert cfg.max_seq_len == block.PREFILL_WINDOW == 1024  # the longest dense prefill, not the rope's reach
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog)) if r["name"] == "DeepSeek-V3.2-Exp")
        changed = {k for k, v in row["config"].items() if k not in config or config[k] != v}
        assert changed == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"}
        assert config["source"] == row["source_url"]
    bm = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bm["configs"] if c["name"] == "deepseek-v3.2-exp")
    assert set(entry["reduced"]) == set(config["reduced"]) <= set(config)


@pytest.mark.parametrize("bad", [
    dict(attention="heads", index_topk=8, index_n_heads=2, index_head_dim=16, q_lora_rank=0, kv_lora_rank=0,
         qk_rope_head_dim=0, v_head_dim=0, attn_score_factor=1.0),
    dict(index_head_dim=4),  # narrower than the rotated part
    dict(index_n_heads=0),
    dict(router_groups=3),  # does not divide 16
    dict(router_groups=16),  # groups of one have no two largest
    dict(router_groups_kept=5),
    dict(router_groups=0),  # kept without groups
    dict(router_groups=8, router_groups_kept=1, n_experts_per_tok=4),  # one group of 2 holds no 4
    dict(router_scoring="softmax", router_bias_scale=0.0, router_scale=1.0),
])
def test_a_configuration_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_has_a_spec(mesh_shape):
    cfg = small()
    mesh = make_mesh(data=mesh_shape[0], model=mesh_shape[1], devices=jax.devices()[: mesh_shape[0] * mesh_shape[1]])
    specs = param_pspecs(cfg, mesh)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(shapes) == jax.tree.structure(specs, is_leaf=lambda s: not isinstance(s, dict))
    for stack in ("layers", "dense_layers"):
        assert {"w_qi", "w_ki", "ki_norm", "ki_norm_bias", "w_wi"} <= set(specs[stack])
    if mesh_shape[1] > 1:
        assert specs["layers"]["w_qi"][2] == "model"  # index heads over model, as the query expansion


# ----------------------------------------------- the program and the reference
CONTEXTS = (24, 32, 33, 96)


@pytest.fixture(scope="module")
def compared(block):
    """Four rows of 24 / 32 / 33 / 96 tokens through the comparison's step
    (chunks of 24: edges at 24 and 48, on both sides of the 32nd key; the two
    kernels interpreted), three decoded positions each, the dense prefill (the
    selection as a mask) over the same rows, and the plain reference."""
    cfg = small()
    params = params_of(cfg)
    dims = dataclasses.asdict(cfg)
    mesh = one_device()
    B, T, psz, pages = len(CONTEXTS), 112, 16, 7
    block.CHUNK = 24
    rng = np.random.default_rng(44)
    seqs = rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    tokens = np.zeros((B, T), np.int32)
    for b, n in enumerate(CONTEXTS):
        tokens[b, :n] = seqs[b, :n]
    lens = jnp.asarray(CONTEXTS, jnp.int32)
    table = jnp.asarray(1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))
    sys_prefill, sys_decode = block.step_functions(
        cfg, dims, mesh, B=B, T=T, n_pages=1 + B * pages, page_size=psz, interpret=True
    )
    last, pools = sys_prefill(params, jnp.asarray(tokens), lens, table)
    got = [[np.asarray(last[b])] for b in range(B)]
    for i in range(3):
        tok = jnp.asarray([seqs[b, n + i] for b, n in enumerate(CONTEXTS)], jnp.int32)
        logits, pools = sys_decode(params, tok, lens + i, table, pools)
        for b in range(B):
            got[b].append(np.asarray(logits[b]))
    dense, _ = jax.jit(lambda p, t, l: prefill(p, cfg, t, l, init_kv_cache(cfg, B, T)))(
        params, jnp.asarray(seqs), lens + 3
    )
    ref = jax.jit(lambda p, t: block._reference(p, dims, t))
    want = [ref(params, jnp.asarray(seqs[b])) for b in range(B)]
    records = [dict(r) for r in block._SELECTION.rows]
    return dict(got=got, dense=np.asarray(dense), want=want, records=records, cfg=cfg)


@pytest.mark.parametrize("row", range(len(CONTEXTS)), ids=[f"ctx{n}" for n in CONTEXTS])
def test_chunked_prefill_then_decode_matches_the_reference(compared, block, row):
    n = CONTEXTS[row]
    logits, distance, _, checked, sel_distance, sel_flipped, sel_checked = compared["want"][row]
    logits = np.asarray(logits)
    for i, g in enumerate(compared["got"][row]):
        w = logits[n - 1 + i]
        assert np.sqrt(np.mean((g - w) ** 2)) / np.std(w) < 2e-3, (n, i)
    assert float(distance) < 1e-3 and int(checked) == 2 * (n + 3)  # two sparse layers, every position
    # what was recorded: every position from the 33rd key on, in all three layers
    rec = compared["records"][row]
    assert rec["bits"].shape[:2] == (3, max(n + 3 - TOPK, 0))
    assert float(sel_distance) < 0.02 and int(sel_flipped) == 0
    assert int(sel_checked) == 3 * sum(range(TOPK + 1, n + 4)) if n + 3 > TOPK else int(sel_checked) == 0
    if rec["bits"].shape[1]:
        read = np.unpackbits(rec["bits"], axis=-1)
        assert (read.sum(axis=-1) == TOPK).all()  # exactly 32 keys a query, none it cannot see
        for j in range(read.shape[1]):
            assert not read[:, j, TOPK + j + 1 :].any()


@pytest.mark.parametrize("row", range(len(CONTEXTS)), ids=[f"ctx{n}" for n in CONTEXTS])
def test_the_dense_prefill_with_the_selection_as_a_mask_matches_the_reference(compared, row):
    n = CONTEXTS[row] + 3
    want = np.asarray(compared["want"][row][0])[:n]
    got = compared["dense"][row, :n]
    assert np.sqrt(np.mean((got - want) ** 2)) / np.std(want) < 2e-3


def test_the_reference_under_its_own_selection_agrees_here_and_a_step_that_reads_every_key_does_not(block):
    """float32 on both sides: the reference's own choice is the step's, so
    following changes nothing; a step with its selection left out reads keys
    far under the threshold, and the check says so."""
    cfg = small()
    params = params_of(cfg)
    dims = dataclasses.asdict(cfg)
    mesh = one_device()
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 64)), jnp.int32)
    table = jnp.asarray(1 + np.arange(5, dtype=np.int32).reshape(1, 5))  # wider than any context
    block.CHUNK = 64
    try:
        block.CONTROLS["step_selects"] = False
        sys_prefill, _ = block.step_functions(cfg, dims, mesh, B=1, T=64, n_pages=6, page_size=16, interpret=True)
        last, _ = sys_prefill(params, tokens, jnp.asarray([64]), table)
    finally:
        block.CONTROLS["step_selects"] = True
    read = np.unpackbits(block._SELECTION.rows[0]["bits"], axis=-1)
    assert read[0, 0].sum() == TOPK + 1 and read[0, -1].sum() == 64  # every key it sees
    out = jax.jit(lambda p, t: block._reference(p, dims, t))(params, tokens[0])
    assert float(out[4]) > 1.0 > block.SELECTION_MARGIN and int(out[5]) > 0
    assert np.isnan(np.asarray(jax.jit(lambda p, t: block.reference_logits(p, dims, t))(params, tokens[0]))).all()


# ------------------------------------------------- the selection, three ways
def _index_case(seed=0, B=2, S=8, P=6, ties=True):
    """Index queries of a window at positions ``start..start + S`` and a
    paged pool of index keys behind a rotated key's lanes; with ``ties``
    every third key repeats its neighbour, so that scores tie exactly."""
    rng = np.random.default_rng(seed)
    Hi, di, lane0, psz = 4, 32, 128, 16
    n_keys = P * psz
    k_i = rng.standard_normal((B, n_keys, di)).astype(np.float32)
    if ties:
        k_i[:, 2::3] = k_i[:, 1:-1:3]
    q_i = jnp.asarray(rng.standard_normal((B, S, Hi, di)), jnp.bfloat16)
    w_i = jnp.asarray(rng.standard_normal((B, S, Hi)), jnp.float32)
    k_i = jnp.asarray(k_i, jnp.bfloat16)
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    pool = jnp.zeros((1, 2, 1 + B * P, psz, lane0 + di), jnp.bfloat16)
    rows = jnp.concatenate([jnp.ones((B, P, psz, lane0), jnp.bfloat16), k_i.reshape(B, P, psz, di)], axis=-1)
    pool = pool.at[0, 1, table].set(rows)
    start = jnp.asarray([n_keys - S - 3, 20], jnp.int32)[:B]
    return q_i, w_i, k_i, pool, table, start, lane0


@pytest.mark.parametrize("q_lens", [(8, 8), (5, 0), (1, 8)], ids=["full", "ragged-idle", "one"])
def test_the_prefill_form_and_the_decode_form_choose_the_same_keys(q_lens):
    """The dense forward's mask (``select_top`` over ``index_scores``), the
    gathered jnp form and the index kernel (interpreted) name the same keys
    at the same position, ties cut at the lower position in all three; a row
    that sees no more than ``topk`` keys reads them all."""
    q_i, w_i, k_i, pool, table, start, lane0 = _index_case()
    B, S = q_i.shape[:2]
    n_keys = k_i.shape[1]
    lens = jnp.asarray(q_lens, jnp.int32)
    pos = start[:, None] + jnp.arange(S)
    visible = (jnp.arange(n_keys)[None, None, :] <= pos[:, :, None]) & (jnp.arange(S)[None, :] < lens[:, None])[:, :, None]
    dense = np.asarray(select_top(index_scores(q_i, w_i, k_i), visible, TOPK))
    args = (q_i, w_i, pool, table, start, lens, jnp.int32(1))
    gathered = np.asarray(index_select_reference(*args, topk=TOPK, lane0=lane0)) == 0.0
    kernel = np.asarray(lightning_indexer(*args, topk=TOPK, lane0=lane0, interpret=True)) == 0.0
    assert (dense == gathered).all() and (dense == kernel).all()
    live = np.asarray(visible).any(axis=-1)
    assert (dense.sum(axis=-1)[live] == np.minimum(np.asarray(pos)[live] + 1, TOPK)).all()
    assert not dense[~live].any()
    # the second row's first queries see 21..28 keys: all of them, no search
    if q_lens[1]:
        assert (dense[1, 0, :21]).all()
    # a tie at the threshold went to the lower position somewhere (the case is built to have some)
    scores = np.where(np.asarray(visible), np.asarray(index_scores(q_i, w_i, k_i)), -np.inf)
    kth = np.sort(scores, axis=-1)[..., -TOPK]
    tied = (scores == kth[..., None]) & np.asarray(visible)
    cut = tied & ~dense
    for b, s in zip(*np.nonzero(cut.any(axis=-1))):
        assert np.nonzero(cut[b, s])[0].min() > np.nonzero(tied[b, s] & dense[b, s])[0].max()


def test_select_top_counts_ties_exactly():
    scores = jnp.asarray([[3.0, 1.0, 1.0, 1.0, 2.0, 1.0, 0.0, 9.0]])
    visible = jnp.asarray([[True] * 7 + [False]])
    assert np.asarray(select_top(scores, visible, 4)).tolist() == [[True, True, True, False, True, False, False, False]]
    assert np.asarray(select_top(scores, visible.at[0, 3:].set(False), 4)).tolist() == [[True] * 3 + [False] * 5]


def test_at_a_context_the_selection_covers_the_logits_are_the_index_less_blocks_bit_for_bit():
    """Up to ``index_topk`` keys a query reads every key: the layer IS the
    latent block without an index, in the dense prefill, through the jnp
    route and through the kernels (a table of exactly ``topk`` keys traces no
    selection at all; a wider one selects and finds nothing to drop)."""
    cfg = small()
    plain = dataclasses.replace(cfg, index_n_heads=0, index_head_dim=0, index_topk=0)
    params = params_of(cfg)
    index_leaves = {"w_qi", "w_ki", "ki_norm", "ki_norm_bias", "w_wi"}
    strip = lambda stack: {k: v for k, v in stack.items() if k not in index_leaves}
    bare = {**params, "layers": strip(params["layers"]), "dense_layers": strip(params["dense_layers"])}
    drawn = params_of(plain)
    assert all((a == b).all() for a, b in zip(jax.tree.leaves(bare), jax.tree.leaves(drawn)))
    B, T, lens = 2, TOPK, jnp.asarray([TOPK - 8, 9])
    toks = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    mesh = one_device()

    def run(c, p, pages, use_pallas):
        table = jnp.asarray(1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))
        last, dense = jit_prefill(p, c, toks, lens, init_kv_cache(c, B, T), last_only=True)
        pools = commit_prefill_to_pages(init_paged_kv(c, 1 + B * pages, 16), dense, table, lens, 16)
        window = toks[:, :8]
        step, _ = jit_chunk(p, c, window, lens, table, pools, use_pallas=use_pallas, interpret=True,
                            q_lens=jnp.asarray([8, 3]), mesh=mesh)
        return np.asarray(last), np.asarray(step)

    for pages in (2, 4):  # 32 keys: no selection traced; 64: traced, nothing to drop
        for use_pallas in (False, True):
            with_index, without = run(cfg, params, pages, use_pallas), run(plain, bare, pages, use_pallas)
            assert (with_index[0] == without[0]).all(), (pages, use_pallas)
            live = np.asarray([[True] * 8, [True] * 3 + [False] * 5])
            assert (with_index[1][live] == without[1][live]).all(), (pages, use_pallas)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_a_suffix_cohort_of_three_in_four_rows_is_the_cohort_in_eight(path):
    """The admission cohort's row bucket is padding and nothing else (ISSUE
    57: the suffix route's 4-row bucket). Three rows behind a shared head of
    48 tokens (past ``index_topk``: every suffix query selects among the
    head's index keys), padded as the engine pads them (a padding row: one
    pad token at position 0 over the null page) to 4 rows and to 8: the same
    last logits and the same pools (the index keys ride in them)."""
    cfg = small()
    params = params_of(cfg)
    mesh = one_device()
    psz, p_max, T, n_pages = 16, 8, 32, 1 + 3 + 3 * 5
    rng = np.random.default_rng(57)
    head = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 48)), jnp.int32)
    own = [rng.integers(0, cfg.vocab_size, n) for n in (5, 9, 13)]

    def run(A):
        pools = init_paged_kv(cfg, n_pages, psz)
        _, pools = jit_chunk(
            params, cfg, head, jnp.zeros((1,), jnp.int32), jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32),
            pools, use_pallas=path == "kernel", interpret=True, mesh=mesh, q_lens=jnp.asarray([48]))
        tokens, lens, pos = np.zeros((A, T), np.int32), np.ones((A,), np.int32), np.zeros((A,), np.int32)
        table = np.zeros((A, p_max), np.int32)
        for b, o in enumerate(own):
            tokens[b, : len(o)], lens[b], pos[b] = o, len(o), 48
            table[b, :3], table[b, 3:] = [1, 2, 3], 4 + 5 * b + np.arange(5)
        last, pools = jit_chunk(
            params, cfg, jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(table), pools,
            use_pallas=path == "kernel", interpret=True, mesh=mesh, logits_at=jnp.asarray(lens - 1),
            q_lens=jnp.asarray(lens))
        return np.asarray(last)[:3], pools

    (four, pools4), (eight, pools8) = run(4), run(8)
    np.testing.assert_allclose(four, eight, atol=1e-5)
    assert (four.argmax(-1) == eight.argmax(-1)).all()
    for a, b in zip(jax.tree.leaves(pools4), jax.tree.leaves(pools8)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_forward_counts_the_keys_it_selected_and_the_keys_it_scored():
    cfg = small()
    params = params_of(cfg)
    table = jnp.asarray(1 + np.arange(12, dtype=np.int32).reshape(2, 6))
    pools = init_paged_kv(cfg, 13, 16)
    window = jnp.ones((2, 8), jnp.int32)
    E = cfg.n_experts_held
    for q_lens, positions, want in [
        ((8, 3), (60, 10), [3 * (TOPK + 13), 3 * 68]),  # one row past the selection, one under it
        ((0, 5), (60, 40), [3 * TOPK, 3 * 45]),  # the idle row reads and scores nothing
    ]:
        *_, stats = jit_chunk(
            params, cfg, window, jnp.asarray(positions), table, pools, use_pallas=False,
            q_lens=jnp.asarray(q_lens), moe_stats=True,
        )
        own = E + moe.LAYER_STATS + moe.FORWARD_STATS
        assert stats.shape == (own + moe.INDEX_STATS + moe.LATENT_STATS,)
        assert stats[own : own + moe.INDEX_STATS].tolist() == want
    # a block with no index keeps the vector it had
    assert moe.moe_stats_init(dataclasses.replace(cfg, index_n_heads=0, index_head_dim=0, index_topk=0)).shape == (
        E + moe.LAYER_STATS + moe.FORWARD_STATS + moe.LATENT_STATS,)


# ------------------------------------------------------ group-limited routing
def test_the_group_limited_choice_differs_from_the_plain_one_and_weighs_unbiased_scores():
    cfg = small(n_experts_per_tok=4, router_groups_kept=2)
    plain = dataclasses.replace(cfg, router_groups=0, router_groups_kept=0)
    D, E = cfg.d_model, cfg.n_experts
    x = jax.random.normal(jax.random.PRNGKey(2), (64, D), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(3), (D, E), jnp.float32) / np.sqrt(D)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4), (E,), jnp.float32)
    chosen, w = (np.asarray(a) for a in moe.route(x, router, cfg, bias))
    chosen_plain, _ = (np.asarray(a) for a in moe.route(x, router, plain, bias))
    assert (np.sort(chosen, axis=1) != np.sort(chosen_plain, axis=1)).any()
    s = np.asarray(jax.nn.sigmoid(x @ router))
    biased = s + np.asarray(bias)
    for t in range(64):
        groups = biased[t].reshape(4, 4)
        kept = np.argsort(-np.sort(groups, axis=1)[:, -2:].sum(axis=1), kind="stable")[:2]
        assert set(chosen[t] // 4) <= set(kept)  # no expert outside the two best groups
        allowed = np.where(np.isin(np.arange(E) // 4, kept), biased[t], -np.inf)
        assert set(chosen[t]) == set(np.argsort(-allowed, kind="stable")[:4])
        np.testing.assert_allclose(w[t], 2.5 * s[t, chosen[t]] / s[t, chosen[t]].sum(), rtol=1e-5)


def _ff_branch(cfg, lp, experts, h):
    lp = {**lp, "pre_mlp_norm": jnp.ones_like(lp["pre_mlp_norm"])}
    out, stats, chosen = feed_forward_residual(h, lp, cfg, moe=(experts, jnp.int32(0), None))
    return np.asarray(out - h), np.asarray(stats), np.asarray(chosen)


def test_sixteen_shares_of_thirty_two_experts_add_up_with_the_shared_expert_counted_once():
    """model-configs section 4, under group-limited routing: 32 experts in 8
    groups of 4, 16 shares of 2 (half a group each), every share routing over
    all 32 and computing the shared expert: the sixteen partial results, less
    the shared expert's fifteen times, sum to the uncut layer's."""
    cfg = small(n_experts=32, router_groups=8, router_groups_kept=4, n_experts_per_tok=4)
    layers = params_of(cfg)["layers"]
    lp = {k: v[0] for k, v in layers.items() if k not in moe.EXPERT_LEAVES}
    experts = {k: layers[k] for k in moe.EXPERT_LEAVES}
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 64), jnp.float32)
    whole, stats, chosen = _ff_branch(cfg, lp, experts, h)
    n = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + cfg.norm_eps)
    shared = np.asarray(gated_mlp(n, lp["shared_gate"], lp["shared_up"], lp["shared_down"], cfg))
    parts, counts = [], []
    for first in range(0, 32, 2):
        share = dataclasses.replace(cfg, expert_first=first, experts_held=2)
        mine = {k: v[:, first : first + 2] for k, v in experts.items()}
        out, st, ch = _ff_branch(share, lp, mine, h)
        assert (ch == chosen).all()  # every share routes over all 32, inside the same groups
        parts.append(out)
        counts.append(st[:2])
    np.testing.assert_allclose(sum(parts) - 15 * shared, whole, rtol=1e-4, atol=1e-5)
    assert np.concatenate(counts).tolist() == stats[:32].tolist() and stats[:32].sum() == 15 * 4
    assert len({tuple(sorted(set(c // 4))) for c in chosen.reshape(15, 4)}) > 1  # tokens keep different groups


# ------------------------------------------- the programs that were there before
# (layer scans in decode_chunk_paged's jaxpr, sha256[:16] of its logits, of the
# K pool, of the V pool) of a.x-k1's rehearsal block in float32, recorded at
# the parent commit (80e0938, before any field of this block existed) by the
# function below, which is ``tests/test_afmoe_block.py``'s.
PINNED_LATENT = (2, "fe5c88747deb27c0", "cacdbc092c279517", "011ba48b8d034aef")


def test_a_latent_block_without_an_index_traces_to_the_program_it_always_did():
    mla = by_path("chip_block_mla_for_dsa_t", os.path.join(CHIP_DIR, "models", "mla.py"))
    cfg = dataclasses.replace(mla.rehearsal_config(384), dtype="float32")
    assert (cfg.index_topk, cfg.router_groups) == (0, 0)
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
    assert (scans, digest(logits), digest(kv["k"]), digest(kv["v"])) == PINNED_LATENT


# ------------------------------------------------------------- the engine
def _engine_config(max_seq_len, **engine):
    return MCPXConfig.from_dict({
        "model": {"max_seq_len": max_seq_len},
        "engine": {"max_batch_size": 4, "max_pages_per_seq": 32, "kv_page_size": 16, "max_decode_len": 8,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "warmup_compile": False,
                   **engine},
    })


def _chunks(eng):
    return int(eng.metrics.prefix_build_chunks._value.get())


def test_a_head_longer_than_a_bucket_is_built_in_chunks_and_its_pages_are_the_one_dispatch_builds():
    """Buckets to 64 against buckets to 256, the same 208-token declared head:
    four dispatches (a dense prefill, then three suffix prefills over the
    pages before) against one; the head's pages hold the same latents,
    rotated keys and index keys, the radix tree the same run, and what is
    generated behind the head is the same tokens."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        chunked = InferenceEngine(_engine_config(64), model_cfg=small(max_seq_len=64))
        whole = InferenceEngine(_engine_config(256), model_cfg=small(max_seq_len=256))
        await chunked.start()
        await whole.start()
        try:
            assert chunked._prefill_buckets == (64,) and whole._prefill_buckets[-1] == 256
            rng = np.random.default_rng(9)
            head = [int(t) for t in rng.integers(4, 380, 208)]
            outs = {}
            for name, eng in (("chunked", chunked), ("whole", whole)):
                assert eng.prompt_capacity(0, len(head)) > len(head) + 20  # the head's own path, not a bucket's
                toks = []
                for i in range(2):
                    tail = [int(t) for t in rng.integers(4, 380, 11)] if name == "chunked" and not outs else None
                    tails = outs.setdefault("tails", [])
                    if len(tails) <= i:
                        tails.append([int(t) for t in np.random.default_rng(20 + i).integers(4, 380, 11)])
                    r = await eng.generate(head + tails[i], max_new_tokens=6, constrained=False,
                                           temperature=0.0, shared_prefix_len=len(head))
                    toks.append(r.token_ids)
                outs[name] = toks
            assert outs["chunked"] == outs["whole"]
            assert (_chunks(chunked), _chunks(whole)) == (4, 1)  # built once, on the first request
            pages = {}
            for name, eng in (("chunked", chunked), ("whole", whole)):
                n, run, _ = eng._prefix_cache.match(tuple(head), cap=len(head), record=False)
                assert n == 208 and len(run) == 13
                pages[name] = {k: np.asarray(v[:, :, np.asarray(run)]) for k, v in eng._paged_kv.items()}
            for k in ("k", "v"):
                np.testing.assert_allclose(pages["chunked"][k], pages["whole"][k], rtol=2e-4, atol=2e-5)
            assert np.abs(pages["whole"]["k"][..., 128:]).max() > 0.1  # the index keys are there
        finally:
            await chunked.aclose()
            await whole.aclose()

    asyncio.run(go())


def test_spilled_pages_bring_their_index_keys_back():
    """The host tier moves page runs of both pools to host RAM and back; the
    index keys ride in the rotated key's rows: generations at contexts past
    the selection, served from re-admitted pages, are a fresh engine's."""
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
            assert eng._snapshot_meta()["kv_widths"] == [160, 32]
            prompts = [eng.tokenizer.encode(f"index probe {i}: " + "wxyz " * 28)[:128] for i in range(8)]
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
