"""The MiniCPM-SALA block: layers that are a mixer followed by the dense
feed-forward, the mixer LINEAR attention (a float32 state a row in the state
pool, which a shared head's END STATE is handed on from) or attention that
reads its top-k KEY BLOCKS by a parameter-free score over pooled keys (page
sums in a pool of their own, the chosen blocks' pages GATHERED by the ragged
kernel). CPU, small sizes, kernels interpreted AND the jnp forms in lockstep;
the plain reference is the benchmark's block module (``benchmarks/chip/models/
sala.py``), imported by path, and the comparison is the one that decides a
benchmark run's ``correct`` (``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_sala_rehearsal.py`` beside the rehearsal child."""

import asyncio
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import (
    commit_prefill_key_sums, commit_prefill_to_pages, init_paged_kv, init_state_pool,
    write_prefill_state,
)
from mcpx.engine.paged_decode import keep_window
from mcpx.models.gemma import sparse
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, init_params
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, compiled, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
W = 8  # the decode window's slots
jit_prefill, jit_chunk = compiled()  # one executable a (configuration, route, shapes): tests/helpers.py


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_sala_t", os.path.join(CHIP_DIR, "models", "sala.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared:
    pages of 4, blocks of 8 of which a query keeps 4 (its own and the one
    before it forced, and block 0)."""
    base = dict(
        vocab_size=512, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
        qk_norm=True, attn_gate=True, layer_pattern="SLLS", block_size=8, block_topk=4, block_window=16,
        pool_stride=4, embed_scale=12.0, residual_scale=1.4 / 32**0.5, logit_divisor=4.0,
        dtype="float32", ssm_chunk_size=16,
    )
    return GemmaConfig(**{**base, **kw})


# ------------------------------------------------------- the tree, the file
def test_the_tree_has_two_stacks_and_the_count_is_the_trees():
    cfg = small()
    params = params_of(cfg)
    assert set(params) == {"embed", "head", "final_norm", "linear_layers", "block_layers"}
    assert params["linear_layers"]["wk"].shape == (2, 128, 128) and params["block_layers"]["wk"].shape == (2, 128, 64)
    assert params["linear_layers"]["o_norm"].shape == (2, 128)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params == cfg.n_active_params


def test_published_counts_of_minicpm_sala(block):
    """The file's parameter arithmetic: the cut holds 2.244 B, the published
    32 layers 9.48 B, a linear layer's state 2 MB a row."""
    with open(os.path.join(CHIP_DIR, "configs", "minicpm-sala.json")) as f:
        config = json.load(f)
    spec = by_path("chip_harness_spec_sala_t", os.path.join(CHIP_DIR, "spec.py"))
    cfg = block.model_config(spec.model_keys(config), 3072)
    assert cfg.layer_pattern == "SLLLSLLL" and cfg.n_params == 2_244_048_384
    assert cfg.ssm_slot_bytes == 2_097_152 and cfg.head_state and cfg.n_attn_layers == 2
    assert cfg.residual_scale == pytest.approx(1.4 / 32**0.5) and cfg.logit_divisor == 16.0
    assert (cfg.block_size, cfg.block_topk, cfg.blocks_kept, cfg.pool_stride) == (64, 64, 32, 16)
    full = dataclasses.replace(
        cfg, n_layers=32, vocab_size=73448,
        layer_pattern="".join("S" if m == "minicpm4" else "L" for m in config["mixer_types_published"]),
    )
    assert full.layer_pattern.count("S") == 8 and full.n_params == 9_477_203_968
    np.testing.assert_allclose(np.exp(cfg.linear_decay[[0, 31]]), np.exp([-(2.0 ** -0.25), -(2.0 ** -8)]), rtol=1e-6)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if '"MiniCPM-SALA"' in l)
        reduced = {"num_hidden_layers", "mixer_types", "vocab_size"}
        assert {k: v for k, v in row["config"].items() if k not in reduced} == {
            k: config[k] for k in row["config"] if k not in reduced}
        assert config["source"] == row["source_url"] and config["mixer_types_published"] == row["config"]["mixer_types"]


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="SLM*"), dict(layer_pattern="SLL"), dict(block_size=6), dict(block_window=12),
    dict(n_experts=4, n_experts_per_tok=2, d_expert=8), dict(norm_plus_one=True), dict(block_topk=0),
])
def test_a_pattern_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


def test_the_new_fields_belong_to_the_pattern():
    for field in (dict(block_size=8), dict(embed_scale=12.0), dict(residual_scale=0.5), dict(logit_divisor=4.0)):
        with pytest.raises(ConfigError):
            GemmaConfig(**field)


def test_a_page_that_is_not_a_pool_stride_is_an_error_at_construction():
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match="pool_stride"):
        InferenceEngine(MCPXConfig.from_dict({"engine": {"kv_page_size": 16}}), model_cfg=small())


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_has_a_spec(mesh_shape):
    cfg = small()
    mesh = make_mesh(data=mesh_shape[0], model=mesh_shape[1], devices=jax.devices()[: mesh_shape[0] * mesh_shape[1]])
    specs = param_pspecs(cfg, mesh)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(jax.tree.map(lambda _: 0, specs, is_leaf=lambda s: not isinstance(s, dict))) \
        == jax.tree.structure(jax.tree.map(lambda _: 0, shapes))
    on_mesh = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    plain = params_of(cfg)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(on_mesh), jax.tree.leaves(plain)))


# --------------------------------------- the linear scan, the window, the pool
def _token_by_token(cfg, lp, n, positions):
    """The recurrence by its definition over [T] -> (o [T, H, d], S_T)."""
    from mcpx.models.gemma.ssm import linear_inputs

    q, k, v = (a[0].astype(jnp.float32) for a in linear_inputs(n[None], lp, cfg, positions[None]))
    lam = jnp.exp(jnp.asarray(cfg.linear_decay))

    def one(S, t):
        q_t, k_t, v_t = t
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    S, o = jax.lax.scan(one, jnp.zeros((cfg.n_heads, cfg.head_dim, cfg.head_dim)), (q, k, v))
    return o, S


def test_the_chunked_scan_from_any_state_is_the_token_by_token_recurrence():
    """``ssd_scan`` under a linear layer's scalars, 40 tokens in chunks of 16
    from the state 24 tokens left: the outputs and the end state of 64."""
    from mcpx.models.gemma.model import stack_row
    from mcpx.models.gemma.ssm import _linear_scalars, linear_inputs, linear_prefill, ssd_scan

    cfg = small()
    lp = stack_row(params_of(cfg)["linear_layers"], 1)
    n = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    want_o, want_S = _token_by_token(cfg, lp, n, jnp.arange(64))
    _, h24 = linear_prefill(n[None, :32], lp, cfg, jnp.asarray([24]))  # padded: the state AT 24
    q, k, v = linear_inputs(n[None, 24:], lp, cfg, jnp.arange(24, 64)[None])
    y, h = ssd_scan(h24, jnp.ones((1, 40, cfg.n_heads)), v, k, q, _linear_scalars(cfg), 16)
    np.testing.assert_allclose(y[0], want_o[24:], atol=2e-5)
    # the pool's layout: the key's d before the heads
    np.testing.assert_allclose(h[0].transpose(1, 0, 2), want_S, atol=2e-5)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
@pytest.mark.parametrize("forwards", [4, 8, 12, 16])
def test_windows_with_rejected_proposals_keep_one_plus_accepted(path, forwards):
    """``forwards`` decode windows of 8 slots behind a prefill, each ``[the
    token, proposals]`` of which the row keeps ``1 + accepted`` (0..7 accepted,
    uneven by row and step; an idle row now and then): every window's logits
    at its kept slots are the dense forward's at those positions, so the state
    moved by exactly what was kept and never by a rejected token."""
    cfg = small(layer_pattern="LLLS")
    params = params_of(cfg)
    B, T0, psz, p_max = 3, 16, 4, 48
    total = T0 + forwards * W
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, total), 0, cfg.vocab_size)
    want, _ = jit_prefill(params, cfg, toks, jnp.full((B,), total), init_kv_cache(cfg, B, total))
    n_pages = 1 + B * p_max
    table = jnp.asarray(1 + np.arange(B * p_max, dtype=np.int32).reshape(B, p_max))
    lens = jnp.full((B,), T0)
    _, dense = jit_prefill(params, cfg, toks[:, :T0], lens, init_kv_cache(cfg, B, T0), last_only=True)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, n_pages, psz), dense, table, lens, psz)
    state = write_prefill_state(init_state_pool(cfg, B + 1, W, n_pages), jnp.arange(B), dense["ssm"])
    state["ksum"] = commit_prefill_key_sums(state["ksum"], dense["k"], table, psz)
    pos = np.full((B,), T0)
    rng = np.random.default_rng(forwards)
    mesh = one_device()
    step = lambda w, p, pools, q: jit_chunk(
        params, cfg, w, p, table, pools, use_pallas=path == "kernel", interpret=True, mesh=mesh, q_lens=q)
    for i in range(forwards):
        keep = rng.integers(1, W + 1, size=B)  # 1 + accepted
        q_lens = np.minimum(W, keep + rng.integers(0, 3, size=B))  # and up to two rejected behind them
        if i % 5 == 3:
            q_lens[i % B], keep[i % B] = 0, 0  # an idle row
        window = np.stack([np.asarray(toks[b, pos[b] : pos[b] + W]) for b in range(B)])
        for b in range(B):  # what is not kept is a WRONG proposal
            window[b, keep[b] :] = (window[b, keep[b] :] + 1 + i) % cfg.vocab_size
        logits, out = step(jnp.asarray(window), jnp.asarray(pos), {**pools, "state": state}, jnp.asarray(q_lens))
        for b in range(B):
            np.testing.assert_allclose(
                logits[b, : keep[b]], want[b, pos[b] : pos[b] + keep[b]], atol=3e-4, err_msg=f"{i} {b}")
        pools = {"k": out["k"], "v": out["v"]}
        state = keep_window(out["state"], jnp.arange(B), jnp.asarray(keep), jnp.asarray(q_lens > 0))
        pos = pos + keep


def test_a_window_wider_than_the_pending_width_is_refused_unless_it_commits():
    cfg = small()
    params = params_of(cfg)
    pools = {**init_paged_kv(cfg, 9, 4), "state": init_state_pool(cfg, 2, W, 9)}
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    call = lambda **kw: jit_chunk(
        params, cfg, jnp.zeros((1, 16), jnp.int32), jnp.zeros((1,), jnp.int32), table, pools,
        use_pallas=False, q_lens=jnp.asarray([16]), **kw)
    with pytest.raises(ValueError, match="pending"):
        call()
    call(commit=True, state_slots=(jnp.asarray([2]), jnp.asarray([0])))  # from an empty state, into slot 0


# ------------------------------------------- the selection, and the gathering
def _plain_selection(q, k, t, cfg):
    """The selection by its definition in numpy, one (query, KV head) at a
    time: q [G, hd] at position t over keys k [t + 1, hd] -> the set of
    blocks, and each block's score."""
    p, b, hd = cfg.pool_stride, cfg.block_size, cfg.head_dim
    r = b // p
    js = [j for j in range(max((t + 1) // p - 1, 0)) if p * j + 2 * p - 1 <= t]
    own = t // b
    score = np.full((own + 1,), -np.inf)
    if js:
        kc = np.stack([k[p * j : p * j + 2 * p].mean(0) for j in js])
        s = (q @ kc.T) / np.sqrt(hd)
        s = np.exp(s - s.max(-1, keepdims=True))
        pooled = (s / s.sum(-1, keepdims=True)).sum(0)
        for n in range(own + 1):
            inside = [pooled[j] for j in range(r * n - 1, r * n + r) if 0 <= j < len(js)]
            score[n] = max(inside) if inside else -np.inf
    forced = [n for n in range(own + 1) if n < cfg.block_init or n > own - cfg.blocks_kept]
    score[forced] = np.inf
    order = sorted(range(own + 1), key=lambda n: (-score[n], n))  # ties: the lower block
    return set(order[: cfg.block_topk]), score


def test_the_selection_is_the_definitions_with_forced_blocks_and_ties():
    cfg = small()
    B, T, K, G, hd = 1, 48, 2, 2, 32
    rng = np.random.default_rng(0)
    q = rng.standard_normal((B, T, K, G, hd)).astype(np.float32)
    k = rng.standard_normal((B, T, K, hd)).astype(np.float32)
    k[:, 8:24] = k[:, 24:40]  # blocks 1, 2 equal blocks 3, 4: pooled scores tie
    kc = sparse.pooled_keys(sparse.page_sums(jnp.asarray(k), 4), 4)
    t = jnp.arange(T)[None]
    got = np.asarray(sparse.selected_blocks(jnp.asarray(q), kc, t, cfg))
    for pos in range(T):
        for g in range(K):
            want, _ = _plain_selection(q[0, pos, g], k[0, : pos + 1, g], pos, cfg)
            assert set(np.flatnonzero(got[0, pos, g])) == want, (pos, g)
    assert all(got[0, pos].sum(-1).tolist() == [min(pos // 8 + 1, 4)] * K for pos in range(T))
    ids, count = sparse.block_lists(jnp.asarray(got), cfg.block_topk)
    assert bool(jnp.all(jnp.diff(jnp.where(ids < 6, ids, 100 + jnp.arange(4)), axis=-1) > 0))
    assert count[0, 47].tolist() == [4, 4] and ids[0, 3, 0].tolist() == [0, 6, 6, 6]


def test_the_score_kernel_is_the_jnp_scores():
    from mcpx.engine.kernels.block_score import block_score

    B, S, K, G, hd, J = 3, 8, 2, 16, 128, 63
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, G, hd), jnp.bfloat16)
    kc = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (B, K, J, hd), jnp.float32)
    pos, q_lens = jnp.asarray([500, 40, 1000]), jnp.asarray([8, 3, 0])
    got = block_score(q, kc, pos, q_lens, stride=16, interpret=True)
    want = sparse.pooled_scores(q, kc, pos[:, None] + jnp.arange(S), 16)
    seen = jnp.isfinite(want)
    assert bool(jnp.all(jnp.isfinite(got[:2]) == seen[:2])) and bool(jnp.all(got[2] == -jnp.inf))
    np.testing.assert_allclose(jnp.where(seen[:2], got[:2], 0), jnp.where(seen[:2], want[:2], 0), atol=1e-6)
    # a slot at position 40 sees pooled key 0 whole (16 j + 31 <= 40), the one at 47 pooled key 1 too
    assert seen[1, 0, 0].sum() == 1 and seen[1, 7, 0].sum() == 2 and seen[0, 0, 0].sum() == 30


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_gathered_is_masked_over_the_same_selection_and_under_the_kept_blocks_it_is_dense(path):
    """One window two ways: as a decode window (the chosen blocks' pages
    GATHERED, ``commit`` off) and as a prefill's (the masked form): the same
    logits and the same blocks read. And a model whose table holds no more
    than the blocks every query keeps runs plain grouped attention: bit for
    bit what the same weights give with the selection wide open."""
    cfg = small(layer_pattern="SSLL")
    params = params_of(cfg)
    B, T0, psz, p_max = 2, 64, 4, 24
    n_pages = 1 + B * p_max
    table = jnp.asarray(1 + np.arange(B * p_max, dtype=np.int32).reshape(B, p_max))
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T0 + W), 0, cfg.vocab_size)
    lens = jnp.asarray([T0, T0 - 9])
    mesh = one_device()

    def filled(cfg):
        _, dense = jit_prefill(params, cfg, toks[:, :T0], lens, init_kv_cache(cfg, B, T0), last_only=True)
        pools = commit_prefill_to_pages(init_paged_kv(cfg, n_pages, psz), dense, table, lens, psz)
        state = write_prefill_state(init_state_pool(cfg, B + 1, W, n_pages), jnp.arange(B), dense["ssm"])
        state["ksum"] = commit_prefill_key_sums(state["ksum"], dense["k"], table, psz)
        return {**pools, "state": state}

    def window(cfg, pools, **kw):
        return jit_chunk(
            params, cfg, toks[:, T0 : T0 + W], lens, table, pools, use_pallas=path == "kernel",
            interpret=True, mesh=mesh, q_lens=jnp.asarray([W, 5]), **kw)

    gathered, _, read_g = window(cfg, filled(cfg), selection=True)
    own = jnp.arange(B, dtype=jnp.int32)
    masked, _, read_m = window(cfg, filled(cfg), selection=True, commit=True, state_slots=(own, own))
    assert bool(jnp.array_equal(read_g[:, 0], read_m[:, 0])) and bool(jnp.array_equal(read_g[:, 1, :5], read_m[:, 1, :5]))
    np.testing.assert_allclose(gathered[0], masked[0], atol=2e-5)
    np.testing.assert_allclose(gathered[1, :5], masked[1, :5], atol=2e-5)
    bits = np.unpackbits(np.asarray(read_g), axis=-1)[..., : p_max * psz // cfg.block_size]
    assert (bits[:, 0].sum(-1) == 4).all() and bits[:, 0, :, :, 0].all()  # 4 of 9 blocks, block 0 among them
    wide = dataclasses.replace(cfg, block_topk=p_max * psz // cfg.block_size)  # nothing a query could drop
    dense_out, _ = window(wide, filled(wide))
    assert float(jnp.max(jnp.abs(dense_out[0] - gathered[0]))) > 1e-3  # the selection does drop what weighs
    ref, _ = jit_prefill(params, wide, toks, jnp.asarray([T0 + W, T0 + W]), init_kv_cache(wide, B, T0 + W))
    np.testing.assert_allclose(dense_out[0], ref[0, T0:], atol=2e-5)


def test_a_shared_pages_key_sum_does_not_depend_on_what_follows_it():
    """Two rows share their first pages (one table run) and go on with
    tokens of their own: the shared pages' rows of the key-sum pool are what
    one row alone wrote, whatever the other wrote behind them, and a decode
    write sums the page it touched again."""
    cfg = small(layer_pattern="SLLL")
    params = params_of(cfg)
    psz, p_max, n_pages = 4, 16, 40
    head = jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0, cfg.vocab_size)
    tails = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, cfg.vocab_size)
    shared = [1, 2, 3, 4]
    tables = jnp.asarray([shared + list(range(5, 17)), shared + list(range(17, 29))], jnp.int32)

    def run(rows):
        pools = {**init_paged_kv(cfg, n_pages, psz), "state": init_state_pool(cfg, 3, W, n_pages)}
        slots = jnp.asarray([2], jnp.int32)
        _, pools = jit_chunk(  # the head, into the shared pages and slot 2
            params, cfg, head, jnp.zeros((1,), jnp.int32), tables[:1], pools, use_pallas=False,
            q_lens=jnp.asarray([16]), commit=True, state_slots=(jnp.asarray([3]), slots))
        for r in rows:
            _, pools = jit_chunk(
                params, cfg, tails[r : r + 1], jnp.asarray([16]), tables[r : r + 1], pools, use_pallas=False,
                q_lens=jnp.asarray([8]), commit=True, state_slots=(slots, jnp.asarray([r])))
        return pools

    one, both = run([0]), run([0, 1])
    ksum = lambda p: np.asarray(p["state"]["ksum"])
    np.testing.assert_array_equal(ksum(one)[:, :, 1:7], ksum(both)[:, :, 1:7])  # the shared pages and row 0's own
    assert np.abs(ksum(both)[:, :, 17:19]).sum() > 0 and np.abs(ksum(one)[:, :, 17:19]).sum() == 0
    np.testing.assert_allclose(ksum(both)[:, 0, 1:5], np.asarray(both["k"])[:, 0, 1:5].sum(2), rtol=1e-6)
    # a decode window into page 7 (positions 24..27) sums that page again
    _, after = jit_chunk(
        params, cfg, tails[:1, :2], jnp.asarray([24]), tables[:1], both, use_pallas=False, q_lens=jnp.asarray([2]))
    np.testing.assert_allclose(ksum(after)[:, 0, 7], np.asarray(after["k"])[:, 0, 7].sum(1), rtol=1e-6)
    np.testing.assert_array_equal(ksum(after)[:, :, 1:5], ksum(both)[:, :, 1:5])


def test_a_head_built_in_chunks_is_the_head_built_at_once():
    """96 tokens as one commit window and as three of 32, each handed the
    state of the one before through its slot: the same end state, key sums
    and last logits; and the dense prefill's too."""
    cfg = small(layer_pattern="SLLL", ssm_chunk_size=16)
    params = params_of(cfg)
    psz, p_max, n_pages = 4, 32, 40
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 96), 0, cfg.vocab_size)
    table = jnp.asarray([list(range(1, 33))], jnp.int32)
    slot, none = jnp.asarray([1], jnp.int32), jnp.asarray([2], jnp.int32)

    def build(chunk):
        pools = {**init_paged_kv(cfg, n_pages, psz), "state": init_state_pool(cfg, 2, W, n_pages)}
        for start in range(0, 96, chunk):
            last, pools = jit_chunk(
                params, cfg, toks[:, start : start + chunk], jnp.asarray([start]), table, pools,
                use_pallas=False, q_lens=jnp.asarray([chunk]), logits_at=jnp.asarray([chunk - 1]),
                commit=True, state_slots=(slot if start else none, slot))
        return last, pools

    (a, pa), (b, pb) = build(96), build(32)
    np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(pa["state"]["ssm"], pb["state"]["ssm"], atol=2e-5)
    np.testing.assert_allclose(pa["state"]["ksum"], pb["state"]["ksum"], atol=1e-5)
    assert int(pb["state"]["n"][1]) == 0 and float(jnp.abs(pb["state"]["layers"][0]["dt"][1]).sum()) == 0
    last, dense = jit_prefill(params, cfg, toks, jnp.asarray([96]), init_kv_cache(cfg, 1, 96), last_only=True)
    np.testing.assert_allclose(a, last, atol=2e-5)
    np.testing.assert_allclose(pa["state"]["ssm"][:, 1], jnp.stack([h[0].reshape(32, -1) for h in dense["ssm"]]), atol=2e-5)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_a_suffix_cohort_of_three_in_four_rows_is_the_cohort_in_eight(path):
    """The admission cohort's row bucket is padding and nothing else (ISSUE
    57: the suffix route's 4-row bucket). Three rows behind a head whose END
    STATE sits in the pool's last slot, padded as the engine pads them (a
    padding row: one pad token at position 0 over the null page, its state
    read from and written to a slot out of range) to 4 rows and to 8: the
    same last logits, and the same pool: the rows' states, key sums and
    counts, the head's slot untouched."""
    cfg = small(layer_pattern="SLLS")
    params = params_of(cfg)
    mesh = one_device()
    psz, p_max, T, n_slots = 4, 16, 16, 9  # an 8-row slab's pool: a slot a row and the head's
    n_pages = 1 + 8 + 3 * 8
    rng = np.random.default_rng(57)
    head = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)), jnp.int32)  # eight shared pages
    own = [rng.integers(0, cfg.vocab_size, n) for n in (5, 9, 13)]
    shared = 1 + np.arange(8)
    head_slot, nowhere = n_slots - 1, n_slots

    def run(A):
        pools = {**init_paged_kv(cfg, n_pages, psz), "state": init_state_pool(cfg, n_slots, W, n_pages)}
        head_table = np.zeros((1, p_max), np.int32)
        head_table[0, :8] = shared
        _, pools = jit_chunk(
            params, cfg, head, jnp.zeros((1,), jnp.int32), jnp.asarray(head_table), pools,
            use_pallas=path == "kernel", interpret=True, mesh=mesh, q_lens=jnp.asarray([32]), commit=True,
            state_slots=(jnp.asarray([nowhere]), jnp.asarray([head_slot])))
        tokens, lens, pos = np.zeros((A, T), np.int32), np.ones((A,), np.int32), np.zeros((A,), np.int32)
        table = np.zeros((A, p_max), np.int32)
        src, dst = np.full((A,), nowhere, np.int32), np.full((A,), nowhere, np.int32)
        for b, o in enumerate(own):
            tokens[b, : len(o)], lens[b], pos[b] = o, len(o), 32
            table[b, :8], table[b, 8:] = shared, 9 + 8 * b + np.arange(8)
            src[b], dst[b] = head_slot, b
        last, pools = jit_chunk(
            params, cfg, jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(table), pools,
            use_pallas=path == "kernel", interpret=True, mesh=mesh, logits_at=jnp.asarray(lens - 1),
            q_lens=jnp.asarray(lens), commit=True, state_slots=(jnp.asarray(src), jnp.asarray(dst)))
        return np.asarray(last)[:3], pools

    (four, pools4), (eight, pools8) = run(4), run(8)
    np.testing.assert_allclose(four, eight, atol=1e-5)
    assert (four.argmax(-1) == eight.argmax(-1)).all()
    for a, b in zip(jax.tree.leaves(pools4), jax.tree.leaves(pools8)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert float(jnp.abs(pools4["state"]["ssm"][:, :3]).sum()) > 0  # the rows' states were written
    assert float(jnp.abs(pools4["state"]["ssm"][:, 3:head_slot]).sum()) == 0  # and no padding row's


# ------------------------------------------------ the served path, the head
def _engine_config(**engine):
    return MCPXConfig.from_dict({
        "model": {"max_seq_len": 1024},
        "engine": {"max_batch_size": 4, "max_decode_len": 24, "kv_page_size": 16, "max_pages_per_seq": 64,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": True,
                   "warmup_compile": False, **engine},
    })


def _served_cfg(vocab):
    return small(vocab_size=vocab, layer_pattern="SLLL", block_size=32, block_topk=4, block_window=64,
                 pool_stride=16, ssm_chunk_size=32, max_seq_len=1024)


HEAD = "Catalogue.\n" + "".join(f"service {i}: does thing number {i} for the fleet\n" for i in range(10))
OTHER = "Catalogue.\n" + "".join(f"service {i}: does another thing {i} for the fleet\n" for i in range(10))
INTENTS = [f"intent {i}: compose and route {i}. JSON:" for i in range(5)]
BUDGETS = [3, 20, 9, 21, 14]


def _serve_plans(config, rounds):
    """One engine; ``rounds`` of (head text, declared or not) each serving the
    five intents behind that head -> (tokens a round, the engine's counts)."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        probe = InferenceEngine(config)
        eng = InferenceEngine(config, model_cfg=_served_cfg(probe.tokenizer.vocab_size), mesh=one_device())
        await eng.start()
        try:
            got = []
            for head, declared in rounds:
                n_head = len(eng.tokenizer.encode(head)) if declared else 0
                ids = [eng.tokenizer.encode(head + i) for i in INTENTS]
                rs = await asyncio.gather(*(
                    eng.generate(p, max_new_tokens=b, constrained=True, temperature=0.0, shared_prefix_len=n_head)
                    for p, b in zip(ids, BUDGETS)))
                got.append([r.token_ids for r in rs])
            for _ in range(200):
                if not eng._inflight:
                    break
                await asyncio.sleep(0.05)
            totals = dict(eng._layer_kind_totals)
            return got, (eng._prefix_state_hits, eng._prefix_state_misses), totals, eng.pallas_paths()["paths"]
        finally:
            await eng.aclose()

    return asyncio.run(go())


@pytest.fixture(scope="module")
def served():
    return {
        # the head declared, twice over; then ANOTHER head that shares its first page
        "head": _serve_plans(_engine_config(), [(HEAD, True), (HEAD, True), (OTHER, True)]),
        "whole": _serve_plans(_engine_config(prefix_cache=False), [(HEAD, False), (OTHER, False)]),
    }


def test_a_plan_over_the_stored_head_state_is_the_plan_prefilled_whole_and_counts_a_hit(served):
    (first, again, _), (hits, _), totals, paths = served["head"]
    (whole, _), (no_hits, no_misses), whole_totals, _ = served["whole"]
    assert first == whole and again == whole and all(first)
    assert hits == 10 and (no_hits, no_misses) == (0, 0)
    # the head (28 pages of 16) went through the linear layers ONCE and a plan's own ~3 pages each time:
    # the two rounds behind it cost under a quarter of one round prefilled whole (the third round, the
    # other head's, prefilled whole in both engines)
    a_round = whole_totals["ssm_prefill_tokens"] // 2
    assert totals["ssm_prefill_tokens"] - a_round < a_round / 4
    assert paths["prefill"]["engaged"] and paths["prefill"]["dispatches"] >= 3 and paths["prefill"]["reason"] is None
    for path in ("ssm", "gather", "decode"):
        assert paths[path]["engaged"] and paths[path]["dispatches"] > 0
    # the gathered form fetched the chosen blocks' pages, not the context's
    assert 0 < totals["attn_gathered_pages"] < 0.5 * totals["attn_ctx_pages"]
    assert totals["attn_sel_tokens"] < totals["attn_ctx_tokens"] and totals["index_bytes_read"] > 0
    assert totals["kv_bytes_read"] == totals["attn_gathered_pages"] * 16 * 32 * 2 * 4
    assert totals["ssm_state_bytes"] == totals["ssm_row_calls"] * 4 * 32 * 32 * 4 * 2


def test_a_partial_match_counts_a_miss_and_prefills_whole(served):
    """ONE head state exists. A second head that shares the first's opening
    page finds pages resident and no state to continue from: it is not built
    over them, its rows prefill whole, serve what a cache-off engine serves,
    and each is a counted miss."""
    (_, _, other), (hits, misses), _, _ = served["head"]
    (_, whole_other), _, _, _ = served["whole"]
    assert other == whole_other and all(other)
    assert hits == 10 and misses == 5


# ------------------------------------------------ compiled for a described v5e
@pytest.fixture(scope="module")
def one_v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    return NamedSharding(mesh, PartitionSpec())


def test_compiled_for_v5e_the_three_kernels_at_the_published_widths(one_v5e):
    """Mosaic takes, at the cell's shapes: the block-score kernel (8 rows x 8
    slots x 2 KV heads of 16 query heads over 1,023 pooled keys), the state
    pool's kernel at one group a head (32 blocks of 128 x 128 a row-layer) and
    the ragged kernel over 128 (row, slot, KV head) page lists of 256 pages in
    the merged view of the pools."""
    import functools

    from jax.experimental.compilation_cache import compilation_cache

    from mcpx.engine.kernels.block_score import block_score
    from mcpx.engine.kernels.paged_attention import ragged_paged_attention
    from mcpx.engine.kernels.ssm import _blocking, ssm_window

    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_v5e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = lambda fn, *shapes, **kw: jax.jit(fn, **kw).lower(*shapes).compile().as_text()
        assert "block_score" in compiled(
            lambda q, kc, p, l: block_score(q, kc, p, l, stride=16),
            sd((8, 8, 2, 16, 128), bf), sd((8, 2, 1023, 128), f32), sd((8,), i32), sd((8,), i32))
        B, G, N, M = 8, 32, 128, 4096
        assert _blocking(M // G, N) == 128
        assert "ssm_window" in compiled(
            lambda pool, *w: ssm_window(pool, 2, *w), sd((6, 9, N, M), f32), sd((B,), i32), sd((B,), i32),
            sd((B, M), f32), sd((B, W, M), f32), sd((B, G, N, W), f32), sd((B, G, W, N), f32), donate_argnums=(0,))
        rows, pool = 8 * 8 * 2, sd((1, 1, 2 * 2 * 8193, 16, 128), bf)
        assert "ragged_paged_attention_gathered" in compiled(
            lambda q, k, v, t, p, l: ragged_paged_attention(q, k, v, t, p, l, 0, name="ragged_paged_attention_gathered"),
            sd((rows, 1, 1, 16, 128), bf), pool, pool, sd((rows, 256), i32), sd((rows,), i32), sd((rows,), i32))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
