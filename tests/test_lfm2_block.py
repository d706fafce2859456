"""The LFM2-MoE block: layers that are a mixer followed by a feed-forward, the
mixer a gated SHORT CONVOLUTION whose whole state is its last two inputs (kept
a slot in the state pool AND a page beside the page's keys, so a radix hit at
any depth starts from the tail of its last matched page) or rotated, QK-normed
GQA at ``head_dim`` 64 (two KV heads to a 128-lane row of the page pools); the
feed-forward dense in the leading layers and 4-of-64 sigmoid-routed experts
after them. CPU, small sizes, kernels interpreted AND the jnp forms in
lockstep; the plain reference is the benchmark's block module
(``benchmarks/chip/models/lfm2.py``), imported by path, and the comparison is
the one that decides a benchmark run's ``correct``
(``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_lfm2_rehearsal.py`` beside the rehearsal child.

The state's rule (docs/engine.md) is held here in its three parts: a row's
live tail over windows with rejected slots, a page's tail written by every
program that fills a prompt page, and a hit that starts from it."""

import asyncio
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import (
    commit_prefill_tails, commit_prefill_to_pages, init_paged_kv, init_state_pool,
    write_prefill_state,
)
from mcpx.engine.paged_decode import _packed_attend, decode_chunk_paged, keep_window
from mcpx.models.gemma import moe, ssm
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, init_params, prefill
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
W = 8  # the decode window's slots
PSZ = 16


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_lfm2_t", os.path.join(CHIP_DIR, "models", "lfm2.py"))


def small(**kw):
    """The block at layer-test size, float32 so that a tail handed on and a
    tail recomputed can be compared to rounding: one leading dense layer, one
    period and a half of the pattern, 8 experts top-2, heads of 64."""
    base = dict(
        vocab_size=512, d_model=128, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=64, d_ff=256,
        layer_pattern="CACCCA", conv_kernel=3, qk_norm=True, rope_theta=1e6, norm_eps=1e-5,
        n_experts=8, n_experts_per_tok=2, d_expert=64, n_dense_layers=1,
        router_scoring="sigmoid", router_bias_scale=0.1, router_norm_eps=1e-6,
        activation="silu", tie_embeddings=True, scale_embeddings=False, norm_plus_one=False,
        dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, params_of(cfg)


# ------------------------------------------------------- the tree, the file
def test_the_tree_has_a_stack_a_kind_and_the_count_is_the_trees(model):
    cfg, params = model
    assert set(params) == {"embed", "final_norm", "conv_layers", "attn_layers", "dense_layers", "layers"}
    assert params["conv_layers"]["w_in"].shape == (4, 128, 384) and params["conv_layers"]["conv_w"].shape == (4, 128, 3)
    assert params["attn_layers"]["wk"].shape == (2, 128, 128) and params["attn_layers"]["q_norm"].shape == (2, 64)
    assert params["dense_layers"]["w_gate"].shape == (1, 128, 256) and params["layers"]["w_gate"].shape == (5, 8, 128, 64)
    assert params["layers"]["router_bias"].dtype == jnp.float32
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.n_params
    assert cfg.n_active_params == cfg.n_params - 5 * 6 * 3 * 128 * 64
    # the taps are uniform in +-1/sqrt(3): no bias, no activation has a leaf
    assert float(jnp.max(jnp.abs(params["conv_layers"]["conv_w"]))) <= 3**-0.5


def test_published_counts_of_lfm2_24b_a2b(block):
    """The file's parameter arithmetic from the tree's own count: 23.84 B
    published, 2.33 B read a token, 5,139 M held by the cut."""
    with open(os.path.join(CHIP_DIR, "configs", "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    spec = by_path("chip_harness_spec_lfm2_t", os.path.join(CHIP_DIR, "spec.py"))
    cfg = block.model_config(spec.model_keys(config), 3072)
    assert cfg.layer_pattern == "CCACCCACCC" and cfg.n_params == 5_139_163_904
    assert (cfg.head_dim, cfg.kv_pack, cfg.kv_pool_heads, cfg.kv_widths) == (64, 2, 4, (128, 128))
    assert cfg.page_state and cfg.suffix_route and not cfg.head_state and cfg.conv_tail_bytes == 16384
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_sparse_layers, cfg.n_recurrent_layers) == (8, 2, 8, 8)
    assert cfg.router_norm_eps == 1e-6 and cfg.router_scale == 1.0 and not cfg.scale_embeddings
    assert "5,139,163,904" in config["reduced"]["num_hidden_layers"] and "10.28 GB" in config["params"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog) if '"LFM2-24B-A2B"' in line)
        reduced = {"num_hidden_layers", "layer_types", "vocab_size"}
        assert {k: v for k, v in row["config"].items() if k not in reduced} == {
            k: config[k] for k in row["config"] if k not in reduced}
        assert config["source"] == row["source_url"] and row["config"]["layer_types"] == published
        assert config["layer_types"] == published[:10]
    full = dataclasses.replace(
        cfg, n_layers=40, vocab_size=65536, layer_pattern="".join("C" if t == "conv" else "A" for t in published))
    assert (full.n_conv_layers, full.n_attn_layers) == (30, 10)
    assert full.n_params == 23_843_661_440 and full.n_active_params == 2_326_881_920


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="CACCCM"), dict(layer_pattern="CACCC"), dict(conv_kernel=1), dict(norm_plus_one=True),
    dict(attn_gate=True), dict(post_norms=True), dict(rope_full_layers=False), dict(d_shared_expert=64),
    dict(layer_types=("full_attention",) * 6), dict(n_dense_layers=6),
])
def test_a_pattern_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


@pytest.mark.parametrize("asked, named", [
    ({"engine": {"hetero_batch": True}}, "hetero_batch"),
    ({"engine": {"kv_tier": {"enabled": True}}}, "kv_tier"),
    ({"model": {"quantize": "int8"}}, "int8"),
    ({"engine": {"speculative": {"enabled": True}}}, "speculative"),
])
def test_what_does_not_carry_the_tail_is_refused_at_construction(asked, named):
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match=named):
        InferenceEngine(MCPXConfig.from_dict(asked), model_cfg=small(vocab_size=384))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_and_the_pool_has_a_spec(mesh_shape):
    cfg = small()
    mesh = make_mesh(data=mesh_shape[0], model=mesh_shape[1], devices=jax.devices()[: mesh_shape[0] * mesh_shape[1]])
    specs = param_pspecs(cfg, mesh)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(jax.tree.map(lambda _: 0, specs, is_leaf=lambda s: not isinstance(s, dict))) \
        == jax.tree.structure(jax.tree.map(lambda _: 0, shapes))
    on_mesh = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    plain = params_of(cfg)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(on_mesh), jax.tree.leaves(plain)))
    # the pool: whole on every device, as the engine places it
    from jax.sharding import NamedSharding, PartitionSpec

    pool = jax.jit(lambda: init_state_pool(cfg, 4, W, 9), out_shardings=NamedSharding(mesh, PartitionSpec()))()
    assert all(leaf.sharding.is_fully_replicated for leaf in jax.tree.leaves(pool))


def test_the_state_pool_of_the_third_kind():
    cfg = small()
    pool = init_state_pool(cfg, 5, W, 33)
    assert set(pool) == {"layers", "n", "tails"}  # NO recurrent state array
    assert len(pool["layers"]) == 4 and set(pool["layers"][0]) == {"conv", "pre"}
    assert pool["layers"][0]["conv"].shape == (5, 2, 128) and pool["layers"][0]["pre"].shape == (5, W, 128)
    assert pool["tails"].shape == (4, 33, 2, 128) and pool["n"].shape == (5,) and pool["n"].dtype == jnp.int32
    served = dataclasses.replace(cfg, dtype="bfloat16")
    assert init_state_pool(served, 5, W, 33)["tails"].dtype == jnp.float32  # the tail is float32 whatever is served
    assert init_paged_kv(cfg, 33, PSZ)["k"].shape == (1, 2, 33, PSZ, 128)  # two heads of 64 a pool row
    assert init_kv_cache(cfg, 3, 32)["k"].shape == (2, 3, 32, 1, 128)
    assert init_state_pool(small(layer_pattern="AAAAAA"), 5, W, 33) == {}


# ----------------------------------------------- the mixer, the router, the head
def test_the_short_convolution_is_the_definition_and_a_tail_continues_it():
    """``v_t = sum_k w[:, k] u_{t-2+k}`` with zeros before the sequence; cut
    anywhere, the second part from the first part's tail is the whole."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 11, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    zero = jnp.zeros((2, 2, 8), jnp.float32)
    whole = ssm.short_conv(u, zero, w)
    up = np.pad(np.asarray(u), ((0, 0), (2, 0), (0, 0)))
    plain = sum(np.asarray(w)[:, k] * up[:, k : k + 11] for k in range(3))
    np.testing.assert_allclose(whole, plain, rtol=1e-6, atol=1e-6)
    for cut in (1, 2, 5, 10):
        tail = ssm.tail_at(u, zero, jnp.full((2,), cut, jnp.int32))
        np.testing.assert_array_equal(tail, np.asarray(up)[:, cut : cut + 2])
        np.testing.assert_allclose(ssm.short_conv(u[:, cut:], tail, w), whole[:, cut:], rtol=1e-6, atol=1e-6)


def test_a_float32_operand_read_as_two_bfloat16_operands_loses_under_a_part_in_2_to_the_14():
    """``ssm.dot_split``: the conv mixer's two products read their float32
    operand as its bfloat16 rounding and what the rounding left, in ONE product
    over the weights. Against the float64 product: 200 times closer than the
    operand rounded once, and the remainder is NOT all zeros (an explicit
    ``reduce_precision``: a cast there and back is one the TPU compiler drops)."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 5, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 64)) / 16, jnp.bfloat16)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    split = np.asarray(ssm.dot_split(x, w), np.float64)
    once = np.asarray(jnp.einsum("bte,ed->btd", x.astype(jnp.bfloat16), w, preferred_element_type=jnp.float32), np.float64)
    err = lambda a: np.sqrt(np.mean((a - exact) ** 2)) / np.std(exact)
    assert err(split) < 2.0**-14 and err(once) > 200 * err(split)
    text = jax.jit(ssm.dot_split).lower(x, w).as_text()
    assert "reduce_precision" in text
    # float32 weights (the tests' own configurations): one plain product
    w32 = w.astype(jnp.float32)
    np.testing.assert_allclose(ssm.dot_split(x, w32), exact, rtol=1e-5, atol=1e-5)


def test_the_router_is_trinity_minis_on_the_same_scores_but_for_its_two_constants():
    """Sigmoid scores, the bias in the CHOICE alone, the chosen renormalised:
    the same ``moe.route``. On the same scores the two blocks choose the same
    experts; the weights differ by trinity-mini's ``route_scale`` and by the
    ``1e-6`` this family adds to the sum."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(40, 128)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(128, 8)) / 128**0.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.1, jnp.float32)
    cfg = small()
    trinity = GemmaConfig(
        n_experts=8, n_experts_per_tok=2, d_expert=64, router_scoring="sigmoid", router_bias_scale=0.1,
        router_scale=2.826, activation="silu",
    )
    chosen, w = moe.route(x, router, cfg, bias)
    chosen_t, w_t = moe.route(x, router, trinity, bias)
    np.testing.assert_array_equal(chosen, chosen_t)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ router)), np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(w, s / (s.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w_t) / 2.826, s / s.sum(-1, keepdims=True), rtol=1e-6)
    assert not np.array_equal(chosen, moe.route(x, router, cfg, None)[0])  # the bias chose
    # ties go to the lower expert
    tied = moe.route(jnp.zeros((1, 128)), router, cfg, None)[0]
    np.testing.assert_array_equal(tied, [[0, 1]])


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_heads_of_64_two_to_a_pool_row_are_the_plain_grouped_attention(path):
    """``_packed_attend`` (the ragged kernel, interpreted, and the jnp gather)
    against grouped attention computed head by head on unpacked keys: a query
    padded with zeros over its row-mate's lanes scores its own head alone, at
    the head's own scale."""
    rng = np.random.default_rng(2)
    cfg = small(dtype="bfloat16")
    B, S, K, G, hd, n_pages, p_max = 3, 8, 2, 2, 64, 13, 4
    q = jnp.asarray(rng.normal(size=(B, S, K, G, hd)), jnp.bfloat16)
    k_tok = jnp.asarray(rng.normal(size=(n_pages * PSZ, K, hd)), jnp.bfloat16)
    v_tok = jnp.asarray(rng.normal(size=(n_pages * PSZ, K, hd)), jnp.bfloat16)
    pack = lambda a: a.reshape(n_pages, PSZ, 1, K * hd).transpose(2, 0, 1, 3)[:, None]  # [1, 1, N, psz, 128]
    table = jnp.asarray([[1, 5, 2, 0], [7, 3, 0, 0], [4, 9, 12, 6]], jnp.int32)
    positions = jnp.asarray([21, 9, 50], jnp.int32)
    q_lens = jnp.asarray([8, 3, 0], jnp.int32)
    got = _packed_attend(
        q, pack(k_tok), pack(v_tok), table, positions, q_lens, 0, cfg,
        mesh=one_device(), use_pallas=path == "kernel", interpret=True,
    )
    want = np.zeros((B, S, K, G, hd), np.float32)
    kf, vf = np.asarray(k_tok, np.float32), np.asarray(v_tok, np.float32)
    for b in range(B):
        slots = (np.asarray(table)[b][:, None] * PSZ + np.arange(PSZ)).reshape(-1)
        for s in range(int(q_lens[b])):
            seen = slots[: int(positions[b]) + s + 1]
            for h in range(K):
                logits = np.asarray(q[b, s, h], np.float32) @ kf[seen, h].T / 8.0  # 64 ** -0.5
                p = np.exp(logits - logits.max(-1, keepdims=True))
                want[b, s, h] = (p / p.sum(-1, keepdims=True)) @ vf[seen, h]
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2e-2, atol=2e-2)  # bfloat16 weights and output
    assert not np.any(np.asarray(got[2])) and not np.any(np.asarray(got[1, 3:]))  # idle row, pad slots: zeros


def test_the_kernel_and_the_jnp_gather_agree_on_packed_heads():
    rng = np.random.default_rng(3)
    cfg = small()
    q = jnp.asarray(rng.normal(size=(2, 8, 2, 2, 64)), jnp.float32)
    pool = lambda: jnp.asarray(rng.normal(size=(1, 2, 9, PSZ, 128)), jnp.float32)
    k, v = pool(), pool()
    args = (q, k, v, jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32), jnp.asarray([17, 3], jnp.int32),
            jnp.asarray([8, 5], jnp.int32), 1, cfg)
    a = _packed_attend(*args, mesh=one_device(), use_pallas=True, interpret=True)
    b = _packed_attend(*args, mesh=one_device(), use_pallas=False, interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


# ------------------------------------------ the state's rule, through the cache

def _tables(B, pages_per_row):
    return jnp.asarray(1 + np.arange(B * pages_per_row, dtype=np.int32).reshape(B, pages_per_row))


@functools.partial(jax.jit, static_argnames=("cfg", "n_pages", "T"))
def _prefill(cfg, params, tokens, lens, table, n_pages, T):
    B = tokens.shape[0]
    dense = init_kv_cache(cfg, B, T)
    last, dense = prefill(params, cfg, tokens, lens, dense, last_only=True)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, n_pages, PSZ), dense, table, lens, PSZ)
    state = write_prefill_state(init_state_pool(cfg, B, W, n_pages), jnp.arange(B, dtype=jnp.int32), dense["ssm"])
    state["tails"] = commit_prefill_tails(state["tails"], dense["ssm"], table, PSZ)
    return last, {**pools, "state": state}


@functools.partial(jax.jit, static_argnames=("cfg", "commit", "path"))
def _chunk_j(cfg, params, tokens, pos, table, pools, q_lens, at, slots, *, commit, path):
    return decode_chunk_paged(
        params, cfg, tokens, pos, table, pools, use_pallas=path == "kernel", interpret=True,
        mesh=one_device(), logits_at=at, q_lens=q_lens,
        state_slots=(None, slots) if commit else None, commit=commit,
    )


def _chunk(cfg, params, tokens, pos, table, pools, q_lens, *, commit=False, path="jnp", at=None, slots=None):
    B = tokens.shape[0]
    return _chunk_j(
        cfg, params, tokens, pos, table, pools, q_lens,
        jnp.maximum(q_lens - 1, 0) if at is None else at,
        jnp.arange(B, dtype=jnp.int32) if slots is None else slots, commit=commit, path=path,
    )


def _ref(block, cfg, params, seq):
    """The reference's logits at EVERY position of ``seq`` [len, V]: causal, so
    one call serves every prefix of it. Padded to a multiple of 32 (one program
    a length class)."""
    n = len(seq)
    padded = np.zeros((-(-n // 32) * 32,), np.int32)
    padded[:n] = seq
    return np.asarray(_ref_j(block, cfg, params, jnp.asarray(padded)))[:n]


_REF_JITS = {}


def _ref_j(block, cfg, params, tokens):
    key = (id(block), cfg)
    if key not in _REF_JITS:
        dims = dataclasses.asdict(cfg)
        _REF_JITS[key] = jax.jit(lambda p, t: block.reference_logits(p, dims, t))
    return _REF_JITS[key](params, tokens)


@functools.lru_cache(maxsize=None)
def _windows(path):
    """ONE segment of 16 decode windows a path -> (what each forward read: a
    (row, position in its kept sequence, logits) a live row, the rows' kept
    sequences at the end)."""
    cfg = small()
    params = params_of(cfg)
    rng = np.random.default_rng(16)
    B, T, ppr = 3, 32, 12
    table, n_pages = _tables(B, ppr), 1 + B * ppr
    lens = np.asarray([20, 32, 7], np.int32)
    seqs = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in lens]
    tokens = np.zeros((B, T), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, : len(s)] = s
    last, pools = _prefill(cfg, params, jnp.asarray(tokens), jnp.asarray(lens), table, n_pages, T)
    read = [[(b, len(seqs[b]) - 1, np.asarray(last[b])) for b in range(B)]]
    for f in range(16):
        q_lens = np.asarray([1 + (3 * b + 5 * f + 2) % W for b in range(B)], np.int32)
        q_lens[2] = 0 if f % 3 == 1 else q_lens[2]  # an idle row changes nothing
        kept = np.minimum(q_lens, 1 + (f + np.arange(B)) % 3)  # 1 + accepted
        window = rng.integers(0, cfg.vocab_size, size=(B, W)).astype(np.int32)
        pos = jnp.asarray([len(s) for s in seqs], jnp.int32)
        logits, pools = _chunk(
            cfg, params, jnp.asarray(window), pos, table, pools, jnp.asarray(q_lens), path=path,
            at=jnp.asarray(np.maximum(kept - 1, 0)),
        )
        pools["state"] = keep_window(pools["state"], jnp.arange(B), jnp.asarray(kept), jnp.asarray(q_lens > 0))
        now = []
        for b in range(B):
            if q_lens[b]:
                seqs[b] += list(window[b, : kept[b]])
                now.append((b, len(seqs[b]) - 1, np.asarray(logits[b])))
        read.append(now)
    return cfg, params, read, seqs


@pytest.mark.parametrize("path", ["kernel", "jnp"])
@pytest.mark.parametrize("forwards", [4, 8, 12, 16])
def test_windows_with_rejected_slots_keep_one_plus_accepted(block, path, forwards):
    """Part 1 of the rule: a row's live tail is ``(u_{t-1}, u_t)`` at its last
    COMMITTED token. A segment of decode windows of uneven live widths, each
    keeping ``1 + accepted`` of its slots (the rest are wrong proposals whose
    ``u`` must never reach the tail): after every forward of the segment's
    first ``forwards`` the logits at each row's last KEPT slot are the
    reference's at that token of the kept sequence. float32: agreement to
    rounding (2e-4 of a logit; a step that keeps a rejected slot reads
    thousands of times that, the controls below)."""
    cfg, params, read, seqs = _windows(path)
    want = [_ref(block, cfg, params, s) for s in seqs]
    checked = 0
    for now in read[: forwards + 1]:
        for b, at, logits in now:
            np.testing.assert_allclose(logits, want[b][at], atol=2e-4)
            checked += 1
    assert checked >= 3 + 2 * forwards


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_a_hit_at_a_split_and_at_a_whole_node_gives_the_logits_a_whole_prefill_gives(model, block, path):
    """Parts 2 and 3 of the rule. Row 0 prefills a 64-token prompt whole: its
    four pages get their keys AND their tails. Three rows then share its
    pages by id, as the radix tree hands them out: one matches the WHOLE node
    (64 tokens), one a SPLIT of it (32, the node cut at a page boundary), one
    a single page; each takes the suffix route from the tail of its last
    matched page and reads the logits of its own whole prefill (and the
    reference's). A row that matches nothing starts from zeros. The suffix
    route writes the tails of the pages IT fills: a second generation of hits
    starts from those."""
    cfg, params = model
    rng = np.random.default_rng(7)
    T, ppr, n_pages = 128, 8, 64
    head = list(rng.integers(0, cfg.vocab_size, size=64))
    own = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (37, 21, 40, 33)]
    depth = [64, 32, 16, 0]
    seqs = [head[:d] + o for d, o in zip(depth, own)]
    # row 0 of the first program: the head, whole, into pages 1..4
    first = np.zeros((1, T), np.int32)
    first[0, :64] = head
    _, pools = _prefill(cfg, params, jnp.asarray(first), jnp.asarray([64], jnp.int32), jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]]), n_pages, T)
    pools["state"] = init_state_pool(cfg, 4, W, n_pages) | {"tails": pools["state"]["tails"]}
    table = np.zeros((4, ppr), np.int32)
    for b, d in enumerate(depth):
        shared = d // PSZ
        table[b, :shared] = [1, 2, 3, 4][:shared]
        table[b, shared:] = 10 + 8 * b + np.arange(ppr - shared)
    suffix = np.zeros((4, T), np.int32)
    for b, o in enumerate(own):
        suffix[b, : len(o)] = o
    q_lens = jnp.asarray([len(o) for o in own], jnp.int32)
    logits, pools = _chunk(
        cfg, params, jnp.asarray(suffix), jnp.asarray(depth, jnp.int32), jnp.asarray(table), pools, q_lens,
        commit=True, path=path,
    )
    whole = np.zeros((4, T), np.int32)
    for b, s in enumerate(seqs):
        whole[b, : len(s)] = s
    want, whole_pools = _prefill(
        cfg, params, jnp.asarray(whole), jnp.asarray([len(s) for s in seqs], jnp.int32), _tables(4, ppr), 64, T)
    for b in range(4):
        np.testing.assert_allclose(logits[b], want[b], atol=2e-4)
        np.testing.assert_allclose(logits[b], _ref(block, cfg, params, seqs[b])[-1], atol=2e-4)
        # the live tail the suffix route left is the whole prefill's
        for c in range(cfg.n_conv_layers):
            np.testing.assert_allclose(
                pools["state"]["layers"][c]["conv"][b], whole_pools["state"]["layers"][c]["conv"][b], atol=1e-4)
    # the pages the suffix FILLED carry their tails, and no other page was touched
    filled = {b: (depth[b] + len(own[b])) // PSZ - depth[b] // PSZ for b in range(4)}
    for b in range(4):
        for i in range(filled[b]):
            mine, theirs = table[b, depth[b] // PSZ + i], 1 + ppr * b + depth[b] // PSZ + i
            np.testing.assert_allclose(pools["state"]["tails"][:, mine], whole_pools["state"]["tails"][:, theirs], atol=1e-4)
    np.testing.assert_array_equal(pools["state"]["tails"][:, table[0, 6]], 0)  # (64 + 37 = 101 tokens: page 6 is not full)
    # a second generation: a row that matches row 0's suffix pages too (96 of its 101 tokens)
    again = seqs[0][:96] + list(rng.integers(0, cfg.vocab_size, size=9))
    t2 = np.zeros((1, ppr), np.int32)
    t2[0, :6], t2[0, 6:] = table[0, :6], [60, 61]
    sfx = np.zeros((1, T), np.int32)
    sfx[0, :9] = again[96:]
    state = init_state_pool(cfg, 1, W, n_pages) | {"tails": pools["state"]["tails"]}
    logits2, _ = _chunk(
        cfg, params, jnp.asarray(sfx), jnp.asarray([96], jnp.int32), jnp.asarray(t2), {**pools, "state": state},
        jnp.asarray([9], jnp.int32), commit=True, path=path,
    )
    np.testing.assert_allclose(logits2[0], _ref(block, cfg, params, again)[-1], atol=2e-4)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_a_suffix_cohort_of_three_in_four_rows_is_the_cohort_in_eight(model, path):
    """The admission cohort's row bucket is padding and nothing else (ISSUE
    57: the suffix route's 4-row bucket). Three rows of which ONE matched two
    pages of a resident prompt and starts from the second page's tail, its
    cohort-mates from zeros, padded as the engine pads them (a padding row:
    one pad token at position 0 over the null page, its slot out of range)
    to 4 rows and to 8: the same last logits, live tails and pages' tails."""
    cfg, params = model
    rng = np.random.default_rng(57)
    T, ppr, n_pages, n_slots = 64, 8, 40, 8
    head = list(rng.integers(0, cfg.vocab_size, size=32))
    own = [list(rng.integers(0, cfg.vocab_size, size=n)) for n in (21, 40, 33)]
    depth = [32, 0, 0]
    first = np.zeros((1, T), np.int32)
    first[0, :32] = head
    _, seeded = _prefill(cfg, params, jnp.asarray(first), jnp.asarray([32], jnp.int32),
                         jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]]), n_pages, T)

    def run(A):
        pools = dict(seeded)
        pools["state"] = init_state_pool(cfg, n_slots, W, n_pages) | {"tails": seeded["state"]["tails"]}
        tokens, lens, pos = np.zeros((A, T), np.int32), np.ones((A,), np.int32), np.zeros((A,), np.int32)
        table, slots = np.zeros((A, ppr), np.int32), np.full((A,), n_slots, np.int32)
        for b, o in enumerate(own):
            tokens[b, : len(o)], lens[b], pos[b], slots[b] = o, len(o), depth[b], b
            shared = depth[b] // PSZ
            table[b, :shared] = [1, 2][:shared]
            table[b, shared:] = 10 + 8 * b + np.arange(ppr - shared)
        logits, pools = _chunk(cfg, params, jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(table), pools,
                               jnp.asarray(lens), commit=True, path=path, slots=jnp.asarray(slots))
        return np.asarray(logits)[:3], pools

    (four, pools4), (eight, pools8) = run(4), run(8)
    np.testing.assert_allclose(four, eight, atol=1e-5)
    assert (four.argmax(-1) == eight.argmax(-1)).all()
    for a, b in zip(jax.tree.leaves(pools4), jax.tree.leaves(pools8)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    conv = pools4["state"]["layers"][0]["conv"]
    assert float(jnp.abs(conv[:3]).sum()) > 0 and float(jnp.abs(conv[3:]).sum()) == 0  # no padding row's tail


def test_a_head_built_in_chunks_hands_on_its_tail(model, block):
    """A declared head longer than a prefill bucket is built a chunk at a time,
    each a suffix prefill over the pages of those before it: the tail crosses
    from chunk to chunk through the LAST PAGE of each (no head slot exists),
    and a row behind the head starts from its last page's."""
    cfg, params = model
    rng = np.random.default_rng(11)
    head = list(rng.integers(0, cfg.vocab_size, size=96))
    own = list(rng.integers(0, cfg.vocab_size, size=13))
    n_pages, ppr = 16, 8
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    pools = {**init_paged_kv(cfg, n_pages, PSZ), "state": init_state_pool(cfg, 1, W, n_pages)}
    nowhere = jnp.asarray([1], jnp.int32)  # the build's row owns no slot: out of range, written nowhere
    for lo in (0, 32, 64):
        chunk = jnp.asarray([head[lo : lo + 32]], jnp.int32)
        _, pools = _chunk(
            cfg, params, chunk, jnp.asarray([lo], jnp.int32), table, pools, jnp.asarray([32], jnp.int32),
            commit=True, slots=nowhere,
        )
    np.testing.assert_array_equal(pools["state"]["layers"][0]["conv"], 0)  # no slot took the head's tail
    whole = np.zeros((1, 128), np.int32)
    whole[0, :96] = head
    _, at_once = _prefill(cfg, params, jnp.asarray(whole), jnp.asarray([96], jnp.int32), table, n_pages, 128)
    np.testing.assert_allclose(pools["state"]["tails"][:, 1:7], at_once["state"]["tails"][:, 1:7], atol=1e-4)
    sfx = np.zeros((1, 32), np.int32)
    sfx[0, :13] = own
    logits, _ = _chunk(cfg, params, jnp.asarray(sfx), jnp.asarray([96], jnp.int32), table, pools, jnp.asarray([13], jnp.int32), commit=True)
    np.testing.assert_allclose(logits[0], _ref(block, cfg, params, head + own)[-1], atol=2e-4)


# ----------------------------------------------------------- the served path
def _engine_config(**engine):
    return MCPXConfig.from_dict({
        "model": {"max_seq_len": 1024},
        "engine": {"max_batch_size": 4, "max_decode_len": 24, "kv_page_size": PSZ, "max_pages_per_seq": 32,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": True,
                   "warmup_compile": False, **engine},
    })


HEAD = "Catalogue.\n" + "".join(f"service {i}: does thing number {i} for the fleet\n" for i in range(6))
SPLIT = "Catalogue.\n" + "".join(f"service {i}: does thing number {i} for the fleet\n" for i in range(3)) \
    + "and then something else entirely, a branch of its own that shares three services.\n"
INTENTS = [f"intent {i}: compose and route {i}. JSON:" for i in range(4)]
BUDGETS = [3, 20, 9, 14]


def _serve_plans(config, rounds):
    """One engine; ``rounds`` of (head text, declared or not), each serving the
    four intents behind that head -> (tokens a round, hits and misses, the
    segment attributes' sums, the kernel paths, the tree's own hits, /healthz's
    placement)."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        probe = InferenceEngine(config)
        cfg = small(vocab_size=probe.tokenizer.vocab_size, max_seq_len=1024, dtype="float32")
        eng = InferenceEngine(config, model_cfg=cfg, mesh=one_device())
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
            stats = eng.queue_stats()
            return (got, (eng._prefix_state_hits, eng._prefix_state_misses), dict(eng._layer_kind_totals),
                    eng.pallas_paths()["paths"], eng._prefix_cache.hits, stats.get("state_pool"))
        finally:
            await eng.aclose()

    return asyncio.run(go())


@pytest.fixture(scope="module")
def served():
    return {
        # the head undeclared (rows insert it themselves), again (whole-node hits), then a prompt family
        # that shares the head's first pages alone: the node SPLITS
        "tree": _serve_plans(_engine_config(), [(HEAD, False), (HEAD, False), (SPLIT, False)]),
        # the head DECLARED: built once, before the first cohort
        "declared": _serve_plans(_engine_config(), [(HEAD, True), (SPLIT, True)]),
        "whole": _serve_plans(_engine_config(prefix_cache=False), [(HEAD, False), (SPLIT, False)]),
    }


def test_served_plans_over_radix_hits_are_the_plans_prefilled_whole(served):
    (first, again, split), (hits, misses), totals, paths, tree_hits, pool = served["tree"]
    (whole, whole_split), (no_hits, no_misses), whole_totals, _, _, _ = served["whole"]
    assert first == whole and again == whole and split == whole_split and all(first) and all(split)
    # every row that matched the tree took the suffix route from a page's tail: a hit a matched row, NO miss
    assert hits == tree_hits and hits >= 7 and misses == 0 and (no_hits, no_misses) == (0, 0)
    assert paths["prefill"]["engaged"] and paths["prefill"]["dispatches"] >= 2 and paths["prefill"]["reason"] is None
    assert paths["decode"]["engaged"] and paths["decode"]["dispatches"] > 0 and "ssm" not in paths
    # the matched pages went through the convolutions once: the three rounds cost under two prefilled whole
    a_round = whole_totals["conv_prefill_tokens"] // 2
    assert totals["conv_prefill_tokens"] < 2 * a_round
    for key in ("conv_row_calls", "conv_slots", "conv_tokens", "conv_tail_bytes", "conv_weight_bytes",
                "moe_assignments", "weight_bytes_routed", "attn_ctx_tokens", "kv_bytes_read"):
        assert totals[key] > 0, key
    assert "ssm_state_bytes" not in totals and "ssm_row_calls" not in totals
    # a call reads and writes a slot's tail and pending window: (2 + 8) x 128 x 4 bytes, both ways
    assert totals["conv_tail_bytes"] == totals["conv_row_calls"] * 10 * 128 * 4 * 2
    assert 0 < totals["conv_weight_bytes"] < totals["weight_bytes_read"]
    # /healthz: 4 slots; a page's tails are 4 conv layers x 2 x 128 x 4 bytes
    assert pool["slots"] == 4 and pool["page_tails_bytes"] % (4 * 2 * 128 * 4) == 0
    assert 0 < pool["page_tails_bytes"] < pool["bytes"]


def test_a_declared_head_is_built_once_and_its_rows_start_from_its_last_page(served):
    (first, split), (hits, misses), totals, paths, _, _ = served["declared"]
    (whole, whole_split), _, whole_totals, _, _, _ = served["whole"]
    assert first == whole and split == whole_split
    assert hits == 8 and misses == 0  # every row behind a declared head hits, the first cohort's too
    assert totals["conv_prefill_tokens"] < whole_totals["conv_prefill_tokens"]


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


def test_compiled_for_v5e_the_ragged_kernel_on_packed_heads_at_the_published_widths(one_v5e):
    """Mosaic takes the ragged kernel at the cell's shapes with the head's own
    scale: 4 pool rows of two KV heads, 8 queries a row, 128 lanes; a decode
    window of 8 slots and a suffix-prefill block of 128."""
    import functools

    from jax.experimental.compilation_cache import compilation_cache

    from mcpx.engine.kernels.paged_attention import ragged_paged_attention

    bf, i32 = jnp.bfloat16, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_v5e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        pool = sd((4, 2, 257, 16, 128), bf)
        for S in (8, 128):
            text = jax.jit(
                lambda q, k, v, t, p, n: ragged_paged_attention(q, k, v, t, p, n, 1, scale=0.125)
            ).lower(sd((8, S, 4, 8, 128), bf), pool, pool, sd((8, 32), i32), sd((8,), i32), sd((8,), i32)).compile().as_text()
            assert "ragged_paged_attention" in text
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
