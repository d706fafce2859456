"""Kernel tests (SURVEY.md §4.2): Pallas paged attention in interpret mode
vs the pure-jnp reference, plus allocator invariants — property-style over
ragged page tables and odd shapes."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.errors import EngineError
from mcpx.ops import paged_attention, paged_attention_reference
from mcpx.engine.kv_cache import (
    PageAllocator,
    commit_prefill_to_pages,
    init_paged_kv,
)
from mcpx.models.gemma.config import GemmaConfig


def make_case(key, B, K, G, hd, psz, p_max, n_pages, max_len):
    """Random q/pages/page_table/seq_lens with ragged lengths."""
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, K, G, hd), jnp.float32)
    k_pages = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    v_pages = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    rng = random.Random(int(jax.random.randint(ks[3], (), 0, 2**31 - 1)))
    seq_lens = [rng.randint(1, max_len) for _ in range(B)]
    table = np.zeros((B, p_max), np.int32)
    used = set([0])
    for b, sl in enumerate(seq_lens):
        need = -(-sl // psz)
        for i in range(need):
            p = rng.choice([x for x in range(1, n_pages) if x not in used])
            used.add(p)
            table[b, i] = p
    return q, k_pages, v_pages, jnp.array(table), jnp.array(seq_lens, jnp.int32)


@pytest.mark.parametrize(
    "B,K,G,hd,psz,maxlen",
    [
        (1, 1, 8, 128, 16, 40),  # MQA
        (3, 2, 2, 128, 16, 50),  # GQA, ragged batch
        (2, 4, 1, 256, 8, 17),   # MHA-ish, odd lengths
    ],
)
def test_kernel_matches_reference(B, K, G, hd, psz, maxlen):
    p_max = -(-maxlen // psz) + 1
    n_pages = B * p_max + 2
    q, kp, vp, table, lens = make_case(
        jax.random.PRNGKey(B * 100 + K), B, K, G, hd, psz, p_max, n_pages, maxlen
    )
    # layer=1 exercises the prefetched layer-slice selection.
    ref = paged_attention_reference(q, kp, vp, table, lens, layer=1)
    out = paged_attention(q, kp, vp, table, lens, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S", [1, 8], ids=["single-query", "chunk"])
@pytest.mark.parametrize(
    "context",
    [1, 16, 17, 256, 257, 272, 512],
    ids=["one-key", "one-page", "page-plus-key", "one-block", "block-plus-key",
         "last-block-one-page", "two-blocks"],
)
def test_single_query_and_chunk_kernels_at_the_key_blocks_edges(context, S):
    """The ``q_lens = S`` and single-query specialisations run the one
    blocked body: every row's context ends on the same edge of a key block
    (16 pages of 16 keys), at the cells' head_dim."""
    from mcpx.engine.kernels.paged_attention import (
        paged_attention_chunk,
        paged_attention_chunk_reference,
    )

    B, K, G, hd, psz, p_max = 2, 2, 2, 128, 16, 33
    n_pages = B * p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(context), 3)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = jnp.asarray(
        1 + np.random.default_rng(context).permutation(n_pages - 1).reshape(B, p_max), jnp.int32
    )
    if S == 1:
        q = jax.random.normal(ks[0], (B, K, G, hd), jnp.float32)
        lens = jnp.asarray([context, max(1, context - 1)], jnp.int32)
        out = paged_attention(q, kp, vp, table, lens, 1, interpret=True)
        ref = paged_attention_reference(q, kp, vp, table, lens, layer=1)
    else:
        q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
        starts = jnp.asarray([context - 1, context], jnp.int32)  # last query: context + S - 1
        out = paged_attention_chunk(q, kp, vp, table, starts, 1, interpret=True)
        ref = paged_attention_chunk_reference(q, kp, vp, table, starts, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_reference_matches_dense_attention():
    """The paged reference itself must equal vanilla dense attention."""
    B, K, G, hd, psz = 1, 1, 4, 64, 4
    S = 12
    key = jax.random.PRNGKey(0)
    q, kp, vp, table, _ = make_case(key, B, K, G, hd, psz, 4, 8, S)
    lens = jnp.array([S])
    # Dense K/V from the pages the table points to.
    k = kp[:, 0][:, np.asarray(table[0])].reshape(K, -1, hd)[:, :S]
    v = vp[:, 0][:, np.asarray(table[0])].reshape(K, -1, hd)[:, :S]
    logits = jnp.einsum("kgh,ksh->kgs", q[0], k) / np.sqrt(hd)
    dense = jnp.einsum("kgs,ksh->kgh", jax.nn.softmax(logits, -1), v)
    ref = paged_attention_reference(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(ref[0]), np.asarray(dense), rtol=1e-5, atol=1e-5)


def test_commit_and_decode_write_roundtrip():
    cfg = GemmaConfig(dtype="float32", n_layers=2, n_kv_heads=2, head_dim=16)
    psz, n_pages, B, T = 4, 16, 2, 8
    paged = init_paged_kv(cfg, n_pages, psz)
    dense = {
        "k": jax.random.normal(jax.random.PRNGKey(1), (2, B, T, 2, 16)),
        "v": jax.random.normal(jax.random.PRNGKey(2), (2, B, T, 2, 16)),
    }
    table = jnp.array([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    seq_lens = jnp.array([T, 5])
    paged = commit_prefill_to_pages(paged, dense, table, seq_lens, psz)
    # Page 1 holds seq0 chunk0, page 2 chunk1.
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 0, 1]),  # [K, psz, hd]
        np.asarray(dense["k"][0, 0, :psz].transpose(1, 0, 2)),
    )
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 1, 4]),
        np.asarray(dense["k"][1, 1, psz:].transpose(1, 0, 2)),
    )
    # Decode write at position 5 for seq1 -> page 4 slot 1.
    k_new = jax.random.normal(jax.random.PRNGKey(3), (2, B, 2, 16))
    v_new = jax.random.normal(jax.random.PRNGKey(4), (2, B, 2, 16))
    from mcpx.engine.paged_decode import _kv_window, _write_kv_window

    window = _kv_window(jnp.array([8, 5]), table, 1, psz, n_pages)
    for layer in range(2):
        paged = {
            "k": _write_kv_window(paged["k"], layer, k_new[layer][:, None], window),
            "v": _write_kv_window(paged["v"], layer, v_new[layer][:, None], window),
        }
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 0, 4, 1]), np.asarray(k_new[0, 1])
    )
    np.testing.assert_allclose(
        np.asarray(paged["v"][:, 1, 4, 1]), np.asarray(v_new[1, 1])
    )
    # seq1's prefill rows in the same page are still there
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 1, 4, 0]), np.asarray(dense["k"][1, 1, psz])
    )


def test_chunk_reference_matches_per_query_fold():
    """paged_attention_chunk_reference == per-query reference with the chunk
    folded into the batch dim (the two formulations the decode paths use)."""
    from mcpx.engine.kernels.paged_attention import paged_attention_chunk_reference

    B, S, K, G, hd, psz, p_max = 2, 4, 2, 3, 16, 4, 6
    n_pages = B * p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = jnp.asarray(np.arange(B * p_max, dtype=np.int32).reshape(B, p_max) + 1)
    start = jnp.array([2, 9], jnp.int32)

    chunk = paged_attention_chunk_reference(q, kp, vp, table, start)

    pos = start[:, None] + jnp.arange(S)  # [B, S]
    fold = paged_attention_reference(
        q.reshape(B * S, K, G, hd),
        kp,
        vp,
        jnp.broadcast_to(table[:, None], (B, S, p_max)).reshape(B * S, p_max),
        (pos + 1).reshape(B * S),
    ).reshape(B, S, K, G, hd)
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(fold), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "B,S,K,G,hd,psz,maxstart",
    [
        (1, 8, 1, 8, 128, 16, 40),  # MQA chunk (Gemma-2B shape class)
        (3, 4, 2, 2, 128, 16, 50),  # GQA, ragged starts
        (2, 1, 4, 1, 256, 8, 17),   # S=1 degenerate (plain decode step)
    ],
)
def test_chunk_kernel_matches_chunk_reference(B, S, K, G, hd, psz, maxstart):
    from mcpx.engine.kernels.paged_attention import (
        paged_attention_chunk,
        paged_attention_chunk_reference,
    )

    p_max = -(-(maxstart + S) // psz) + 1
    n_pages = B * p_max + 2
    ks = jax.random.split(jax.random.PRNGKey(B * 10 + S), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    rng = random.Random(7)
    starts = jnp.asarray([rng.randint(0, maxstart) for _ in range(B)], jnp.int32)
    table = np.zeros((B, p_max), np.int32)
    used = {0}
    for b in range(B):
        for i in range(p_max):
            p = rng.choice([x for x in range(1, n_pages) if x not in used])
            used.add(p)
            table[b, i] = p
    table = jnp.asarray(table)
    ref = paged_attention_chunk_reference(q, kp, vp, table, starts, layer=1)
    out = paged_attention_chunk(q, kp, vp, table, starts, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_chunk_kernel_clamps_overhanging_rows():
    """A finished row's frozen start + chunk width may overhang the page
    table by up to one chunk; the kernel must clamp its page walk to the
    table width instead of reading page_table[b, Pmax] out of bounds
    (regression: done rows in the speculative decode loop)."""
    from mcpx.engine.kernels.paged_attention import (
        paged_attention_chunk,
        paged_attention_chunk_reference,
    )

    B, S, K, G, hd, psz, p_max = 1, 4, 1, 2, 16, 4, 3
    n_pages = p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    start = jnp.array([p_max * psz - 1], jnp.int32)  # last in-table position
    out = paged_attention_chunk(q, kp, vp, table, start, interpret=True)
    ref = paged_attention_chunk_reference(q, kp, vp, table, start)
    # Query 0 is fully in-table; its output must be exact. Later queries'
    # visible ranges overhang the table and are garbage by contract.
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(ref[:, 0]), rtol=2e-5, atol=2e-5
    )


def test_decode_chunk_matches_sequential_steps():
    """decode_chunk_paged(S tokens) == S x decode_step_paged: same logits at
    every chunk position and identical page pools afterward (the speculation
    verify pass must be an exact re-expression of sequential decode)."""
    from mcpx.engine.paged_decode import decode_chunk_paged, decode_step_paged
    from mcpx.models.gemma.model import init_params

    cfg = GemmaConfig(
        dtype="float32", d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64
    )
    B, S, psz, p_max = 2, 5, 4, 4
    n_pages = B * p_max + 1
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool0 = {
        "k": jax.random.normal(
            jax.random.PRNGKey(1), (cfg.n_kv_heads, cfg.n_layers, n_pages, psz, cfg.head_dim)
        ),
        "v": jax.random.normal(
            jax.random.PRNGKey(2), (cfg.n_kv_heads, cfg.n_layers, n_pages, psz, cfg.head_dim)
        ),
    }
    table = jnp.asarray(np.arange(B * p_max, dtype=np.int32).reshape(B, p_max) + 1)
    pos0 = jnp.array([3, 6], jnp.int32)  # mid-page, ragged starts
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)

    seq_pool = {k: v for k, v in pool0.items()}
    seq_logits = []
    for i in range(S):
        lg, seq_pool = decode_step_paged(
            params, cfg, tokens[:, i], pos0 + i, table, seq_pool, use_pallas=False
        )
        seq_logits.append(lg)
    seq_logits = jnp.stack(seq_logits, axis=1)  # [B, S, V]

    chunk_logits, chunk_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0, use_pallas=False
    )
    np.testing.assert_allclose(
        np.asarray(chunk_logits), np.asarray(seq_logits), rtol=2e-5, atol=2e-5
    )
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(chunk_pool[key]), np.asarray(seq_pool[key]), rtol=2e-5, atol=2e-5
        )


def test_decode_chunk_pallas_interpret_matches_reference_path():
    """Chunk forward with the Pallas kernel (interpret mode) == jnp path."""
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.model import init_params

    cfg = GemmaConfig(
        dtype="float32", d_model=32, n_layers=1, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=64
    )
    B, S, psz, p_max = 2, 3, 4, 3
    n_pages = B * p_max + 1
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool0 = {
        "k": jax.random.normal(
            jax.random.PRNGKey(1), (cfg.n_kv_heads, cfg.n_layers, n_pages, psz, cfg.head_dim)
        ),
        "v": jax.random.normal(
            jax.random.PRNGKey(2), (cfg.n_kv_heads, cfg.n_layers, n_pages, psz, cfg.head_dim)
        ),
    }
    table = jnp.asarray(np.arange(B * p_max, dtype=np.int32).reshape(B, p_max) + 1)
    pos0 = jnp.array([1, 5], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    ref_logits, ref_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0, use_pallas=False
    )
    pal_logits, pal_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0, use_pallas=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(pal_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pal_pool[key]), np.asarray(ref_pool[key]))


def test_allocator_invariants():
    a = PageAllocator(n_pages=32, page_size=8, max_pages_per_seq=8)
    p1 = a.allocate(1, 20)  # 3 pages
    assert len(p1) == 3
    p2 = a.allocate(2, 1)
    assert len(p2) == 1
    a.check_invariants()
    grown = a.extend(1, 40)  # 5 pages
    assert len(grown) == 5
    a.check_invariants()
    a.free(1)
    a.free(1)  # double-free is a no-op
    a.check_invariants()
    stats = a.stats()
    assert stats.sequences == 1
    assert stats.free_pages == 31 - 1  # only seq 2's single page held
    with pytest.raises(EngineError, match="already has pages"):
        a.allocate(2, 4)


def test_allocator_exhaustion():
    a = PageAllocator(n_pages=4, page_size=8, max_pages_per_seq=8)
    a.allocate(1, 24)  # 3 pages = all available
    assert not a.can_allocate(1)
    with pytest.raises(EngineError, match="out of KV pages"):
        a.allocate(2, 1)
    a.free(1)
    assert a.can_allocate(24)


def test_allocator_respects_max_pages_per_seq():
    a = PageAllocator(n_pages=64, page_size=8, max_pages_per_seq=2)
    with pytest.raises(EngineError, match="max_pages_per_seq"):
        a.allocate(1, 100)
