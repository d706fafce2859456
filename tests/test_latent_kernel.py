"""The absorbed latent kernel (``ragged_paged_attention_latent``), beside
``test_ragged_kernel.py``: interpret mode against the ``jax.numpy``
reference of the absorbed form over ragged rows, a row of one page and of
128, a window wider than one query block; its blocking; and the lowering for
the TPU from the CPU, bare and under the engine's ``shard_map``."""

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.engine.kernels.paged_attention import (
    LATENT_ROWS,
    NEG_INF,
    _latent_blocking,
    _latent_call,
    latent_paged_attention_reference,
    latent_query_slots,
    latent_rung,
    latent_rungs,
    ragged_paged_attention_latent,
)


def _case(seed, B, S, H, r, w, psz, p_max, layers=2, dtype=jnp.float32):
    """Random queries, pools and a page table that scatters every row's
    pages over the pool."""
    rng = random.Random(seed)
    n_pages = B * p_max + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_latent = jax.random.normal(ks[0], (B, S, H, r), dtype)
    q_rope = jax.random.normal(ks[1], (B, S, H, w), dtype)
    rope = jax.random.normal(ks[2], (1, layers, n_pages, psz, w), dtype)
    latent = jax.random.normal(ks[3], (1, layers, n_pages, psz, r), dtype)
    pages = list(range(1, n_pages))
    rng.shuffle(pages)
    table = jnp.asarray(np.asarray(pages[: B * p_max], np.int32).reshape(B, p_max))
    return q_latent, q_rope, rope, latent, table


def _both(args, starts, q_lens, layer, scale=0.13):
    starts, q_lens = jnp.asarray(starts, jnp.int32), jnp.asarray(q_lens, jnp.int32)
    ref = latent_paged_attention_reference(*args, starts, q_lens, layer, scale=scale)
    out = ragged_paged_attention_latent(*args, starts, q_lens, layer, scale=scale, interpret=True)
    return np.asarray(out), np.asarray(ref)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_latent_kernel_matches_reference_over_mixed_batches(seed):
    """One launch over a MIXED slab — a full window, plain decode rows, a
    partial window, an idle row — agrees with the absorbed jnp reference
    everywhere, the zeroed pad and idle positions included, in both layers."""
    rng = random.Random(seed)
    B, S, H, r, w, psz, p_max = 6, 5, 4, 32, 128, 4, 12
    args = _case(seed, B, S, H, r, w, psz, p_max)
    q_lens = [S, 1, rng.randint(2, S - 1), 0, rng.randint(0, S), 1]
    starts = [rng.randint(0, p_max * psz - max(1, n) - 1) for n in q_lens]
    for layer in (0, 1):
        out, ref = _both(args, starts, q_lens, layer)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        for b in range(B):
            assert np.all(out[b, q_lens[b]:] == 0.0), (layer, b)


def test_a_row_of_one_page_and_a_row_of_128():
    """The cell's table is 128 pages wide: a row that fills it (2,047 keys
    behind its query, eight key blocks of 256) beside a row whose whole
    context is its first page, and an idle one."""
    B, S, H, r, w, psz, p_max = 3, 8, 4, 32, 128, 16, 128
    args = _case(7, B, S, H, r, w, psz, p_max, layers=1)
    out, ref = _both(args, [p_max * psz - S, 3, 500], [S, 1, 0], 0)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    assert np.abs(out[0]).max() > 0 and np.all(out[2] == 0.0)


def test_a_window_wider_than_a_query_block_runs_as_blocks():
    """A suffix-prefill window: 64 heads leave a program 8 queries, so a
    40-query window is five query blocks, each streaming up to its own last
    query; rows ragged within it."""
    B, S, H, r, w, psz, p_max = 2, 40, 64, 16, 128, 8, 16
    assert _latent_blocking(S, H, psz, p_max)[:2] == (8, 64)
    args = _case(3, B, S, H, r, w, psz, p_max, layers=1)
    out, ref = _both(args, [20, 0], [17, 40], 0)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    assert np.all(out[0, 17:] == 0.0)


def test_bfloat16_pools_round_the_weights_as_the_reference_does():
    B, S, H, r, w, psz, p_max = 2, 8, 4, 128, 128, 16, 4
    args = _case(5, B, S, H, r, w, psz, p_max, layers=1, dtype=jnp.bfloat16)
    out, ref = _both(args, [30, 11], [8, 1], 0, scale=0.05)
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32), rtol=0.02, atol=0.02)


# ------------------------------------------------- the live slots' rung
# (q_lens of the row under test, window, heads, selecting): every live count
# of an 8-slot decode window at both published head counts, and a 64-slot
# suffix window whose third query block holds 6 live slots.
RUNG_CASES = [
    (n, 8, H, selecting) for H in (64, 128) for selecting in (False, True) for n in (0, 1, 2, 3, 5, 8)
] + [(22, 64, 64, True), (22, 64, 128, False)]


@functools.lru_cache(maxsize=None)
def _interpreted(rungs):
    """One jitted interpret-mode call a ladder: a case's live counts are data."""
    return jax.jit(functools.partial(_latent_call, scale=0.13, interpret=True, rungs=rungs))


@pytest.mark.parametrize("n, S, H, selecting", RUNG_CASES)
def test_a_rows_live_slots_take_their_rung_and_nothing_else_moves(n, S, H, selecting):
    """The kernel at the rung of each block's live slots agrees with the jnp
    reference; its live rows are bit for bit those of the same body run at
    the whole block (the tile before the rungs: ``rungs=(Sq,)``), and every
    dead slot is the zero a pad query outputs."""
    B, r, w, psz, p_max = 2, 32, 128, 16, 32  # 512 keys: two key blocks, the second part filled
    args = _case(11 + n, B, S, H, r, w, psz, p_max, layers=1)
    q_lens = jnp.asarray([n, 1], jnp.int32)
    starts = jnp.asarray([20 * psz - S - 3, 17], jnp.int32)
    select = None
    if selecting:
        picked = jax.random.bernoulli(jax.random.PRNGKey(n), 0.3, (B, S, p_max * psz))
        select = jnp.where(picked.at[:, :, 0].set(True), 0.0, NEG_INF).astype(jnp.float32)
    sq = _latent_blocking(S, H, psz, p_max)[0]
    assert sq == 8
    out, whole = (np.asarray(_interpreted(rungs)(*args, starts, q_lens, 0, select)) for rungs in (None, (sq,)))
    ref = np.asarray(latent_paged_attention_reference(*args, starts, q_lens, 0, select, scale=0.13))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    live = np.arange(S)[None, :] < np.asarray(q_lens)[:, None]
    assert np.array_equal(out[live], whole[live])
    assert np.all(out[~live] == 0.0) and np.all(whole[~live] == 0.0)
    assert n == 0 or np.abs(out[0, :n]).min(axis=(1, 2)).max() > 0


@pytest.mark.parametrize("sq", [1, 2, 3, 5, 8, 16, 128])
def test_a_rung_holds_its_live_slots_and_never_more_than_the_block(sq):
    rungs = latent_rungs(sq)
    assert rungs == tuple(sorted(set(rungs))) and rungs[-1] == sq and rungs[0] == 1
    taken = [int(latent_rung(q, sq)) for q in range(sq + 1)]
    assert taken == sorted(taken) and set(taken) <= set(rungs)  # monotone, on the ladder
    assert all(max(q, 1) <= t <= sq and t < 2 * max(q, 1) for q, t in zip(range(sq + 1), taken))
    # an array of live counts takes the rungs its members take
    assert np.broadcast_to(latent_rung(jnp.arange(sq + 1), sq), (sq + 1,)).tolist() == taken


@pytest.mark.parametrize("S, H", [(8, 64), (8, 128), (64, 128), (5, 4)])
def test_the_forwards_counter_is_the_sum_of_the_kernels_rungs(S, H):
    """``moe.add_forward_stats``' last counter of a latent block is what the
    kernel's programs multiplied: the rung of every query block with a live
    slot, by the one function both call, times the layers."""
    from mcpx.models.gemma import moe
    from mcpx.models.gemma.config import GemmaConfig

    cfg = GemmaConfig(
        vocab_size=384, d_model=64, n_layers=3, n_heads=H, n_kv_heads=1, head_dim=16, d_ff=64,
        attention="latent", q_lora_rank=16, kv_lora_rank=32, qk_rope_head_dim=16, v_head_dim=16,
        n_experts=4, n_experts_per_tok=2, d_expert=16, norm_plus_one=False,
    )
    q_lens = np.asarray([0, 1, 2, 3, 5, S, min(22, S), 0])
    sq = _latent_blocking(S, H, 16, 32)[0]
    want = 0
    for q in q_lens:  # a program a (row, query block), as the kernel's grid walks them
        for q0 in range(0, S, sq):
            qn = int(np.clip(q - q0, 0, sq))
            want += int(latent_rung(qn, sq)) if qn else 0
    assert int(latent_query_slots(jnp.asarray(q_lens), S, H)) == want
    stats = moe.add_forward_stats(cfg, moe.moe_stats_init(cfg), jnp.asarray(q_lens) + 40, jnp.asarray(q_lens), S)
    assert int(stats[-moe.LATENT_STATS]) == want * cfg.n_layers
    # a forward that read no page (the expanded prefill) multiplied none
    assert int(moe.add_forward_stats(cfg, moe.moe_stats_init(cfg), jnp.asarray(q_lens), jnp.asarray(q_lens))[-moe.LATENT_STATS]) == 0
    # a block with heads for a cache counts no such thing
    dense = GemmaConfig.named("test")
    assert not dense.latent and moe.moe_stats_init(dense).shape == (moe.LAYER_STATS + moe.FORWARD_STATS,)


@pytest.mark.parametrize("H, p_max, selecting", [(64, 128, False), (128, 512, True)])
@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_every_rung_lowers_for_tpu_at_the_published_widths(n, H, p_max, selecting):
    """An 8-slot decode window of 64 heads (a.x-k1's) and of 128 under a
    selection (deepseek's): the arm of each rung beside the whole block's,
    lowered for Mosaic from the CPU."""
    B, S, r, w, L, n_pages, psz = 8, 8, 512, 128, 2, 33, 16
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    shapes = [
        sd((B, S, H, r), bf), sd((B, S, H, w), bf), sd((1, L, n_pages, psz, w + (128 if selecting else 0)), bf),
        sd((1, L, n_pages, psz, r), bf), sd((B, p_max), i32), sd((B,), i32), sd((B,), i32), sd((), i32),
    ] + ([sd((B, S, p_max * psz), f32)] if selecting else [])
    assert n in latent_rungs(S)
    call = functools.partial(_latent_call, scale=0.13, rungs=tuple(sorted({n, S})))
    text = jax.jit(call).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    name = "ragged_paged_attention_selected" if selecting else "ragged_paged_attention_latent"
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("S, H, want", [
    (1, 64, (1, 64)), (8, 64, (8, 64)), (128, 64, (8, 64)), (1024, 64, (8, 64)),
    (8, 4, (8, 4)), (300, 4, (128, 4)), (8, 1024, (8, 512)),
])
def test_latent_blocking_keeps_a_program_to_its_rows(S, H, want):
    sq, g, p_blk = _latent_blocking(S, H, 16, 128)
    assert (sq, g) == want and p_blk == 16 and H % g == 0
    assert sq * g <= max(LATENT_ROWS, 8 * g)
    assert _latent_blocking(S, H, 16, 4)[2] == 4  # no wider than the table


@pytest.mark.parametrize("S", [1, 8, 9, 128])
def test_latent_kernel_lowers_for_tpu_on_one_device_and_under_shard_map(S):
    """At the published widths (64 heads, a 512-wide latent, the rotated key
    in a 128-lane row), from the CPU: the Mosaic lowering, bare and under the
    engine's shard_map on a 2 x 2 mesh (rows over data, heads over model, the
    pools whole on every device)."""
    from mcpx.engine.paged_decode import _latent_attend
    from mcpx.models.gemma.config import GemmaConfig
    from mcpx.parallel.mesh import make_mesh

    B, H, r, w, L, n_pages, psz, p_max = 8, 64, 512, 128, 2, 33, 16, 32
    bf = jnp.bfloat16
    shapes = [
        jax.ShapeDtypeStruct(s, d) for s, d in (
            ((B, S, H, r), bf), ((B, S, H, w), bf), ((1, L, n_pages, psz, w), bf),
            ((1, L, n_pages, psz, r), bf), ((B, p_max), jnp.int32), ((B,), jnp.int32),
            ((B,), jnp.int32), ((), jnp.int32),
        )
    ]
    kernel = functools.partial(ragged_paged_attention_latent, scale=0.13)
    text = jax.jit(kernel).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ragged_paged_attention_latent" in text

    cfg = GemmaConfig(
        vocab_size=384, d_model=256, n_layers=L, n_heads=H, n_kv_heads=1, head_dim=128, d_ff=256,
        attention="latent", q_lora_rank=64, kv_lora_rank=r, qk_rope_head_dim=64, v_head_dim=128,
        norm_plus_one=False,
    )
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    lp = {"w_ukv": jax.ShapeDtypeStruct((r, H, 256), bf)}

    def attend(q, w_ukv, rope_pool, latent_pool, table, pos, lens):
        return _latent_attend(q, {"w_ukv": w_ukv}, cfg, rope_pool, latent_pool, table, pos, lens,
                              jnp.asarray(1), mesh=mesh, use_pallas=True, interpret=False)

    q = jax.ShapeDtypeStruct((B, S, H, 192), bf)
    text = jax.jit(attend).trace(
        q, lp["w_ukv"], shapes[2], shapes[3], shapes[4], shapes[5], shapes[6]
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("S", [1, 8, 64])
def test_the_index_and_the_selecting_attention_lower_for_tpu_on_one_device_and_under_shard_map(S):
    """At the published widths of the block with a learned index (128 heads,
    64 index heads x 128, the index key behind the rotated key's 128 lanes, a
    table of 512 pages), from the CPU: the Mosaic lowering of both calls,
    bare and through ``_latent_attend`` under the engine's shard_map on a
    2 x 2 mesh (rows over data, attention heads over model, every index head
    on each device)."""
    from mcpx.engine.kernels.paged_attention import lightning_indexer
    from mcpx.engine.paged_decode import _latent_attend
    from mcpx.models.gemma.config import GemmaConfig
    from mcpx.parallel.mesh import make_mesh

    B, H, Hi, di, r, w, L, n_pages, psz, p_max = 8, 128, 64, 128, 512, 128, 2, 33, 16, 512
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    rope_pool, latent_pool = sd((1, L, n_pages, psz, w + di), bf), sd((1, L, n_pages, psz, r), bf)
    rows = [sd((B, p_max), i32), sd((B,), i32), sd((B,), i32), sd((), i32)]
    index = functools.partial(lightning_indexer, topk=2048, lane0=128)
    text = jax.jit(index).trace(
        sd((B, S, Hi, di), bf), sd((B, S, Hi), f32), rope_pool, *rows
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "lightning_indexer" in text
    attend = functools.partial(ragged_paged_attention_latent, scale=0.13)
    text = jax.jit(attend).trace(
        sd((B, S, H, r), bf), sd((B, S, H, w), bf), rope_pool, latent_pool, *rows,
        sd((B, S, p_max * psz), f32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ragged_paged_attention_selected" in text

    cfg = GemmaConfig(
        vocab_size=384, d_model=256, n_layers=L, n_heads=H, n_kv_heads=1, head_dim=128, d_ff=256,
        attention="latent", q_lora_rank=64, kv_lora_rank=r, qk_rope_head_dim=64, v_head_dim=128,
        index_n_heads=Hi, index_head_dim=di, index_topk=2048, norm_plus_one=False,
    )
    assert cfg.kv_widths == (w + di, r)
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])

    def both(q, w_ukv, q_i, w_i, rope, latent, table, pos, lens):
        return _latent_attend(q, {"w_ukv": w_ukv}, cfg, rope, latent, table, pos, lens,
                              jnp.asarray(1), (q_i, w_i), mesh=mesh, use_pallas=True, interpret=False)

    text = jax.jit(both).trace(
        sd((B, S, H, 192), bf), sd((r, H, 256), bf), sd((B, S, Hi, di), bf), sd((B, S, Hi), f32),
        rope_pool, latent_pool, *rows[:3],
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2
    # a table no wider than the selection traces no index at all
    narrow = jax.jit(both).trace(
        sd((B, S, H, 192), bf), sd((r, H, 256), bf), sd((B, S, Hi, di), bf), sd((B, S, Hi), f32),
        rope_pool, latent_pool, sd((B, 128), i32), *rows[1:3],
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "lightning_indexer" not in narrow and "ragged_paged_attention_latent" in narrow


# ------------------------------------------------- a run of pages in one copy
# Page tables of 3 key blocks of 16 pages and half a fourth (the row's last 8
# pages, then the null page), over a pool of RUN_POOL pages: (name, the row's
# page ids, the flags ``page_run_flags`` must give its four blocks).
RUN_POOL = 120
_asc = lambda a, n=16: list(range(a, a + n))
_mid = lambda a: [a] + [a + 1 + (5 * i) % 14 for i in range(14)] + [a + 15]  # ends right, middle out of order
RUN_TABLES = {
    "all-runs": (_asc(1) + _asc(40) + _asc(17) + _asc(60, 8), [1, 1, 1, 0]),
    "no-runs": ([1 + (37 * i) % 101 for i in range(56)], [0, 0, 0, 0]),
    "mixed": (_asc(5) + [21 + (7 * i) % 16 for i in range(16)] + _asc(80) + _asc(100, 8), [1, 0, 1, 0]),
    "descending-block": (_asc(1) + _asc(40)[::-1] + _asc(17) + _asc(60, 8), [1, 0, 1, 0]),
    "ends-match-middle-shuffled": (_asc(1) + _mid(40) + _asc(17) + _asc(60, 8), [1, 0, 1, 0]),
    "one-step-off": (_asc(1) + _asc(40, 8) + _asc(49, 8) + _asc(17) + _asc(60, 8), [1, 0, 1, 0]),
    "run-ends-at-the-pools-last-page": (_asc(1) + _asc(40) + _asc(RUN_POOL - 16) + _asc(60, 8), [1, 1, 1, 0]),
}
# (start of the window, live slots): a context that ends inside block 2 (its
# first two blocks whole, the third fetched page by page whatever its flag),
# one that fills the three blocks exactly, one that reaches the cut block.
RUN_CONTEXTS = [(2 * 256 + 70, 3), (3 * 256 - 8, 8), (3 * 256 + 40, 1)]


def _run_table(name, B):
    from mcpx.engine.kernels.paged_attention import page_run_flags

    pages, want = RUN_TABLES[name]
    assert len(pages) == 56 and sorted(set(pages)) == sorted(pages) and max(pages) < RUN_POOL
    # the other rows read the same pages in an order with no run in it
    table = np.asarray([pages + [0] * 8] + [pages[::-1] + [0] * 8] * (B - 1), np.int32)
    flags = np.asarray(page_run_flags(jnp.asarray(table), 16, RUN_POOL))
    assert flags.tolist() == [want] + [[0] * 4] * (B - 1)
    return jnp.asarray(table), jnp.asarray(flags)


def _pools(seed, layers, width_k, width_v, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (
        jax.random.normal(ks[0], (1, layers, RUN_POOL, 16, width_k), dtype),
        jax.random.normal(ks[1], (1, layers, RUN_POOL, 16, width_v), dtype),
    )


def _run_case_attention(name, selecting):
    B, S, H, r, w = 2, 8, 4, 32, 128
    table, flags = _run_table(name, B)
    rope, latent = _pools(3, 2, w + (128 if selecting else 0), r, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q_latent = jax.random.normal(ks[0], (B, S, H, r), jnp.float32)
    q_rope = jax.random.normal(ks[1], (B, S, H, w), jnp.float32)
    select = None
    if selecting:
        picked = jax.random.bernoulli(ks[2], 0.4, (B, S, 64 * 16))
        select = jnp.where(picked.at[:, :, 0].set(True), 0.0, NEG_INF).astype(jnp.float32)
    for start, n in RUN_CONTEXTS:
        rows = (jnp.asarray([start, start - 100], jnp.int32), jnp.asarray([n, 1], jnp.int32), 1, select)
        args = (q_latent, q_rope, rope, latent, table)
        by_run = np.asarray(_interpreted(None)(*args, *rows, flags))
        by_page = np.asarray(_interpreted(None)(*args, *rows, jnp.zeros_like(flags)))
        assert np.array_equal(by_run, by_page), (name, start)
        ref_rope = rope[..., :w]  # the reference reads the rotated key's lanes alone
        ref = np.asarray(latent_paged_attention_reference(q_latent, q_rope, ref_rope, latent, table, *rows, scale=0.13))
        np.testing.assert_allclose(by_run, ref, rtol=2e-5, atol=2e-5)
        assert np.abs(by_run[0, :n]).min(axis=(1, 2)).max() > 0


@pytest.mark.parametrize("name", list(RUN_TABLES))
def test_the_latent_kernel_fetches_a_run_in_one_copy_and_nothing_moves(name):
    """A key block whose pages lie side by side is one DMA a pool: bit for bit
    the page-by-page fetch (the same call with every flag 0) and within
    tolerance of the jnp reference, at contexts that end inside a block, on a
    block's edge and in the block the table cuts short."""
    _run_case_attention(name, selecting=False)


@pytest.mark.parametrize("name", list(RUN_TABLES))
def test_the_selecting_kernel_fetches_a_run_in_one_copy_and_nothing_moves(name):
    """The same under a selection, the rotated key the first 128 lanes of a
    256-lane page row (a run's copy slices the lanes of 16 pages at once)."""
    _run_case_attention(name, selecting=True)


@pytest.mark.parametrize("name", list(RUN_TABLES))
def test_the_index_kernel_fetches_a_run_in_one_copy_and_chooses_the_same_keys(name):
    """The index keys (lanes 128 on of the rotated key's page rows) of a run
    in one copy: the selection is EQUAL to the page-by-page fetch's and to
    the jnp reference's."""
    from mcpx.engine.kernels.paged_attention import index_select_reference, lightning_indexer

    B, S, Hi, di, lane0, topk = 2, 8, 4, 32, 128, 64
    table, flags = _run_table(name, B)
    pool, _ = _pools(9, 2, lane0 + di, 8, jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    q_i = jax.random.normal(ks[0], (B, S, Hi, di), jnp.bfloat16)
    w_i = jax.random.normal(ks[1], (B, S, Hi), jnp.float32)
    for start, n in RUN_CONTEXTS:
        args = (q_i, w_i, pool, table, jnp.asarray([start, 30], jnp.int32), jnp.asarray([n, 1], jnp.int32), jnp.int32(1))
        by_run = np.asarray(lightning_indexer(*args, flags, topk=topk, lane0=lane0, interpret=True))
        by_page = np.asarray(lightning_indexer(*args, jnp.zeros_like(flags), topk=topk, lane0=lane0, interpret=True))
        ref = np.asarray(index_select_reference(*args, topk=topk, lane0=lane0))
        assert np.array_equal(by_run, by_page) and np.array_equal(by_run, ref), (name, start)
        assert (by_run[0, :n] == 0.0).sum(axis=-1).tolist() == [topk] * n


def test_the_flag_is_what_the_kernels_fetch_by():
    """A flag set on a block that is NO run makes the kernels read the 16
    pages after the block's first id, not the table's: what they return is
    what the table with that run written into it gives. (No caller sets such
    a flag: ``page_run_flags`` checks every step. It shows that the flag, and
    not a second look at the table, chooses the fetch.)"""
    from mcpx.engine.kernels.paged_attention import lightning_indexer

    B, S, H, r, w = 1, 8, 4, 32, 128
    pages = RUN_TABLES["no-runs"][0]
    table = jnp.asarray([pages + [0] * 8], jnp.int32)
    as_run = jnp.asarray([pages[:16] + _asc(pages[16]) + pages[32:] + [0] * 8], jnp.int32)
    forced = jnp.asarray([[0, 1, 0, 0]], jnp.int32)
    rope, latent = _pools(3, 1, w + 32, r, jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q_latent = jax.random.normal(ks[0], (B, S, H, r), jnp.bfloat16)
    q_rope = jax.random.normal(ks[1], (B, S, H, w), jnp.bfloat16)
    rows = (jnp.asarray([3 * 256 - 8], jnp.int32), jnp.asarray([8], jnp.int32), 0, None)
    call = _interpreted(None)
    got = np.asarray(call(q_latent, q_rope, rope, latent, table, *rows, forced))
    assert np.array_equal(got, np.asarray(call(q_latent, q_rope, rope, latent, as_run, *rows)))
    assert not np.array_equal(got, np.asarray(call(q_latent, q_rope, rope, latent, table, *rows)))
    q_i = jax.random.normal(ks[2], (B, S, 4, 32), jnp.bfloat16)
    w_i = jax.random.normal(ks[3], (B, S, 4), jnp.float32)
    index = functools.partial(lightning_indexer, topk=64, lane0=w, interpret=True)
    got = np.asarray(index(q_i, w_i, rope, table, *rows[:3], forced))
    assert np.array_equal(got, np.asarray(index(q_i, w_i, rope, as_run, *rows[:3])))
    assert not np.array_equal(got, np.asarray(index(q_i, w_i, rope, table, *rows[:3])))


@pytest.mark.parametrize("pages, pool, want", [
    (_asc(1) + _asc(17), 33, [1, 1]),
    (_asc(1) + _asc(18), 33, [1, 0]),  # ids past the pool: the last would be page 33 of 33
    (_asc(0) + [0] * 16, 33, [1, 0]),  # an idle row's zeros are no run; the null page may start one
    (_asc(1, 20), 33, [1, 0]),  # a table 20 wide: its second block is cut short
    ([3, 4, 5, 6], 33, [1]),  # a table narrower than a key block is one block of its width
    ([3, 5, 4, 6], 33, [0]),
])
def test_page_run_flags_check_every_step_and_the_pools_bounds(pages, pool, want):
    from mcpx.engine.kernels.paged_attention import latent_key_pages, page_run_flags

    table = jnp.asarray([pages], jnp.int32)
    assert latent_key_pages(16, len(pages)) == min(16, len(pages))
    assert np.asarray(page_run_flags(table, 16, pool)).tolist() == [want]


@pytest.mark.parametrize("S, H", [(8, 128), (64, 128), (8, 1024)])
def test_the_key_block_counters_are_what_the_kernels_programs_fetch(S, H):
    """``latent_key_blocks`` walks the kernel's grid: a (row, head block,
    query block) program fetches ``cdiv(pages, 16)`` key blocks through its
    last live query, those before its last page's block whole; a run among
    the whole ones counts. Times the layers it is the forward's counter."""
    from mcpx.engine.kernels.paged_attention import latent_key_blocks
    from mcpx.models.gemma import moe
    from mcpx.models.gemma.config import GemmaConfig

    table, flags = _run_table("mixed", 3)
    flags = flags.at[1].set(jnp.asarray([1, 1, 1, 0]))  # a second row of three runs
    starts, q_lens = np.asarray([600, 3 * 256 - 30, 90]), np.asarray([min(S, 22), min(S, 40), 0])
    sq, g, _ = _latent_blocking(S, H, 16, 64)
    blocks = runs = 0
    for b in range(3):
        for q0 in range(0, S, sq):
            qn = int(np.clip(q_lens[b] - q0, 0, sq))
            n_pages = min(-(-(starts[b] + q0 + qn) // 16), 64) if qn else 0
            blocks += -(-n_pages // 16) * (H // g)
            runs += sum(int(flags[b, i]) for i in range(n_pages // 16)) * (H // g)
    got = latent_key_blocks(flags, jnp.asarray(starts), jnp.asarray(q_lens), S, H, 16, 64)
    assert (int(got[0]), int(got[1])) == (blocks, runs) and 0 < runs < blocks
    cfg = GemmaConfig(
        vocab_size=384, d_model=64, n_layers=3, n_heads=H, n_kv_heads=1, head_dim=16, d_ff=64,
        attention="latent", q_lora_rank=16, kv_lora_rank=32, qk_rope_head_dim=16, v_head_dim=16,
        n_experts=4, n_experts_per_tok=2, d_expert=16, norm_plus_one=False,
    )
    stats = moe.add_forward_stats(
        cfg, moe.moe_stats_init(cfg), jnp.asarray(starts + q_lens), jnp.asarray(q_lens), S, (flags, 16, 64)
    )
    assert stats[-2:].tolist() == [blocks * 3, runs * 3]
    # no table given (a dense prefill reads no page): no block counted
    assert moe.add_forward_stats(cfg, moe.moe_stats_init(cfg), jnp.asarray(q_lens), jnp.asarray(q_lens))[-2:].tolist() == [0, 0]
