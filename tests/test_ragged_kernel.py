"""Ragged mixed-phase kernel + fused multi-step dispatch (ISSUE 15):
seeded kernel-vs-reference property coverage over mixed row batches,
compile-count invariance across ragged phase mixes via the cost-registry
sentinel, and fused-vs-per-step greedy byte parity through the live
engine (mid-window retirement, replan pin, spill/readmit interleave), and
every window length through ONE segment executable (ISSUE 31)."""

import asyncio
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.engine.pacing import SegmentPacer
from mcpx.engine.kernels.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_reference,
)


# --------------------------------------------------- kernel property test
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ragged_kernel_matches_reference_over_mixed_batches(seed):
    """Seeded property test: one launch serving a MIXED batch — rows with
    q_len = S (suffix prefill), q_len = 1 (plain decode), 1 < q_len < S
    (spec-verify windows) and q_len = 0 (idle) — agrees with the jnp
    reference everywhere, INCLUDING the zeroed pad/idle positions, over
    random page tables and start offsets."""
    rng = random.Random(seed)
    B, S = 6, 5
    K, G, hd, psz = 2, 2, 16, 4
    p_max = 12
    n_pages = B * p_max + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = np.zeros((B, p_max), np.int32)
    used = {0}
    for b in range(B):
        for i in range(p_max):
            p = rng.choice([x for x in range(1, n_pages) if x not in used])
            used.add(p)
            table[b, i] = p
    # The mix: every row class the engine dispatches, plus random fill.
    q_lens = [S, 1, rng.randint(2, S - 1), 0, rng.randint(0, S), 1]
    starts = [
        rng.randint(0, p_max * psz - max(1, q_lens[b]) - 1) for b in range(B)
    ]
    table_j = jnp.asarray(table)
    starts_j = jnp.asarray(starts, jnp.int32)
    q_lens_j = jnp.asarray(q_lens, jnp.int32)
    for layer in (0, 1):
        ref = ragged_paged_attention_reference(
            q, kp, vp, table_j, starts_j, q_lens_j, layer
        )
        out = ragged_paged_attention(
            q, kp, vp, table_j, starts_j, q_lens_j, layer, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )
        # The pad contract explicitly: zeros past each row's q_len.
        for b in range(B):
            assert np.all(np.asarray(out[b, q_lens[b]:]) == 0.0), (layer, b)


@pytest.mark.parametrize("S", [1, 8], ids=["single-query", "window"])
def test_reference_matches_plain_dense_attention(S):
    """The one jnp reference against attention written with no pages at all:
    each row's keys and values laid out densely from the pages its table
    names, query i attending the first ``start + i + 1`` of them."""
    B, K, G, hd, psz, p_max = 2, 2, 3, 16, 4, 6
    n_pages = B * p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = 1 + np.random.default_rng(5).permutation(n_pages - 1).reshape(B, p_max)
    starts = [2, 9]
    ref = ragged_paged_attention_reference(
        q, kp, vp, jnp.asarray(table, jnp.int32), jnp.asarray(starts, jnp.int32),
        jnp.full((B,), S, jnp.int32), 1,
    )
    for b in range(B):
        k = np.asarray(kp[:, 1][:, table[b]]).reshape(K, p_max * psz, hd)
        v = np.asarray(vp[:, 1][:, table[b]]).reshape(K, p_max * psz, hd)
        for i in range(S):
            n = starts[b] + i + 1
            logits = np.einsum("kgh,ksh->kgs", np.asarray(q[b, i]), k[:, :n]) / np.sqrt(hd)
            dense = np.einsum("kgs,ksh->kgh", np.asarray(jax.nn.softmax(logits, -1)), v[:, :n])
            np.testing.assert_allclose(np.asarray(ref[b, i]), dense, rtol=1e-5, atol=1e-5)


# (B, S, K, G, head_dim, page size, deepest start): windows whose every slot
# is live, the dense contract as the ragged one's case.
_ALL_LIVE = {
    "single-query-mqa": (1, 1, 1, 8, 128, 16, 39),
    "single-query-gqa-ragged-batch": (3, 1, 2, 2, 128, 16, 49),
    "single-query-mha-odd-lengths": (2, 1, 4, 1, 256, 8, 16),
    "window-mqa": (1, 8, 1, 8, 128, 16, 40),  # the Gemma-2B shape class
    "window-gqa-ragged-starts": (3, 4, 2, 2, 128, 16, 50),
    "window-of-one-mha": (2, 1, 4, 1, 256, 8, 17),
}


@pytest.mark.parametrize("case", sorted(_ALL_LIVE))
def test_kernel_matches_reference_with_every_slot_live(case):
    """Rows at random depths whose tables name only the pages they hold
    (null page 0 past them), every window slot live, at the published head
    widths; layer 1 exercises the prefetched layer-slice selection."""
    B, S, K, G, hd, psz, deepest = _ALL_LIVE[case]
    rng = random.Random(case)
    p_max = -(-(deepest + S) // psz) + 1
    n_pages = B * p_max + 2
    ks = jax.random.split(jax.random.PRNGKey(B * 10 + S), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    starts = [rng.randint(0, deepest) for _ in range(B)]
    free = rng.sample(range(1, n_pages), n_pages - 1)
    table = np.zeros((B, p_max), np.int32)
    for b, start in enumerate(starts):
        for i in range(-(-(start + S) // psz)):
            table[b, i] = free.pop()
    args = (
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts, jnp.int32),
        jnp.full((B,), S, jnp.int32), 1,
    )
    out = ragged_paged_attention(*args, interpret=True)
    ref = ragged_paged_attention_reference(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_kernel_clamps_a_window_that_overhangs_the_table():
    """A row's start + window width may overhang its page table by up to one
    window; the kernel must clamp its page walk to the table width instead
    of reading page_table[b, Pmax] out of bounds (regression: done rows in
    the speculative decode loop)."""
    B, S, K, G, hd, psz, p_max = 1, 4, 1, 2, 16, 4, 3
    n_pages = p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    start = jnp.array([p_max * psz - 1], jnp.int32)  # last in-table position
    full = jnp.full((B,), S, jnp.int32)
    out = ragged_paged_attention(q, kp, vp, table, start, full, interpret=True)
    ref = ragged_paged_attention_reference(q, kp, vp, table, start, full)
    # Query 0 is fully in-table; its output must be exact. Later queries'
    # visible ranges overhang the table and are garbage by contract.
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(ref[:, 0]), rtol=2e-5, atol=2e-5
    )


def test_ragged_idle_rows_stream_zero_pages_and_output_zeros():
    """The idle-row contract, tested at the only level it CAN be tested:
    from the outputs alone, streamed-then-masked and never-streamed are
    indistinguishable (the masking's correctness argument), so the page
    walk bound is a factored-out pure function — an idle row (q_len = 0)
    streams exactly zero pages however deep its frozen history, while
    live rows stream through their last visible position clamped to the
    table width. Plus the end-to-end half: idle rows output zeros."""
    from mcpx.engine.kernels.paged_attention import _ragged_n_pages

    n = _ragged_n_pages(
        jnp.asarray([512, 5, 5, 19, 0]),  # frozen-deep idle, decode, ...
        jnp.asarray([0, 1, 0, 4, 1]),
        4,
        8,
    )
    # Without the q_len gate the first/third rows would stream their
    # whole dead history (128 / 2 pages of DMA per head per layer per
    # forward — and done rows ride many forwards in a fused window).
    assert list(np.asarray(n)) == [0, 2, 0, 6, 1]

    B, S, K, G, hd, psz, p_max = 2, 3, 1, 2, 16, 4, 3
    n_pages = p_max + 1
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 1, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 1, n_pages, psz, hd), jnp.float32)
    table = jnp.asarray([[1, 2, 3], [1, 2, 3]], jnp.int32)
    starts = jnp.asarray([2, 5], jnp.int32)
    q_lens = jnp.asarray([3, 0], jnp.int32)
    out = ragged_paged_attention(
        q, kp, vp, table, starts, q_lens, 0, interpret=True
    )
    ref = ragged_paged_attention_reference(q, kp, vp, table, starts, q_lens, 0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert np.all(np.asarray(out[1]) == 0.0)


def test_ragged_kernel_blocks_long_windows_over_queries():
    """A window wider than Q_BLOCK runs as Q_BLOCK-query blocks per row (a
    whole 1024-token prefill window does not fit Mosaic's scoped VMEM in
    one program); a width that is not a multiple is padded with dead
    queries, never cut into slivers. Rows whose live length ends inside a
    block, exactly on a block edge, before the first block ends and at zero
    must all agree with the reference, pads included."""
    from mcpx.engine.kernels.paged_attention import Q_BLOCK

    S = 2 * Q_BLOCK + Q_BLOCK // 2  # 320: three blocks, the last half dead
    B, K, G, hd, psz, p_max = 5, 2, 2, 16, 8, 48
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    n_pages = B * p_max + 1
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 1, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 1, n_pages, psz, hd), jnp.float32)
    table = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(B, p_max)
    q_lens = jnp.asarray([S, 90, Q_BLOCK, 0, 3], jnp.int32)
    starts = jnp.asarray([p_max * psz - S, 11, 0, 40, 5], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, table, starts, q_lens, 0, interpret=True)
    ref = ragged_paged_attention_reference(q, kp, vp, table, starts, q_lens, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    for b in range(B):
        assert np.all(np.asarray(out[b, int(q_lens[b]):]) == 0.0), b


# ------------------------------------------------- the blocking's edges
def _edge_case(contexts, q_lens, S, *, K=2, G=2, hd=16, psz=16, p_max=40, seed=11):
    """Rows whose last live query sits at cache position ``context - 1``,
    over a shuffled page table; returns the kernel's arguments."""
    B = len(contexts)
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_pages = B * p_max + 1
    q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
    table = 1 + rng.permutation(n_pages - 1).astype(np.int32).reshape(B, p_max)
    q_lens = np.asarray(q_lens, np.int32)
    starts = np.maximum(np.asarray(contexts, np.int32) - q_lens, 0)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(q_lens)


# One key block is 16 pages of 16 keys here (``_blocking``: two lane widths).
_BLOCK_EDGES = {
    "one-page": 16,
    "one-key": 1,
    "a-page-and-a-key": 17,
    "one-block-less-a-key": 255,
    "exactly-one-block": 256,
    "one-block-plus-one-key": 257,
    "last-block-holds-one-page": 272,
    "last-block-holds-a-page-and-a-key": 273,
    "exactly-two-blocks": 512,
    "the-whole-table": 640,
}


@pytest.mark.parametrize("edge", sorted(_BLOCK_EDGES))
@pytest.mark.parametrize("S", [1, 8], ids=["decode", "segment"])
def test_ragged_kernel_at_the_key_blocks_edges(edge, S):
    """A compute step covers a block of 16 pages: contexts that end on a
    block's edge, one key past it, inside its first page and in a last
    block of one page agree with the gathered reference, beside a row of
    another length and an idle row in the same launch."""
    from mcpx.engine.kernels.paged_attention import _blocking

    assert _blocking(2, 2, 16, 16, S, 4, 4, 40) == (2, 16)
    ctx = _BLOCK_EDGES[edge]
    live = min(S, ctx)
    args = _edge_case([ctx, 300, 77], [live, 1, 0], S)
    out = ragged_paged_attention(*args, 1, interpret=True)
    ref = ragged_paged_attention_reference(*args, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out[0, live:]) == 0.0) and np.all(np.asarray(out[2]) == 0.0)
    assert np.any(np.asarray(out[0, :live]) != 0.0)


@pytest.mark.parametrize("edge", sorted(_BLOCK_EDGES))
@pytest.mark.parametrize("S", [1, 8], ids=["single-query", "window"])
def test_all_live_windows_at_the_key_blocks_edges_at_the_cells_head_dim(edge, S):
    """The same edges at head_dim 128 with every slot live: row 0's FIRST
    query sees exactly the edge's keys (its window walks on across it, as
    far as the table goes), row 1's LAST query does."""
    from mcpx.engine.kernels.paged_attention import _blocking

    assert _blocking(2, 2, 128, 16, S, 4, 4, 40) == (2, 16)
    ctx = _BLOCK_EDGES[edge]
    first, last = min(ctx - 1, 640 - S), max(ctx - S, 0)
    args = _edge_case([first + S, last + S], [S, S], S, hd=128, seed=ctx)
    out = ragged_paged_attention(*args, 1, interpret=True)
    ref = ragged_paged_attention_reference(*args, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ragged_kernel_serves_a_mixed_slab_in_one_launch():
    """A prefill row of Q_BLOCK + 90 queries (two query blocks, the second
    ragged), a verify window, decode rows at three depths and idle rows, in
    one launch of one executable."""
    from mcpx.engine.kernels.paged_attention import Q_BLOCK

    S = Q_BLOCK + 90
    q_lens = [S, 1, 0, 5, 1, 0, 1]
    contexts = [S + 37, 256, 600, 257, 16, 0, 639]
    args = _edge_case(contexts, q_lens, S)
    out = ragged_paged_attention(*args, 0, interpret=True)
    ref = ragged_paged_attention_reference(*args, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    for b, n in enumerate(q_lens):
        assert np.all(np.asarray(out[b, n:]) == 0.0), b


def test_ragged_kernel_with_a_last_head_block_that_is_not_full():
    """Three KV heads where two fit a program: the second head block holds
    one head, copies one head's pages and writes one head's output."""
    from mcpx.engine.kernels.paged_attention import _blocking

    S, K, G, hd = 128, 3, 2, 256
    assert _blocking(K, G, hd, 16, S, 4, 4, 40) == (2, 16)
    args = _edge_case([300, 257, 0, 40], [S, 3, 0, 1], S, K=K, G=G, hd=hd)
    out = ragged_paged_attention(*args, 1, interpret=True)
    ref = ragged_paged_attention_reference(*args, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(out)))


# ----------------------------------------- lowering for the chip, from CPU
# (K, G, head_dim): Gemma's two, then every cell's: olmo2-1b; mistral-7b
# (on four chips K local is 4: the shard_map case below); mellum2 and
# trinity-mini.
_HEAD_LAYOUTS = {
    "2b": (1, 8, 256), "7b": (16, 1, 256),
    "olmo2-1b": (16, 1, 128), "mistral-7b": (8, 4, 128), "mellum2": (4, 8, 128),
}


@pytest.mark.parametrize("layout", sorted(_HEAD_LAYOUTS))
@pytest.mark.parametrize("sq", [1, 8, 128])
def test_blocking_fits_its_budget_at_every_layout(layout, sq):
    """``_blocking`` alone, from shapes: the heads split evenly over the
    fewest programs that fit, one or two lane widths of keys a step, and what a program
    then holds (buffers in flight, q/out blocks, carries, a score tile) is
    inside the budget it names. A decode window takes every head; the 2B
    layout's 128-query block the one it has."""
    from mcpx.engine.kernels.paged_attention import VMEM_BUDGET, _blocking, _vmem_bytes

    K, G, hd = _HEAD_LAYOUTS[layout]
    for k_local in {K, max(1, K // 2)}:  # whole, and split over a model axis of 2
        h_blk, p_blk = _blocking(k_local, G, hd, 16, sq, 2, 2, 32)
        assert 1 <= h_blk <= k_local and p_blk in (8, 16)
        n_programs = -(-k_local // h_blk)
        assert h_blk == -(-k_local // n_programs)  # even: no program nearly empty
        size = dict(G=G, hd=hd, page_size=16, sq=sq, pool_itemsize=2, q_itemsize=2)
        assert _vmem_bytes(h_blk, p_blk, **size) <= VMEM_BUDGET
        if h_blk < k_local:  # fewer programs would not have fit, even at one lane width
            assert _vmem_bytes(-(-k_local // (n_programs - 1)), 8, **size) > VMEM_BUDGET
        if p_blk == 8:  # the second lane width would not have
            assert _vmem_bytes(h_blk, 16, **size) > VMEM_BUDGET
        if sq <= 8:
            assert h_blk == k_local
    assert _blocking(K, G, hd, 16, sq, 2, 2, 3)[1] == 3  # no wider than the table


def test_blocking_halves_the_key_block_when_one_head_does_not_fit():
    from mcpx.engine.kernels.paged_attention import VMEM_BUDGET, _blocking, _vmem_bytes

    h_blk, p_blk = _blocking(2, 16, 512, 16, 128, 4, 4, 32)
    assert h_blk == 1 and 1 <= p_blk < 8
    size = dict(G=16, hd=512, page_size=16, sq=128, pool_itemsize=4, q_itemsize=4)
    assert _vmem_bytes(1, 2 * p_blk, **size) > VMEM_BUDGET



def _kernel_arg_shapes(S, K, G, hd, B=4, psz=16, p_max=128):
    sd = jax.ShapeDtypeStruct
    pool = sd((K, 2, B * p_max + 1, psz, hd), jnp.bfloat16)
    return (
        sd((B, S, K, G, hd), jnp.bfloat16), pool, pool,
        sd((B, p_max), jnp.int32), sd((B,), jnp.int32), sd((B,), jnp.int32),
        sd((), jnp.int32),
    )


@pytest.mark.parametrize("layout", sorted(_HEAD_LAYOUTS))
@pytest.mark.parametrize("S", [1, 8, 9, 128])
def test_kernel_lowers_for_tpu_on_one_device_and_under_shard_map(layout, S):
    """Cross-platform lowering (jaxpr -> Mosaic MLIR, no chip needed) at the
    published head layouts and every cell's, for every window class the
    engine dispatches (``_blocking`` gives each its own H_BLK):
    the bare kernel on one device, and the engine's call — the kernel under
    ``shard_map`` — on a 2x2 (data x model) mesh. Interpret mode lowers to
    plain HLO, so without this neither a jaxpr->Mosaic error nor "Mosaic
    kernels cannot be automatically partitioned" can fail on CPU."""
    from mcpx.engine.paged_decode import _ragged_kernel_on_mesh
    from mcpx.parallel.mesh import make_mesh

    args = _kernel_arg_shapes(S, *_HEAD_LAYOUTS[layout])
    jax.jit(ragged_paged_attention).trace(*args).lower(lowering_platforms=("tpu",))
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    # as the four-chip cell shapes it: 8 rows a device, a table 32 wide
    args = _kernel_arg_shapes(S, *_HEAD_LAYOUTS[layout], B=16, p_max=32)
    jax.jit(
        lambda *a: _ragged_kernel_on_mesh(mesh, *a, interpret=False)
    ).trace(*args).lower(lowering_platforms=("tpu",))


def test_bare_kernel_on_a_sharded_mesh_is_what_the_shard_map_prevents():
    """The failure the shard_map exists for, pinned so the lowering test
    above is known to see it: the same kernel called bare with inputs
    sharded over a 2x2 mesh cannot lower for TPU."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mcpx.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    specs = (
        P("data", None, None, "model", None), P(), P(),
        P("data", None), P("data"), P("data"), P(),
    )
    args = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, s))
        for a, s in zip(_kernel_arg_shapes(8, *_HEAD_LAYOUTS["2b"]), specs)
    )
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(ragged_paged_attention).trace(*args).lower(
            lowering_platforms=("tpu",)
        )


def test_ragged_kernel_route_refuses_to_run_without_a_mesh():
    """There is no bare-kernel arm in the forward: a caller that forgets
    ``mesh=`` fails at trace time on CPU instead of at lowering on >1 chip."""
    from mcpx.engine.paged_decode import decode_chunk_paged

    z = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="mesh="):
        decode_chunk_paged(
            {}, None, z, z[0], z, {"k": jnp.zeros((1, 1, 1, 1, 1))},
            interpret=True, q_lens=z[0],
        )
    # ... and one that states no live widths has no contract to fall back on.
    with pytest.raises(TypeError, match="q_lens"):
        decode_chunk_paged({}, None, z, z[0], z, {"k": jnp.zeros((1, 1, 1, 1, 1))})


def test_sharded_kernel_matches_reference_on_virtual_mesh():
    """The shard_map'd call computes what the reference computes on a 2x2
    mesh, for both head splits: KV heads over ``model`` (GQA, K divides)
    and the query-group axis with the pools whole (MQA)."""
    from mcpx.engine.paged_decode import _ragged_kernel_on_mesh
    from mcpx.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    B, S, hd, psz, p_max = 4, 3, 16, 4, 6
    for K, G in ((2, 2), (1, 4)):
        ks = jax.random.split(jax.random.PRNGKey(K), 3)
        n_pages = B * p_max + 1
        q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
        kp = jax.random.normal(ks[1], (K, 2, n_pages, psz, hd), jnp.float32)
        vp = jax.random.normal(ks[2], (K, 2, n_pages, psz, hd), jnp.float32)
        table = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(B, p_max)
        starts = jnp.asarray([5, 0, 17, 9], jnp.int32)
        q_lens = jnp.asarray([S, 1, 0, 2], jnp.int32)
        out = jax.jit(
            lambda *a: _ragged_kernel_on_mesh(mesh, *a, interpret=True)
        )(q, kp, vp, table, starts, q_lens, jnp.asarray(1, jnp.int32))
        ref = ragged_paged_attention_reference(q, kp, vp, table, starts, q_lens, 1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


# ------------------------------------------------- through the model step
def _step_case(cfg, B, S, psz, p_max, pos0):
    """Params, random resident pools, private tables and a window of tokens."""
    from mcpx.engine.kv_cache import init_paged_kv
    from mcpx.models.gemma.model import init_params

    pools = {
        name: jax.random.normal(jax.random.PRNGKey(i + 1), pool.shape, pool.dtype)
        for i, (name, pool) in enumerate(init_paged_kv(cfg, B * p_max + 1, psz).items())
    }
    table = jnp.asarray(np.arange(B * p_max, dtype=np.int32).reshape(B, p_max) + 1)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    return init_params(cfg, jax.random.PRNGKey(0)), pools, table, tokens, jnp.asarray(pos0, jnp.int32)


def test_one_window_of_s_tokens_matches_s_windows_of_one():
    """decode_chunk_paged over S tokens == S forwards of one token each: the
    same logits at every window slot and identical page pools afterward (the
    speculation verify pass must be an exact re-expression of sequential
    decode)."""
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.config import GemmaConfig

    cfg = GemmaConfig(
        dtype="float32", d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64
    )
    B, S = 2, 5
    params, pool0, table, tokens, pos0 = _step_case(cfg, B, S, 4, 4, [3, 6])  # mid-page starts

    seq_pool, seq_logits = pool0, []
    for i in range(S):
        lg, seq_pool = decode_chunk_paged(
            params, cfg, tokens[:, i : i + 1], pos0 + i, table, seq_pool,
            use_pallas=False, q_lens=jnp.ones((B,), jnp.int32),
        )
        seq_logits.append(lg[:, 0])
    window_logits, window_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0,
        use_pallas=False, q_lens=jnp.full((B,), S, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(window_logits), np.asarray(jnp.stack(seq_logits, axis=1)),
        rtol=2e-5, atol=2e-5,
    )
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(window_pool[key]), np.asarray(seq_pool[key]), rtol=2e-5, atol=2e-5
        )


def test_model_step_on_the_interpreted_kernel_matches_the_jnp_route():
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.config import GemmaConfig
    from mcpx.parallel.mesh import make_mesh

    cfg = GemmaConfig(
        dtype="float32", d_model=32, n_layers=1, n_heads=2, n_kv_heads=1, head_dim=128, d_ff=64
    )
    B, S = 2, 3
    params, pool0, table, tokens, pos0 = _step_case(cfg, B, S, 4, 3, [1, 5])
    q_lens = jnp.full((B,), S, jnp.int32)
    ref_logits, ref_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0, use_pallas=False, q_lens=q_lens
    )
    pal_logits, pal_pool = decode_chunk_paged(
        params, cfg, tokens, pos0, table, pool0, use_pallas=True, interpret=True,
        q_lens=q_lens, mesh=make_mesh(data=1, model=1, devices=jax.devices()[:1]),
    )
    np.testing.assert_allclose(
        np.asarray(pal_logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5
    )
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pal_pool[key]), np.asarray(ref_pool[key]))


_TINY = dict(vocab_size=384, d_model=64, n_heads=4, d_ff=128, dtype="float32")
_SPARSE = dict(
    n_experts=8, n_experts_per_tok=2, d_expert=32, activation="silu",
    tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
)
_BLOCKS = {
    "default": dict(_TINY, n_layers=2, n_kv_heads=2, head_dim=32),
    "windowed-sparse": dict(
        _TINY, **_SPARSE, n_layers=4, n_kv_heads=2, head_dim=32, sliding_window=8,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
    ),
    "latent": dict(
        _TINY, **_SPARSE, n_layers=2, n_kv_heads=1, head_dim=16, attention="latent",
        q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
    ),
}


@pytest.mark.parametrize("block", sorted(_BLOCKS))
def test_a_live_slots_logits_do_not_depend_on_its_rows_pad_slots(block):
    """Same ``q_lens < S``, two fillings of the pad slots (and of an idle
    row): bit-equal logits at every live slot and equal expert counters. A
    pad slot's key lies past every live query's visible range at every
    layer, and a sparse feed-forward routes it nowhere."""
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.config import GemmaConfig

    cfg = GemmaConfig(**_BLOCKS[block])
    B, S = 4, 8
    # row 2's window crosses a page edge
    params, pools, table, _, positions = _step_case(cfg, B, S, 16, 4, [20, 9, 14, 37])
    q_lens = jnp.asarray([3, 0, 7, 1], jnp.int32)
    rng = np.random.default_rng(0)
    live = np.arange(S)[None, :] < np.asarray(q_lens)[:, None]
    one = rng.integers(0, cfg.vocab_size, (B, S))
    other = np.where(live, one, rng.integers(0, cfg.vocab_size, (B, S)))
    assert (one != other)[~live].any()

    def run(tokens):
        return decode_chunk_paged(
            params, cfg, jnp.asarray(tokens, jnp.int32), positions, table, pools,
            use_pallas=False, q_lens=q_lens, moe_stats=True,
        )

    (logits_a, _, stats_a), (logits_b, _, stats_b) = run(one), run(other)
    np.testing.assert_array_equal(np.asarray(logits_a)[live], np.asarray(logits_b)[live])
    if cfg.n_experts:
        assert np.asarray(stats_a).tolist() == np.asarray(stats_b).tolist()
        assert int(stats_a[: cfg.n_experts].sum()) == cfg.n_layers * 2 * int(q_lens.sum())
    else:
        assert stats_a is None and stats_b is None


# ------------------------------------------------------------ engine-level
def _engine_cfg(**overrides):
    eng = {
        "max_batch_size": 4,
        "max_decode_len": 24,
        "kv_page_size": 16,
        "max_pages_per_seq": 16,
        "temperature": 0.0,
        # The CPU proxy serves the SAME kernel body TPUs run, via the
        # Pallas interpreter (the ISSUE 15 headline contract).
        "use_pallas": True,
        "interpret": True,
    }
    eng.update(overrides)
    return MCPXConfig.from_dict(
        {"model": {"size": "test", "max_seq_len": 256}, "engine": eng}
    )


def _mk(**overrides):
    from mcpx.engine.engine import InferenceEngine

    return InferenceEngine(_engine_cfg(**overrides))


def test_compile_count_invariant_across_ragged_mixes():
    """Cost-registry sentinel gate: after one warm pass per executable,
    serving any prefill/decode mix — fresh prompts, deep radix repeats
    (ragged suffix offsets), short-budget rows retiring mid-window next
    to long-budget rows — compiles NOTHING new. Raggedness (q_lens,
    start offsets, page tables) is data, so the executable population is
    a function of bucket geometry alone."""

    async def go():
        eng = _mk()
        await eng.start()
        try:
            tok = eng.tokenizer
            header = "Compose a DAG.\nServices:\n"
            prompts = [
                tok.encode(header + f"svc-{i} in:a out:b\nIntent: t{i}\nJSON:")
                for i in range(3)
            ]
            # Warm pass: compiles full prefill, suffix prefill (repeat),
            # admit/merge, segment for the A=1 cohort bucket.
            for p in prompts:
                await eng.generate(p, max_new_tokens=12, constrained=False)
            await eng.generate(prompts[0], max_new_tokens=12, constrained=False)
            snap0 = {
                name: e["compiles"]
                for name, e in eng.costs.snapshot(materialize=False)[
                    "executables"
                ].items()
            }
            # The ragged mixes: repeats at three different matched
            # offsets, a novel tail (different suffix length), and
            # budgets from 1 to the cap (mid-window retirement).
            for i, p in enumerate(prompts):
                await eng.generate(
                    p, max_new_tokens=1 + 7 * i, constrained=False
                )
            novel = tok.encode(header + "svc-9 in:x out:y\nIntent: n\nJSON:")
            await eng.generate(novel, max_new_tokens=3, constrained=False)
            snap1 = {
                name: e["compiles"]
                for name, e in eng.costs.snapshot(materialize=False)[
                    "executables"
                ].items()
            }
            assert snap1 == snap0, (snap0, snap1)
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_fused_vs_per_step_greedy_byte_parity_with_mid_window_retirement():
    """The fused window is a pure cadence lever: the SAME greedy requests
    — staggered budgets so rows retire mid-window while neighbours keep
    decoding, plus a replan pin held across serving — produce
    byte-identical tokens under steps_per_dispatch=1 and =4, and the
    fused engine issues measurably fewer decode dispatches."""

    async def go():
        per_step = _mk(steps_per_dispatch=1)
        fused = _mk(steps_per_dispatch=4)
        await per_step.start()
        await fused.start()
        try:
            tok = per_step.tokenizer
            header = "Fused parity header padding words.\n"
            prompts = [
                tok.encode(header + f"intent {i}: compose. JSON:")
                for i in range(6)
            ]
            budgets = [2, 19, 7, 23, 1, 12]  # retire at different windows

            async def serve(eng):
                pin = await eng.pin_prefix(prompts[0])  # replan-pin shape
                rs = await asyncio.gather(
                    *(
                        eng.generate(
                            p,
                            max_new_tokens=b,
                            constrained=False,
                            temperature=0.0,
                        )
                        for p, b in zip(prompts, budgets)
                    )
                )
                eng.unpin_prefix(pin)
                return [r.token_ids for r in rs]

            a = await serve(per_step)
            b = await serve(fused)
            assert a == b
            # Cadence actually moved: fewer dispatches per decoded token.
            ps = per_step.pallas_paths()["paths"]["decode"]["dispatches"]
            fu = fused.pallas_paths()["paths"]["decode"]["dispatches"]
            ps_tok = per_step.metrics.decode_tokens._value.get()
            fu_tok = fused.metrics.decode_tokens._value.get()
            assert ps_tok == fu_tok > 0
            assert fu < ps, (fu, ps)
        finally:
            await per_step.aclose()
            await fused.aclose()

    asyncio.run(go())


class _FixedWindow(SegmentPacer):
    """Every segment is asked for ``n`` forwards, and what the worker then
    reports as dispatched is kept."""

    def __init__(self, n: int) -> None:
        super().__init__()
        self.n, self.lengths = n, []

    def window(self, tick, ceiling):
        return min(ceiling, self.n)

    def dispatched(self, t0, t1, forwards):
        self.lengths.append(forwards)
        super().dispatched(t0, t1, forwards)


@pytest.mark.parametrize("hetero", [False, True], ids=["homogeneous", "hetero"])
def test_one_executable_serves_every_window_with_the_same_greedy_tokens(hetero):
    """The segment's length is an OPERAND (ISSUE 31): the same greedy
    requests, staggered so rows retire mid-window, decode byte-identical
    tokens at windows of 4, 8 and 16 forwards on one engine, and the
    compile sentinel does not move between them: one executable, whatever
    length the pacer asks for. The per-step engine of the case above is
    the reference at a fourth length."""

    async def go():
        eng = _mk(
            steps_per_dispatch=4, hetero_batch=hetero, prefix_cache=False,
            max_decode_len=64,
            warmup_compile=True, warmup_max_len=64,  # as served: all warm at start
        )
        ref = _mk(
            steps_per_dispatch=1, hetero_batch=hetero, prefix_cache=False,
            max_decode_len=64,
        )
        await eng.start()
        await ref.start()
        try:
            tok = eng.tokenizer
            prompts = [
                tok.encode(f"Window parity header.\nintent {i}: compose. JSON:")
                for i in range(6)
            ]
            budgets = [2, 60, 7, 41, 1, 12]  # retire at different forwards

            async def serve(e):
                rs = await asyncio.gather(
                    *(
                        e.generate(
                            p, max_new_tokens=b, constrained=True, temperature=0.0
                        )
                        for p, b in zip(prompts, budgets)
                    )
                )
                return [r.token_ids for r in rs]

            def compiles():
                return {
                    name: e["compiles"]
                    for name, e in eng.costs.snapshot(materialize=False)[
                        "executables"
                    ].items()
                }

            assert all(len(p) <= 64 for p in prompts)
            want = await serve(ref)
            assert all(want)
            # What the benchmark's ``correct`` reads: the compile count at
            # ``started`` must not move, whatever lengths are served.
            snap, dispatches = compiles(), {}
            assert snap["hetero_segment" if hetero else "segment"] == 1
            for n in (4, 8, 16):
                pacer = eng._pacer = _FixedWindow(n)
                before = eng.pallas_paths()["paths"]["decode"]["dispatches"]
                assert await serve(eng) == want, n
                assert set(pacer.lengths) == {n}  # asked for, and reported
                dispatches[n] = (
                    eng.pallas_paths()["paths"]["decode"]["dispatches"] - before
                )
                assert compiles() == snap, (n, snap, compiles())
            # The length did change the cadence: more, shorter segments.
            assert dispatches[4] >= dispatches[8] >= dispatches[16] >= 2
            assert dispatches[4] > dispatches[16]
        finally:
            await eng.aclose()
            await ref.aclose()

    asyncio.run(go())


def test_fused_parity_survives_spill_readmit_interleave():
    """Fused dispatch under the tiered KV cache: repeats whose matched
    runs spill to host RAM and re-admit between windows still decode
    byte-identically to the per-step cadence."""

    async def go():
        def tiered(steps):
            return _mk(
                steps_per_dispatch=steps,
                max_decode_len=8,
                prefix_cache_entries=64,
                kv_tier={"enabled": True, "host_mb": 64.0},
            )

        eng1 = tiered(1)
        eng4 = tiered(4)
        await eng1.start()
        await eng4.start()
        try:
            tok = eng1.tokenizer
            prompts = [
                tok.encode(f"tier probe {i}: " + "wxyz " * 28)[:128]
                for i in range(8)
            ]

            async def serve(eng):
                outs = []
                for _ in range(2):  # round 2 re-admits round 1's spills
                    for p in prompts:
                        r = await eng.generate(
                            p,
                            max_new_tokens=8,
                            constrained=False,
                            temperature=0.0,
                        )
                        outs.append(r.token_ids)
                return outs

            a = await serve(eng1)
            b = await serve(eng4)
            assert a == b
            tier = eng4.prefix_cache_stats()["tier"]
            assert tier["spills"] > 0, tier
        finally:
            await eng1.aclose()
            await eng4.aclose()

    asyncio.run(go())
