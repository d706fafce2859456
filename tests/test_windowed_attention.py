"""The ragged kernel's per-call attention window (a sliding layer's) and its
``jnp`` twin, against a dense masked softmax written here: a query at
position p sees the keys in (p - window, p]. Interpreted kernel on the CPU,
plus the windowed kernel's lowering for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.engine.kernels.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_reference,
)

K, G, HD, PSZ = 2, 2, 16, 4


def _dense_masked_softmax(q, kp, vp, table, starts, q_lens, window):
    """Row by row, query by query, over the row's own keys in order."""
    B, S = q.shape[:2]
    out = np.zeros(q.shape, np.float64)
    for b in range(B):
        keys = np.concatenate([np.asarray(kp[:, 0, p], np.float64) for p in table[b]], axis=1)  # [K, ctx, hd]
        vals = np.concatenate([np.asarray(vp[:, 0, p], np.float64) for p in table[b]], axis=1)
        for i in range(int(q_lens[b])):
            pos = int(starts[b]) + i
            lo = max(0, pos - window + 1)
            for k in range(K):
                s = np.asarray(q[b, i, k], np.float64) @ keys[k, lo : pos + 1].T / np.sqrt(HD)  # [G, n]
                w = np.exp(s - s.max(-1, keepdims=True))
                out[b, i, k] = (w / w.sum(-1, keepdims=True)) @ vals[k, lo : pos + 1]
    return out


def _case(S, seed):
    """Rows whose contexts start anywhere in their pages, one deep enough that
    under a short window its first visible page is far from page 0."""
    rng = np.random.default_rng(seed)
    B, p_max = 4, 48 + S // PSZ
    n_pages = 1 + B * p_max
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, HD), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 1, n_pages, PSZ, HD), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 1, n_pages, PSZ, HD), jnp.float32)
    table = 1 + rng.permutation(B * p_max).astype(np.int32).reshape(B, p_max)
    q_lens = np.asarray([S, max(1, S // 2), 0, S], np.int32)
    starts = np.asarray([0, 3, 40, p_max * PSZ - S - 1], np.int32)  # the last: deep into its pages
    return q, kp, vp, table, starts, q_lens


@pytest.mark.parametrize("window", [1, 8, 10_000], ids=["w1", "w8", "past-context"])
@pytest.mark.parametrize("S", [1, 8, 128], ids=["decode", "segment", "suffix-prefill"])
def test_windowed_kernel_and_its_twin_match_a_dense_masked_softmax(S, window):
    q, kp, vp, table, starts, q_lens = _case(S, seed=S + window)
    want = _dense_masked_softmax(q, kp, vp, table, starts, q_lens, window)
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(q_lens), 0)
    twin = ragged_paged_attention_reference(*args, jnp.int32(window))
    kernel = ragged_paged_attention(*args, jnp.int32(window), interpret=True)
    np.testing.assert_allclose(np.asarray(twin), want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kernel), want, rtol=2e-4, atol=2e-5)
    # the deep row's first query sees nothing of page 0 under a short window
    assert window > 100 or (starts[3] - window + 1) // PSZ > 10
    if window > 100:  # past every context: the program without a window
        plain = ragged_paged_attention(*args, interpret=True)
        np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain), rtol=1e-6, atol=1e-6)


def test_a_query_block_past_the_first_starts_at_its_own_first_visible_page():
    """A 256-query window runs as two 128-query blocks; under a window of 8
    the second block's first visible page is 30 pages on from the first's."""
    S, window = 256, 8
    q, kp, vp, table, starts, q_lens = _case(S, seed=1)
    want = _dense_masked_softmax(q, kp, vp, table, starts, q_lens, window)
    out = ragged_paged_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(q_lens), 0,
        jnp.int32(window), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


def _deep_case(S, seed, p_max=200):
    """Contexts deep enough for three key blocks (64 pages of 4 keys each)."""
    rng = np.random.default_rng(seed)
    B = 4
    n_pages = 1 + B * p_max
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, HD), jnp.float32)
    kp = jax.random.normal(ks[1], (K, 1, n_pages, PSZ, HD), jnp.float32)
    vp = jax.random.normal(ks[2], (K, 1, n_pages, PSZ, HD), jnp.float32)
    table = 1 + rng.permutation(B * p_max).astype(np.int32).reshape(B, p_max)
    q_lens = np.asarray([S, 1, 0, max(1, S - 3)], np.int32)
    starts = np.asarray([601, 256, 200, p_max * PSZ - S], np.int32)
    return q, kp, vp, table, starts, q_lens


# A key block is 64 pages of 4 keys here: 256 keys.
_WINDOWS = {
    "first-visible-page-inside-a-block": 260,  # the row at 601: page 85 of 151, not 64 or 128
    "skips-whole-blocks-to-one-page": 3,
    "skips-whole-blocks-to-two-blocks": 400,
    "exactly-one-block": 256,
    "one-block-plus-one-key": 257,
    "a-page-less-than-a-block": 252,
}


@pytest.mark.parametrize("name", sorted(_WINDOWS))
@pytest.mark.parametrize("S", [1, 8], ids=["decode", "segment"])
def test_windowed_kernel_at_the_key_blocks_edges(S, name):
    """The key blocks are counted from the first page the query block's
    first query can see, wherever in the row's pages that falls: a window
    that starts inside what would be a block from page 0, one that skips
    whole blocks, and windows that end on a block's edge."""
    from mcpx.engine.kernels.paged_attention import _blocking

    assert _blocking(K, G, HD, PSZ, S, 4, 4, 200) == (K, 64)
    window = _WINDOWS[name]
    q, kp, vp, table, starts, q_lens = _deep_case(S, seed=S + window)
    want = _dense_masked_softmax(q, kp, vp, table, starts, q_lens, window)
    out = ragged_paged_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(q_lens), 0,
        jnp.int32(window), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


def test_the_windows_of_one_scan_may_differ_by_layer():
    """The window is data of the call, not of the executable: one jitted
    function serves a sliding and a full layer."""
    q, kp, vp, table, starts, q_lens = _case(8, seed=3)
    fn = jax.jit(lambda w: ragged_paged_attention(
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(q_lens), 0, w, interpret=True))
    for window in (4, 2**30):
        want = _dense_masked_softmax(q, kp, vp, table, starts, q_lens, window)
        np.testing.assert_allclose(np.asarray(fn(jnp.int32(window))), want, rtol=2e-4, atol=2e-5)
    assert fn._cache_size() == 1


def test_the_reference_takes_the_window_with_every_slot_live():
    q, kp, vp, table, starts, _ = _case(8, seed=4)
    full = np.full((4,), 8, np.int32)
    want = _dense_masked_softmax(q, kp, vp, table, starts, full, 5)
    out = ragged_paged_attention_reference(
        q, kp, vp, jnp.asarray(table), jnp.asarray(starts), jnp.asarray(full), 0, 5
    )
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize(
    "Kh,Gh,hd",
    [(4, 8, 128), (16, 1, 128), (8, 4, 128), (1, 8, 256), (16, 1, 256)],
    ids=["mellum2", "olmo2-1b", "mistral-7b", "2b", "7b"],
)
@pytest.mark.parametrize("S", [1, 8, 128])
def test_windowed_kernel_lowers_for_tpu_at_the_mellum_head_layout(S, Kh, Gh, hd):
    """GQA 32:4 at head_dim 128 (mellum2, trinity-mini: the cells that pass
    a window) and every other cell's layout, the window one more prefetched
    scalar: the bare kernel on one device and under the engine's shard_map
    on 2 x 2."""
    from mcpx.engine.paged_decode import _ragged_kernel_on_mesh
    from mcpx.parallel.mesh import make_mesh

    sd = jax.ShapeDtypeStruct
    B, p_max = 4, 32
    pool = sd((Kh, 12, B * p_max + 1, 16, hd), jnp.bfloat16)
    args = (sd((B, S, Kh, Gh, hd), jnp.bfloat16), pool, pool, sd((B, p_max), jnp.int32),
            sd((B,), jnp.int32), sd((B,), jnp.int32), sd((), jnp.int32), sd((), jnp.int32))
    jax.jit(ragged_paged_attention).trace(*args).lower(lowering_platforms=("tpu",))
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    jax.jit(lambda *a: _ragged_kernel_on_mesh(mesh, *a, interpret=False)).trace(*args).lower(
        lowering_platforms=("tpu",)
    )
