"""Attention that reads whole KEY BLOCKS chosen by a parameter-free score
over pooled keys (an ``S`` layer of ``GemmaConfig.mixer_ffn``).

A query at position ``t`` of KV group ``g`` (``G = n_heads / n_kv_heads``
heads on one KV head), with ``p = pool_stride`` (= the cache's page size),
``b = block_size``, ``r = b / p`` pooled keys a block:

  Kc[j]   = mean(K[p j .. p j + 2p - 1])           visible when p j + 2p - 1 <= t
  s[h, j] = softmax_j(q_h . Kc[j] / sqrt(hd))      over the visible j, a head
  S[j]    = sum of s[h, j] over the group's heads
  B[n]    = max S[r n - 1 .. r n + r - 1]          (r + 1 wide, stride r, padding 1)

and the query attends the tokens ``<= t`` of its ``block_topk`` best blocks
``n <= t // b`` by ``B``, ties to the lower block, blocks ``< block_init`` and
the ``block_window / b`` blocks ending at its own always among them: one
selection a (query, KV head). A query that sees no more than ``block_topk``
blocks attends everything before it, so the rule is dense attention there and
one rule serves every length.

``Kc[j]`` spans two pages, and a page may be shared (a radix node's) while
the one after it is a row's own: the cache keeps each page's key SUM
(``engine/kv_cache.init_paged_kv``: float32, a row a page), which depends on
that page's tokens alone, and ``pooled_keys`` forms ``(sum_j + sum_{j+1}) /
2p`` where the scores are taken.

``selected_blocks`` is the selection as a mask over blocks; ``block_lists``
turns it into each query's ascending list, which is what the paged forward
gathers by (``engine/paged_decode.py``); ``token_mask`` is the dense
forward's form of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mcpx.models.gemma.config import GemmaConfig


def pooled_keys(sums: jax.Array, stride: int) -> jax.Array:
    """Page sums [..., P, hd] float32 -> the pooled keys [..., P - 1, hd]:
    pooled key ``j`` is the mean over pages ``j`` and ``j + 1``."""
    return (sums[..., :-1, :] + sums[..., 1:, :]) / (2.0 * stride)


def page_sums(k: jax.Array, stride: int) -> jax.Array:
    """Keys [B, T, K, hd] (T a multiple of ``stride``) -> [B, K, T / stride,
    hd] float32, each page's sum."""
    B, T, K, hd = k.shape
    pages = k.astype(jnp.float32).reshape(B, T // stride, stride, K, hd).sum(axis=2)
    return pages.transpose(0, 2, 1, 3)


def pooled_scores(
    q: jax.Array,  # [B, T, K, G, hd]
    kc: jax.Array,  # [B, K, J, hd] float32: pooled_keys
    t: jax.Array,  # [B, T] each query's position
    stride: int,
) -> jax.Array:
    """``S[j]`` of every (query, KV head): [B, T, K, J] float32, -inf where the
    query does not see pooled key ``j`` whole. (A decode window's come from
    ``engine/kernels/block_score.py`` on the kernel route.)"""
    J, hd = kc.shape[2], kc.shape[3]
    f32 = jnp.float32
    logits = jnp.einsum("btkgh,bkjh->btkgj", q.astype(f32), kc, precision="highest") / hd**0.5
    seen = (stride * jnp.arange(J) + 2 * stride - 1)[None, None, :] <= t[:, :, None]  # [B, T, J]
    seen = seen[:, :, None, None, :]
    logits = jnp.where(seen, logits, -jnp.inf)
    top = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(logits - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    s = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return jnp.where(seen[:, :, :, 0], jnp.sum(s, axis=3), -jnp.inf)


def blocks_from_scores(pooled: jax.Array, t: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """``pooled_scores`` [B, T, K, J] -> [B, T, K, N] bool, N = ceil((J + 1) /
    r): the blocks each (query, KV head) reads."""
    from mcpx.models.gemma.model import select_top

    r = cfg.block_size // cfg.pool_stride
    J = pooled.shape[-1]
    N = -(-(J + 1) // r)
    # Block n's score: the max over pooled r n - 1 .. r n + r - 1. Pad one in
    # front (the padding) and up to r N + 1 behind, then r + 1 strided views.
    padded = jnp.pad(
        pooled, ((0, 0),) * 3 + ((1, r * N - J),), constant_values=-jnp.inf
    )  # index i holds pooled i - 1; length r N + 1
    score = padded[..., 0 : r * N : r]
    for i in range(1, r + 1):
        score = jnp.maximum(score, padded[..., i : i + r * N : r])
    n = jnp.arange(N)
    own = (t // cfg.block_size)[:, :, None, None]  # [B, T, 1, 1]
    forced = (n < cfg.block_init) | (n > own - cfg.blocks_kept)
    visible = jnp.broadcast_to(n <= own, score.shape)
    return select_top(jnp.where(forced, jnp.inf, score), visible, cfg.block_topk)


def selected_blocks(q: jax.Array, kc: jax.Array, t: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """q [B, T, K, G, hd], ``pooled_keys`` [B, K, J, hd], positions [B, T] ->
    [B, T, K, N] bool: the blocks each (query, KV head) reads."""
    return blocks_from_scores(pooled_scores(q, kc, t, cfg.pool_stride), t, cfg)


def block_lists(chosen: jax.Array, topk: int) -> tuple[jax.Array, jax.Array]:
    """[..., N] bool -> (each query's chosen blocks ascending [..., topk]
    int32, unchosen places holding N; how many it chose [...])."""
    N = chosen.shape[-1]
    ids = jnp.where(chosen, jnp.arange(N, dtype=jnp.int32), N)
    if N < topk:
        ids = jnp.pad(ids, ((0, 0),) * (ids.ndim - 1) + ((0, topk - N),), constant_values=N)
    return jnp.sort(ids, axis=-1)[..., :topk], jnp.sum(chosen, axis=-1).astype(jnp.int32)


def token_mask(chosen: jax.Array, block: int, S: int) -> jax.Array:
    """[B, T, K, N] bool over blocks -> [B, T, K, S] over tokens (the caller
    adds the causal bound)."""
    return jnp.repeat(chosen, block, axis=-1)[..., :S]


def selects(cfg: GemmaConfig, context: int) -> bool:
    """Whether a context of this many tokens can hold a block a query drops
    (static: below it the selection is everything, and is not computed)."""
    return context > cfg.block_topk * cfg.block_size
