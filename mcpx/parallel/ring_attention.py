"""Ring attention: sequence-parallel causal attention over a ``seq`` mesh axis.

Long-context / context-parallel support (charter first-class item; the
reference has no sequence-length strategy at all — it concatenates every
service into one prompt, reference ``control_plane.py:65-67``). The serving
engine doesn't need this (planner contexts are short by design — retrieval
shortlists the prompt, SURVEY.md §5 long-context), but the framework ships a
real, tested implementation for long-context prefill:

  - tokens are sharded contiguously over the ``seq`` mesh axis: device i
    holds global positions ``[i*Tl, (i+1)*Tl)``;
  - each device keeps its queries resident and rotates its K/V block around
    the ring with ``jax.lax.ppermute`` (neighbour hops over ICI — bandwidth
    per step is ``2·B·Tl·K·hd`` bytes, overlappable with the block matmul);
  - softmax is accumulated **online** (flash-style running max/sum in
    float32), so no device ever materialises the full [T, T] score matrix;
  - causality and right-padding are enforced per block from *global*
    positions — no [B, T, S] mask is ever built.

``ring_prefill`` runs the full Gemma forward with the attention op swapped
(``model.forward(attend_fn=...)``): everything outside attention is
token-local, so the MLP/norm/rope compute is automatically sequence-parallel
under the same sharding.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mcpx.core.errors import ConfigError
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import KVCache, Params, forward, init_kv_cache
from mcpx.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS, _axis

_NEG = -1e30


def _ring_block_attend(
    q: jax.Array,  # [B, Tl, K, G, hd] local queries (f32 accumulation inside)
    k_local: jax.Array,  # [B, Tl, K, hd] local K block
    v_local: jax.Array,  # [B, Tl, K, hd] local V block
    seq_lens: jax.Array,  # [B] global valid lengths
    *,
    n_shards: int,
    block_len: int,
) -> jax.Array:
    """Per-device body run under shard_map. Returns [B, Tl, K, G, hd] f32.

    The ring is unrolled in Python (``n_shards`` is a static mesh dimension),
    which lets the final step skip its ppermute — the rotated block would
    never be read — and gives XLA the whole pipeline to overlap hops with
    block matmuls.
    """
    B, Tl, K, G, hd = q.shape
    idx = lax.axis_index(SEQ_AXIS)
    scale = 1.0 / math.sqrt(hd)
    q_pos = idx * block_len + jnp.arange(Tl)  # [Tl] global query positions

    m = jnp.full((B, Tl, K, G), _NEG, jnp.float32)
    l = jnp.zeros((B, Tl, K, G), jnp.float32)
    o = jnp.zeros((B, Tl, K, G, hd), jnp.float32)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    k_blk, v_blk = k_local, v_local

    for step in range(n_shards):
        # After `step` rotations the resident block originated at shard
        # (idx - step) mod n — its global positions anchor the causal mask.
        src = (idx - step) % n_shards
        kv_pos = src * block_len + jnp.arange(Tl)  # [Tl]
        keep = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, None, :] < seq_lens[:, None, None]
        )  # [B, Tl_q, Tl_kv]
        scores = (
            jnp.einsum(
                "btkgh,bskh->btkgs", q, k_blk, preferred_element_type=jnp.float32
            )
            * scale
        )
        keep_b = keep[:, :, None, None, :]
        scores = jnp.where(keep_b, scores, _NEG)
        new_m = jnp.maximum(m, jnp.max(scores, axis=-1))
        # exp(NEG - NEG) = 1 for fully-masked rows, so multiply by the mask
        # to zero those contributions (keeps l exact, avoids -inf NaNs).
        p = jnp.exp(scores - new_m[..., None]) * keep_b
        alpha = jnp.exp(m - new_m)
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "btkgs,bskh->btkgh", p, v_blk.astype(jnp.float32)
        )
        m = new_m
        if step < n_shards - 1:
            k_blk = lax.ppermute(k_blk, SEQ_AXIS, perm)
            v_blk = lax.ppermute(v_blk, SEQ_AXIS, perm)

    # Fully-masked queries (right padding) have l == 0; emit zeros for them.
    return o / jnp.where(l == 0.0, 1.0, l)[..., None]


def ring_attention(
    q: jax.Array,  # [B, T, K, G, hd] (global)
    k: jax.Array,  # [B, T, K, hd]
    v: jax.Array,  # [B, T, K, hd]
    seq_lens: jax.Array,  # [B]
    mesh: Mesh,
) -> jax.Array:
    """Causal self-attention with T sharded over the ``seq`` mesh axis.

    Same contract as ``model._attend`` restricted to self-attention (S == T,
    causal + right-padding mask derived from ``seq_lens``). Output dtype
    follows ``v``.
    """
    if SEQ_AXIS not in mesh.shape:
        raise ConfigError("ring_attention requires a mesh with a 'seq' axis")
    n = mesh.shape[SEQ_AXIS]
    T = q.shape[1]
    if T % n != 0:
        raise ConfigError(f"sequence length {T} must divide seq axis {n}")
    B = q.shape[0]
    b_ax = _axis(mesh, DATA_AXIS, B)
    m_ax = _axis(mesh, MODEL_AXIS, q.shape[2])
    fn = jax.shard_map(
        functools.partial(
            _ring_block_attend, n_shards=n, block_len=T // n
        ),
        mesh=mesh,
        in_specs=(
            P(b_ax, SEQ_AXIS, m_ax, None, None),
            P(b_ax, SEQ_AXIS, m_ax, None),
            P(b_ax, SEQ_AXIS, m_ax, None),
            P(b_ax),
        ),
        out_specs=P(b_ax, SEQ_AXIS, m_ax, None, None),
        check_vma=False,
    )
    # No upcast of q: the QK^T einsum requests f32 accumulation via
    # preferred_element_type, same numerics contract as the dense _attend —
    # bf16 inputs stay on the MXU's native path.
    out = fn(q, k, v, seq_lens)
    return out.astype(v.dtype)


def ring_prefill(
    params: Params,
    cfg: GemmaConfig,
    tokens: jax.Array,  # [B, T], T % mesh.seq == 0
    seq_lens: jax.Array,  # [B]
    mesh: Mesh,
    kv_cache: Optional[KVCache] = None,
    last_only: bool = False,
) -> tuple[jax.Array, KVCache]:
    """Sequence-parallel prefill: ``model.prefill`` semantics with the
    attention op swapped for ring attention. Token-local compute (embedding,
    norms, rope, MLP) is sequence-parallel via sharding propagation; only
    attention communicates (ppermute ring over ICI).

    The dense [B, T, S] mask is never built; the returned KV cache is the
    standard [L, B, T, K, hd] pytree (seq-sharded on axis 2 under the mesh).
    ``last_only`` returns [B, V] logits at each row's last valid position
    (the serving engine's prefill contract — the [B, T, V] buffer never
    exists).
    """
    B, T = tokens.shape
    if kv_cache is None:
        kv_cache = init_kv_cache(cfg, B, T)
    if kv_cache["k"].shape[2] != T:
        raise ConfigError(
            f"ring_prefill requires cache length == T ({kv_cache['k'].shape[2]} != {T})"
        )
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    def attend(qg, k_cache, v_cache, _mask):
        return ring_attention(qg, k_cache, v_cache, seq_lens, mesh)

    # forward() ignores the mask except inside attend_fn; pass a scalar
    # placeholder so no [B, T, S] mask is materialised.
    dummy_mask = jnp.zeros((), bool)
    return forward(
        params, cfg, tokens, positions, kv_cache, dummy_mask, attend,
        logits_at=seq_lens - 1 if last_only else None,
    )
