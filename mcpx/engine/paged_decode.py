"""Decode-step forward pass against the paged KV cache.

Same math as ``mcpx.models.gemma.model`` (shares its RMSNorm/RoPE
primitives and param pytree) but the attention reads/writes go to the shared
page pools via the Pallas ragged paged-attention kernel
(``engine/kernels/paged_attention.py``) instead of a dense per-batch cache.
Kept separate from the model so the dense path stays a clean correctness
reference (SURVEY.md §4.2) and the paged path owns its layout decisions.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mcpx.engine.kernels.paged_attention import (
    paged_attention_chunk,
    paged_attention_chunk_reference,
    ragged_paged_attention,
    ragged_paged_attention_reference,
)
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import apply_rope, rms_norm
from mcpx.parallel.mesh import DATA_AXIS, MODEL_AXIS, _axis


def _ragged_kernel_on_mesh(
    mesh: Mesh,
    qg: jax.Array,  # [B, S, K, G, hd]
    k_all: jax.Array,  # [K, L, N, Psz, hd]
    v_all: jax.Array,
    page_table: jax.Array,  # [B, Pmax]
    positions: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """The ragged kernel under ``jax.shard_map`` over the engine mesh: XLA
    will not partition a Mosaic call by itself, so each device runs the
    kernel on its own block. Rows split over ``data``; KV heads over
    ``model`` where they divide — the pools' own sharding
    (``InferenceEngine._init_pools``) — else the query-group axis with the
    pools replicated (MQA). An axis that does not divide stays whole on
    every device, so a one-device mesh is the same code with nothing split.
    No collective is needed: a (row, head) pair's attention is complete on
    the device that holds it."""
    B, _, K, G, _ = qg.shape
    rows = _axis(mesh, DATA_AXIS, B)
    heads = _axis(mesh, MODEL_AXIS, K)
    groups = None if heads else _axis(mesh, MODEL_AXIS, G)
    q_spec = P(rows, None, heads, groups, None)
    pool_spec = P(heads, None, None, None, None)
    return jax.shard_map(
        functools.partial(ragged_paged_attention, interpret=interpret),
        mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(rows, None), P(rows), P(rows), P()),
        out_specs=q_spec,
        check_vma=False,
    )(qg, k_all, v_all, page_table, positions, q_lens, jnp.asarray(layer, jnp.int32))


def decode_chunk_paged(
    params: dict[str, Any],
    cfg: GemmaConfig,
    tokens: jax.Array,  # [B, S] int32 — chunk of new tokens per sequence
    positions: jax.Array,  # [B] int32 — slot tokens[:, 0] is written to
    page_table: jax.Array,  # [B, Pmax] int32
    paged_kv: dict[str, jax.Array],  # k/v: [K, L, N, Psz, hd]
    *,
    use_pallas: bool = True,
    interpret: bool = False,
    logits_at: "jax.Array | None" = None,  # [B] chunk slot per row, or None
    active_cols: "jax.Array | None" = None,  # [C] token ids: compact unembed
    q_lens: "jax.Array | None" = None,  # [B] live window slots (ragged rows)
    mesh: Optional[Mesh] = None,  # engine mesh; required with q_lens + use_pallas
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Multi-token decode step: S new tokens per sequence in ONE forward.

    This is the verify/extend pass for grammar fast-forward speculation
    (SURVEY.md §6: "speculative decoding headroom"): forced-token chains
    from the plan DFA need no sampling, only KV population and the logits
    at the chain end — so S sequential decode steps collapse into one
    forward whose per-token cost is amortised over the weight loads that
    dominate decode on TPU. The pools ([K, L, N, Psz, hd], all layers) are
    carried through the layer scan; each layer writes its chunk K/V with
    one flat scatter, then the chunk kernel streams that layer's pages
    once for all S queries (query i sees cache through ``positions+i``).

    Tokens past a sequence's valid chain are pads; their K/V slots hold
    garbage that the next chunk (which starts at the first invalid
    position) overwrites, and their logits are ignored by the caller.
    ``q_lens`` makes the raggedness explicit: with per-row live window
    widths the attention (kernel AND jnp reference, in lockstep) streams
    only each row's own pages and zeroes pad-query outputs — suffix
    prefill, plain decode and spec-verify rows share one executable whose
    compile key is the padded window shape alone. None keeps the dense
    pre-ragged contract (every slot computed, pads garbage-but-unread);
    either way the logits callers read are bit-identical, because a pad
    slot's cache position lies strictly past every live query's visible
    range at every layer. Returns ([B, S, V] logits, pools) — or
    ([B, V], pools) when ``logits_at`` names the single chunk slot per
    row to unembed.
    """
    B, S = tokens.shape
    K, L, N, psz, hd = paged_kv["k"].shape
    if use_pallas and q_lens is not None and mesh is None:
        # The ragged kernel only runs under shard_map (a one-device mesh is
        # its trivial case): a bare Mosaic call cannot lower on >1 chip, and
        # the CPU interpreter would not show that.
        raise ValueError("decode_chunk_paged: the ragged kernel route (q_lens) needs mesh=")
    from mcpx.models.gemma.quant import dequant_layer, embed_lookup, unembed

    # Weight-only int8 serving mode (models/gemma/quant.py): identity
    # plumbing on plain params; the second of the two param choke points.
    # Quantized leaves stay the HBM-resident buffers — embed rows gather
    # as int8 + per-row scales, layers dequantize per layer INSIDE the
    # scan body (see dequant_layer), unembeds scale on the output.
    x = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))  # [B, S, D]
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

    pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)  # [B, S]
    # Flat token-slot index into the [K, L, N*psz, hd] pool view: ONE
    # single-advanced-index scatter per layer into the scan CARRY (measured
    # ~3x cheaper on v5e than scattering per-layer slices through scan
    # xs/ys, which copies whole pool slices).
    flat_idx = jnp.take_along_axis(page_table, pos_mat // psz, axis=1) * psz + pos_mat % psz

    def attend(q, k_all, v_all, layer):
        # Both paths stream/gather each sequence's pages ONCE for all S
        # chunk queries (folding the chunk into the batch dim instead would
        # multiply page traffic by S — the dominant decode cost), and the
        # kernel and jnp reference stay in LOCKSTEP on the ragged contract
        # (q_lens) so tier-1's interpret/jnp runs exercise the same
        # semantics TPUs serve.
        qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
        if use_pallas:
            if q_lens is not None:
                out = _ragged_kernel_on_mesh(
                    mesh, qg, k_all, v_all, page_table, positions, q_lens, layer,
                    interpret=interpret,
                )
            else:
                out = paged_attention_chunk(
                    qg, k_all, v_all, page_table, positions, layer,
                    interpret=interpret,
                )
        elif q_lens is not None:
            out = ragged_paged_attention_reference(
                qg, k_all, v_all, page_table, positions, q_lens, layer
            )
        else:
            out = paged_attention_chunk_reference(
                qg, k_all, v_all, page_table, positions, layer
            )
        return out.reshape(B, S, cfg.n_heads * cfg.head_dim)

    def body(carry, lp):
        x, k_all, v_all, layer = carry  # pools: [K, L, N, Psz, hd]
        lp = dequant_layer(lp, jnp.dtype(cfg.dtype))
        h = rms_norm(x, lp["pre_attn_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,dkh->bskh", h, lp["wq"])  # [B, S, H, hd]
        k = jnp.einsum("bsd,dkh->bskh", h, lp["wk"])  # [B, S, K, hd]
        v = jnp.einsum("bsd,dkh->bskh", h, lp["wv"])
        q = apply_rope(q, pos_mat, cfg.rope_theta)
        k = apply_rope(k, pos_mat, cfg.rope_theta)
        k_all = (
            k_all.reshape(K, L, N * psz, hd)
            .at[:, layer, flat_idx]
            .set(k.transpose(2, 0, 1, 3).astype(k_all.dtype))
            .reshape(K, L, N, psz, hd)
        )
        v_all = (
            v_all.reshape(K, L, N * psz, hd)
            .at[:, layer, flat_idx]
            .set(v.transpose(2, 0, 1, 3).astype(v_all.dtype))
            .reshape(K, L, N, psz, hd)
        )
        attn = attend(q, k_all, v_all, layer)
        wo = lp["wo"].reshape(cfg.n_heads * cfg.head_dim, cfg.d_model)
        x = x + jnp.einsum("bsf,fd->bsd", attn, wo)
        h = rms_norm(x, lp["pre_mlp_norm"], cfg.norm_eps)
        ff = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, lp["w_gate"]), approximate=True)
        ff = ff * jnp.einsum("bsd,df->bsf", h, lp["w_up"])
        x = x + jnp.einsum("bsf,fd->bsd", ff, lp["w_down"])
        return (x, k_all, v_all, layer + 1), None

    (x, k_new, v_new, _), _ = lax.scan(
        body,
        (x, paged_kv["k"], paged_kv["v"], jnp.asarray(0, jnp.int32)),
        params["layers"],
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if active_cols is not None:
        # Draft verification needs logits at EVERY chunk position, but only
        # over the grammar's C active columns: gather those unembed rows
        # and contract against them — [B, S, C] instead of [B, S, V]. At a
        # 256k SentencePiece vocab with a few-thousand-column grammar this
        # is ~100x less unembed compute/memory than full-vocab all-position
        # logits, which is what makes per-position verification affordable
        # at all (the "last-only unembed" optimisation stays intact for the
        # non-draft path below).
        return unembed(x, params["embed"], subset=active_cols), {
            "k": k_new,
            "v": v_new,
        }
    if logits_at is not None:
        # Serving only reads ONE position's logits per row (the last valid
        # chunk slot): gather the hidden state BEFORE the unembed so the
        # [B, S, V] logits buffer never exists and the unembed matmul costs
        # 1/S of the all-positions version — at subword vocab sizes that
        # buffer and those FLOPs rival a whole transformer layer.
        x1 = x[jnp.arange(B), logits_at]  # [B, D]
        return unembed(x1, params["embed"]), {"k": k_new, "v": v_new}
    return unembed(x, params["embed"]), {"k": k_new, "v": v_new}


def decode_step_paged(
    params: dict[str, Any],
    cfg: GemmaConfig,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32 — slot this token is written to
    page_table: jax.Array,  # [B, Pmax] int32
    paged_kv: dict[str, jax.Array],  # k/v: [K, L, N, Psz, hd]
    *,
    use_pallas: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One decode step for the whole batch; returns ([B, V] logits, pools).

    The S=1 specialisation of ``decode_chunk_paged`` — a single forward body
    to maintain (their equivalence is pinned by
    ``test_decode_chunk_matches_sequential_steps``).
    """
    logits, pools = decode_chunk_paged(
        params,
        cfg,
        tokens[:, None],
        positions,
        page_table,
        paged_kv,
        use_pallas=use_pallas,
        interpret=interpret,
    )
    return logits[:, 0], pools
