"""Gemma-architecture decoder — pure-functional JAX, TPU-first.

Design choices (vs a torch-style port):
  - params are a plain pytree with layer weights **stacked on a leading
    axis**, and the layer stack runs under ``lax.scan`` — one layer is traced
    and compiled once regardless of depth, and XLA pipelines the scan;
  - two entry points, both jit-friendly with **static shapes**: ``prefill``
    (full-sequence, causal) and ``decode_step`` (one token per sequence
    against a KV cache) — no data-dependent Python control flow;
  - attention logits/softmax computed in float32, weights stored bfloat16
    (MXU-native);
  - GQA/MQA: queries reshaped to [B, T, K, q_per_kv, hd] so the same einsum
    serves MHA (K=H), GQA and MQA (K=1) without branching;
  - KV cache is a dense [L, B, S, K, hd] pytree here; the paged-attention
    engine (``mcpx.engine``) swaps in Pallas kernels for the decode hot loop.

The reference framework has no model code (its planner is a remote OpenAI
call, reference ``control_plane.py:69-73``); this module is the north star's
in-tree replacement.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.moe import activation, moe_forward, split_layers

Params = dict[str, Any]
KVCache = dict[str, jax.Array]


# --------------------------------------------------------------------- init
def _draw_normal(key: jax.Array, divisor: jax.Array, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) / divisor).astype(dtype)


def _draw_by_layer(key: jax.Array, divisor: jax.Array, shape, dtype) -> jax.Array:
    """``_draw_normal`` of a layer-stacked leaf, one layer (one key) at a time."""
    return lax.map(
        lambda k: _draw_normal(k, divisor, shape[1:], dtype), jax.random.split(key, shape[0])
    )


def init_params(cfg: GemmaConfig, key: jax.Array, leaf_transform=None, mesh=None) -> Params:
    """Random-init parameters (bfloat16 by default), layer-stacked.

    Every leaf is CREATED with its sharding: one jitted draw per leaf whose
    ``out_shardings`` is the leaf's ``param_pspecs`` entry on ``mesh`` (None:
    the default device), so no device holds a whole leaf that the specs
    split, nor the whole tree — what lets a 14 GB tree start on four 16 GB
    chips. The bits do not depend on the mesh (threefry is partitionable: an
    element's bits are a function of the key and its index), nor on the jit:
    the divisor is a runtime operand, because XLA rewrites a divide by a
    compile-time constant into a multiply by its reciprocal, which rounds
    differently wherever sqrt(fan_in) is not a power of two.

    ``leaf_transform(name, array)`` is applied to each tensor AT CREATION
    (e.g. ``quant.leaf_quantizer`` for int8 serving), on the sharded leaf:
    intermediates are freed as each transformed leaf replaces them, so the
    full-precision tree never needs to exist at once — the property that
    lets 7B-int8 initialise on a 16 GB chip."""
    dtype = jnp.dtype(cfg.dtype)
    t = leaf_transform or (lambda _name, w: w)
    k_embed, k_q, k_k, k_v, k_o, k_gate, k_up, k_down = jax.random.split(key, 8)
    L, D, H, K, hd, F, V = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.vocab_size,
    )
    if mesh is None:
        sharding = lambda _name: None
    else:
        from jax.sharding import NamedSharding

        from mcpx.parallel.mesh import param_pspecs

        specs = param_pspecs(cfg, mesh)
        by_name = {**specs["layers"], **{k: v for k, v in specs.items() if k != "layers"}}
        sharding = lambda name: NamedSharding(mesh, by_name[name])

    def normal(name, key, shape, fan_in, by_layer=False):
        draw = jax.jit(
            _draw_by_layer if by_layer else _draw_normal,
            static_argnames=("shape", "dtype"),
            out_shardings=sharding(name),
        )
        return t(name, draw(key, np.float32(math.sqrt(fan_in)), shape=shape, dtype=dtype))

    def gain(name, shape):
        # The norm's scale at its identity: 0 under a (1 + scale) gain, else 1.
        fill = jnp.zeros if cfg.norm_plus_one else jnp.ones
        return t(name, fill(shape, dtype, device=sharding(name)))

    layers = {
        "pre_attn_norm": gain("pre_attn_norm", (L, D)),
        "pre_mlp_norm": gain("pre_mlp_norm", (L, D)),
        "wq": normal("wq", k_q, (L, D, H, hd), D),
        "wk": normal("wk", k_k, (L, D, K, hd), D),
        "wv": normal("wv", k_v, (L, D, K, hd), D),
        "wo": normal("wo", k_o, (L, H, hd, D), H * hd),
    }
    if cfg.n_experts:
        E, Fe = cfg.n_experts_held, cfg.d_expert
        layers["router"] = normal("router", jax.random.fold_in(key, 8), (L, D, cfg.n_experts), D)
        # The expert stacks are most of the tree: drawn a layer at a time, so
        # the float32 transient is one layer's and not the leaf's.
        layers["w_gate"] = normal("w_gate", k_gate, (L, E, D, Fe), D, by_layer=True)
        layers["w_up"] = normal("w_up", k_up, (L, E, D, Fe), D, by_layer=True)
        layers["w_down"] = normal("w_down", k_down, (L, E, Fe, D), Fe, by_layer=True)
    else:
        layers["w_gate"] = normal("w_gate", k_gate, (L, D, F), D)
        layers["w_up"] = normal("w_up", k_up, (L, D, F), D)
        layers["w_down"] = normal("w_down", k_down, (L, F, D), F)
    params = {
        "embed": normal("embed", k_embed, (V, D), D),
        "layers": layers,
        "final_norm": gain("final_norm", (D,)),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal("head", jax.random.fold_in(key, 9), (D, V), D)
    return params


def init_kv_cache(cfg: GemmaConfig, batch: int, max_len: int, dtype: str | None = None) -> KVCache:
    d = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, d), "v": jnp.zeros(shape, d)}


# ------------------------------------------------------------------- pieces
def rms_norm(x: jax.Array, scale: jax.Array, eps: float, plus_one: bool = True) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * lax.rsqrt(var + eps)
    if not plus_one:  # a plain gain
        return (normed * scale.astype(jnp.float32)).astype(x.dtype)
    # Gemma convention: scale is a residual around 1.
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_kinds(cfg: GemmaConfig) -> dict[str, jax.Array]:
    """What differs from layer to layer, as data the ONE layer scan scans
    beside the weights: ``inv_freq`` [L, hd/2] and ``rope_factor`` [L]
    (``GemmaConfig.rope_tables``), ``window`` [L] (``layer_windows``). Empty
    where every layer is alike: the scan then carries what it always did."""
    kinds = {}
    rope = cfg.rope_tables()
    if rope is not None:
        kinds["inv_freq"], kinds["rope_factor"] = jnp.asarray(rope[0]), jnp.asarray(rope[1])
    windows = cfg.layer_windows()
    if windows is not None:
        kinds["window"] = jnp.asarray(windows)
    return kinds


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    kind: "dict[str, jax.Array] | None" = None,
) -> jax.Array:
    """Rotary embeddings. x: [..., seq, heads, head_dim]; positions: [..., seq].
    ``kind``: this layer's slice of ``layer_kinds`` — its own inverse
    frequencies and the factor on cos and sin, where the layers differ."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    if kind and "inv_freq" in kind:
        freq = kind["inv_freq"]
    else:
        freq = jnp.exp(
            -math.log(theta) * (2.0 * jnp.arange(half, dtype=jnp.float32) / head_dim)
        )  # [half]
    angles = positions[..., None].astype(jnp.float32) * freq  # [..., seq, half]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if kind and "rope_factor" in kind:
        cos, sin = cos * kind["rope_factor"], sin * kind["rope_factor"]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array) -> jax.Array:
    """q: [B, T, K, G, hd]; k,v: [B, S, K, hd]; mask: [B, T, S] (True=keep).

    Returns [B, T, K, G, hd]. Softmax in float32.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("btkgh,bskh->btkgs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", weights.astype(v.dtype), v)
    return out


def _layer(
    x: jax.Array,
    lp: dict[str, jax.Array],
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
    mask: jax.Array,
    write_idx: jax.Array,
    cfg: GemmaConfig,
    attend_fn=None,
    kind: "dict[str, jax.Array] | None" = None,
    moe: "tuple | None" = None,
) -> tuple:
    """One transformer block over [B, T]; writes K/V at ``write_idx``.

    x: [B, T, D]; k_cache/v_cache: [B, S, K, hd]; positions: [B, T];
    mask: [B, T, S]; write_idx: [B, T] absolute cache slots for this chunk.
    ``kind``: this layer's slice of ``layer_kinds``. ``moe``: (expert
    stacks, layer index, live [B, T]) under a sparse feed-forward; the
    return then ends with the layer's counters and chosen experts.
    """
    B, T, D = x.shape
    h = rms_norm(x, lp["pre_attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
    q = jnp.einsum("btd,dkh->btkh", h, lp["wq"])
    k = jnp.einsum("btd,dkh->btkh", h, lp["wk"])
    v = jnp.einsum("btd,dkh->btkh", h, lp["wv"])
    q = apply_rope(q, positions, cfg.rope_theta, kind)
    k = apply_rope(k, positions, cfg.rope_theta, kind)

    b_idx = jnp.arange(B)[:, None]  # [B, 1] broadcast with write_idx [B, T]
    k_cache = k_cache.at[b_idx, write_idx].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[b_idx, write_idx].set(v.astype(v_cache.dtype))

    if kind and "window" in kind:
        # A sliding layer's query sees itself and the window - 1 keys before.
        s_idx = jnp.arange(mask.shape[-1])
        mask = mask & (s_idx[None, None, :] > positions[:, :, None] - kind["window"])
    qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
    attn = (attend_fn or _attend)(qg, k_cache, v_cache, mask)
    attn = attn.reshape(B, T, cfg.n_heads * cfg.head_dim)
    wo = lp["wo"].reshape(cfg.n_heads * cfg.head_dim, D)
    x = x + jnp.einsum("btf,fd->btd", attn, wo)

    h = rms_norm(x, lp["pre_mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
    if moe is not None:
        experts, layer, live = moe
        ff, stats, chosen = moe_forward(h, lp["router"], experts, layer, cfg, live)
        return x + ff, k_cache, v_cache, stats, chosen
    gate = jnp.einsum("btd,df->btf", h, lp["w_gate"])
    up = jnp.einsum("btd,df->btf", h, lp["w_up"])
    ff = activation(cfg, gate) * up
    x = x + jnp.einsum("btf,fd->btd", ff, lp["w_down"])
    return x, k_cache, v_cache


def embed_tokens(params: Params, cfg: GemmaConfig, tokens: jax.Array) -> jax.Array:
    from mcpx.models.gemma.quant import embed_lookup

    x = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def output_logits(
    params: Params, cfg: GemmaConfig, x: jax.Array, subset: "jax.Array | None" = None
) -> jax.Array:
    """Float32 logits of final-normed hidden states: against the embedding
    matrix where it is tied, else against the ``head`` leaf [D, V].
    ``subset`` [C] restricts to those vocabulary entries."""
    from mcpx.models.gemma.quant import unembed

    if cfg.tie_embeddings:
        return unembed(x, params["embed"], subset=subset)
    head = params["head"] if subset is None else params["head"][:, subset]
    return jnp.einsum("...d,dv->...v", x, head, preferred_element_type=jnp.float32)


def forward(
    params: Params,
    cfg: GemmaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    kv_cache: KVCache,
    mask: jax.Array,
    attend_fn=None,
    logits_at: "jax.Array | None" = None,
    live: "jax.Array | None" = None,
    routing: bool = False,
) -> tuple:
    """Core forward over a [B, T] token chunk against a [L, B, S, K, hd]
    cache. ``positions`` are absolute (double as cache write slots);
    ``mask`` is [B, T, S] (True = attend). ``attend_fn`` swaps the attention
    op (e.g. ring attention for sequence-parallel long-context prefill).
    ``logits_at`` [B]: unembed only that position per row -> [B, V].
    ``live`` [B, T]: the slots that are tokens and not padding; a sparse
    feed-forward routes the others nowhere. ``routing``: also return the
    experts chosen, [L, B, T, k]."""
    from mcpx.models.gemma.quant import dequant_layer

    # Weight-only int8 serving mode (quant.py): identity plumbing on plain
    # params. The quantized leaves stay the HBM-resident buffers — embed
    # rows gather as int8 + per-row scales, and the layer stack dequantizes
    # PER LAYER inside the scan body (see dequant_layer's docstring for why
    # position matters).
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params, cfg, tokens)
    scanned, experts = split_layers(cfg, params["layers"])

    def body(carry, scanned):
        lp, kind, k_c, v_c = scanned
        lp = dequant_layer(lp, dtype)
        if cfg.n_experts:
            x, layer = carry
            x, k_c, v_c, _stats, chosen = _layer(
                x, lp, k_c, v_c, positions, mask, positions, cfg, attend_fn, kind,
                moe=(experts, layer, live),
            )
            return (x, layer + 1), (k_c, v_c, chosen)
        x, k_c, v_c = _layer(carry, lp, k_c, v_c, positions, mask, positions, cfg, attend_fn, kind)
        return x, (k_c, v_c)

    carry = (x, jnp.asarray(0, jnp.int32)) if cfg.n_experts else x
    carry, ys = lax.scan(
        body, carry, (scanned, layer_kinds(cfg), kv_cache["k"], kv_cache["v"])
    )
    x = carry[0] if cfg.n_experts else carry
    k_new, v_new = ys[0], ys[1]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    if logits_at is not None:
        # Single-position unembed (serving prefill reads only each row's
        # last prompt token): gathering the hidden state first keeps the
        # [B, T, V] logits buffer from ever existing — at subword vocab
        # sizes that buffer is hundreds of MB and its matmul rivals the
        # whole layer stack.
        B = tokens.shape[0]
        x = x[jnp.arange(B), logits_at]  # [B, D]
    out = output_logits(params, cfg, x), {"k": k_new, "v": v_new}
    return out + (ys[2],) if routing else out


# -------------------------------------------------------------- entrypoints
def prefill(
    params: Params,
    cfg: GemmaConfig,
    tokens: jax.Array,
    seq_lens: jax.Array,
    kv_cache: KVCache,
    last_only: bool = False,
    routing: bool = False,
) -> tuple:
    """Prefill a padded [B, T] batch. ``seq_lens`` [B] masks right-padding.

    Returns logits [B, T, V] and the filled cache — or [B, V] (each row's
    last valid position only) with ``last_only``, the serving path's shape;
    with ``routing`` also the experts each slot chose, [L, B, T, k].
    """
    B, T = tokens.shape
    S = kv_cache["k"].shape[2]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    s = jnp.arange(S)
    causal = s[None, None, :] <= positions[:, :, None]  # [B, T, S]
    valid = s[None, None, :] < seq_lens[:, None, None]
    mask = causal & valid
    return forward(
        params, cfg, tokens, positions, kv_cache, mask,
        logits_at=seq_lens - 1 if last_only else None,
        live=positions < seq_lens[:, None],
        routing=routing,
    )


def decode_step(
    params: Params,
    cfg: GemmaConfig,
    token: jax.Array,
    cur_index: jax.Array,
    kv_cache: KVCache,
) -> tuple[jax.Array, KVCache]:
    """One decode step: ``token`` [B] is written at per-sequence slot
    ``cur_index`` [B]; attends to cache[0..cur_index]. Returns logits [B, V]
    and the updated cache."""
    B = token.shape[0]
    S = kv_cache["k"].shape[2]
    positions = cur_index[:, None]  # [B, 1]
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]  # [B, 1, S]
    logits, kv_cache = forward(params, cfg, token[:, None], positions, kv_cache, mask)
    return logits[:, 0, :], kv_cache
