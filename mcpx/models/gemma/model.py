"""Gemma-architecture decoder — pure-functional JAX, TPU-first.

Design choices (vs a torch-style port):
  - params are a plain pytree with layer weights **stacked on a leading
    axis**, and the layer stack runs under ``lax.scan`` — one layer is traced
    and compiled once regardless of depth, and XLA pipelines the scan; a
    leaf is scanned in the shape its matmul contracts, so that the scan's
    slice feeds the dot with nothing between (``wo`` is stored per head,
    ``[L, H, hd, D]``, and scanned as the view ``[L, H * hd, D]`` that
    ``layer_stacks`` takes of the whole stack outside the loop);
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

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.moe import (
    activation, add_forward_stats, add_layer_stats, moe_forward, moe_stats_init, split_layers,
)

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
        stacks = (
            "layers", "dense_layers", "mamba_layers", "attn_layers", "linear_layers", "block_layers",
            "conv_layers", "scan_layers",
        )
        by_name = {
            **specs.get("layers", {}),
            **{k: v for k, v in specs.items() if k not in stacks},
            **{s + "." + k: v for s in stacks[1:] for k, v in specs.get(s, {}).items()},
        }
        sharding = lambda name: NamedSharding(mesh, by_name[name])

    def normal(name, key, shape, fan_in, by_layer=False, stack="", as_type=dtype):
        # ``stack``: "" or "dense_layers.", the leading dense layers' own
        # stack, whose keys are folded apart from the other stack's.
        draw = jax.jit(
            _draw_by_layer if by_layer else _draw_normal,
            static_argnames=("shape", "dtype"),
            out_shardings=sharding(stack + name),
        )
        key = jax.random.fold_in(key, 20) if stack else key
        return t(name, draw(key, np.float32(math.sqrt(fan_in)), shape=shape, dtype=as_type))

    def gain(name, shape, stack=""):
        # The norm's scale at its identity: 0 under a (1 + scale) gain, else 1.
        fill = jnp.zeros if cfg.norm_plus_one else jnp.ones
        return t(name, fill(shape, dtype, device=sharding(stack + name)))

    def latent_attention(n, stack):
        """``cfg.latent``: the query's bottleneck and its norm, the shared
        latent (with the rotated key beside it, ``w_dkv``'s last columns) and
        its norm, the per-head expansions, Wo over every head's value."""
        rq, rkv, dr, dv = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
        index = {}
        if cfg.index_topk:
            # The index: its queries out of the query's latent, one key and
            # the per-head weights out of the layer's normed input; the key's
            # LayerNorm at its identity (gain 1, bias 0).
            Hi, di = cfg.index_n_heads, cfg.index_head_dim
            index = {
                "w_qi": normal("w_qi", jax.random.fold_in(key, 16), (n, rq, Hi, di), rq, stack=stack),
                "w_ki": normal("w_ki", jax.random.fold_in(key, 17), (n, D, di), D, stack=stack),
                "ki_norm": t("ki_norm", jnp.ones((n, di), dtype, device=sharding(stack + "ki_norm"))),
                "ki_norm_bias": t(
                    "ki_norm_bias", jnp.zeros((n, di), dtype, device=sharding(stack + "ki_norm_bias"))
                ),
                "w_wi": normal("w_wi", jax.random.fold_in(key, 18), (n, D, Hi), D, stack=stack),
            }
        return {
            **index,
            "pre_attn_norm": gain("pre_attn_norm", (n, D), stack),
            "pre_mlp_norm": gain("pre_mlp_norm", (n, D), stack),
            "w_dq": normal("w_dq", k_q, (n, D, rq), D, stack=stack),
            "q_lora_norm": gain("q_lora_norm", (n, rq), stack),
            # fan-in times the score factor squared: a head's scores then have
            # unit variance under the block's softmax scale, as 1 / sqrt(fan_in)
            # leaves them where the scale is head_dim^-0.5 alone
            "w_uq": normal(
                "w_uq", jax.random.fold_in(key, 15), (n, rq, H, hd + dr),
                rq * cfg.attn_score_factor**2, stack=stack,
            ),
            "w_dkv": normal("w_dkv", k_k, (n, D, rkv + dr), D, stack=stack),
            "kv_lora_norm": gain("kv_lora_norm", (n, rkv), stack),
            "w_ukv": normal("w_ukv", k_v, (n, rkv, H, hd + dv), rkv, stack=stack),
            "wo": normal("wo", k_o, (n, H, dv, D), H * dv, stack=stack),
        }

    def attention(n, stack=""):
        """The leaves every layer has, for a stack of ``n`` layers."""
        if cfg.latent:
            return latent_attention(n, stack)
        leaves = {
            "pre_attn_norm": gain("pre_attn_norm", (n, D), stack),
            "pre_mlp_norm": gain("pre_mlp_norm", (n, D), stack),
            "wq": normal("wq", k_q, (n, D, H, hd), D, stack=stack),
            "wk": normal("wk", k_k, (n, D, K, hd), D, stack=stack),
            "wv": normal("wv", k_v, (n, D, K, hd), D, stack=stack),
            "wo": normal("wo", k_o, (n, H, hd, D), H * hd, stack=stack),
        }
        if cfg.qk_norm:
            leaves["q_norm"] = gain("q_norm", (n, hd), stack)
            leaves["k_norm"] = gain("k_norm", (n, hd), stack)
        if cfg.attn_gate:
            leaves["w_attn_gate"] = normal(
                "w_attn_gate", jax.random.fold_in(key, 10), (n, D, H, hd), D, stack=stack
            )
        if cfg.post_norms:
            leaves["post_attn_norm"] = gain("post_attn_norm", (n, D), stack)
            leaves["post_mlp_norm"] = gain("post_mlp_norm", (n, D), stack)
        return leaves

    def dense_ff(n, stack=""):
        return {
            "w_gate": normal("w_gate", k_gate, (n, D, F), D, stack=stack),
            "w_up": normal("w_up", k_up, (n, D, F), D, stack=stack),
            "w_down": normal("w_down", k_down, (n, F, D), F, stack=stack),
        }

    if cfg.mixer_ffn:
        return _init_mixer_ffn(cfg, key, normal, sharding, t)
    if cfg.conv_ffn:
        return _init_conv_ffn(cfg, key, normal, sharding, t)
    if cfg.scan_ffn:
        return _init_scan_ffn(cfg, key, normal, sharding, t)
    if cfg.hybrid:
        return _init_hybrid(cfg, key, normal, sharding, t)
    Ls = cfg.n_sparse_layers
    layers = attention(Ls or L)
    if cfg.n_experts:
        E, Fe, Fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared_expert
        layers["router"] = normal("router", jax.random.fold_in(key, 8), (Ls, D, cfg.n_experts), D)
        if cfg.router_bias_scale:
            # float32 whatever the weights' type: it is added to float32 scores.
            layers["router_bias"] = normal(
                "router_bias", jax.random.fold_in(key, 11), (Ls, cfg.n_experts),
                cfg.router_bias_scale**-2, as_type=jnp.float32,
            )
        if Fs:
            layers["shared_gate"] = normal("shared_gate", jax.random.fold_in(key, 12), (Ls, D, Fs), D)
            layers["shared_up"] = normal("shared_up", jax.random.fold_in(key, 13), (Ls, D, Fs), D)
            layers["shared_down"] = normal("shared_down", jax.random.fold_in(key, 14), (Ls, Fs, D), Fs)
        # The expert stacks are most of the tree: drawn a layer at a time, so
        # the float32 transient is one layer's and not the leaf's.
        layers["w_gate"] = normal("w_gate", k_gate, (Ls, E, D, Fe), D, by_layer=True)
        layers["w_up"] = normal("w_up", k_up, (Ls, E, D, Fe), D, by_layer=True)
        layers["w_down"] = normal("w_down", k_down, (Ls, E, Fe, D), Fe, by_layer=True)
    else:
        layers.update(dense_ff(L))
    params = {
        "embed": normal("embed", k_embed, (V, D), D),
        "layers": layers,
        "final_norm": gain("final_norm", (D,)),
    }
    if cfg.n_dense_layers:
        # The leading dense layers are a stack of their own: a dense
        # feed-forward [Ld, D, F] and an expert stack [Ls, E, D, Fe] cannot
        # share a leaf.
        Ld = cfg.n_dense_layers
        params["dense_layers"] = {**attention(Ld, "dense_layers."), **dense_ff(Ld, "dense_layers.")}
    if not cfg.tie_embeddings:
        params["head"] = normal("head", jax.random.fold_in(key, 9), (D, V), D)
    return params


def _init_hybrid(cfg: GemmaConfig, key: jax.Array, normal, sharding, t) -> Params:
    """``init_params`` of a ``layer_pattern`` model: three stacks, a row a
    layer of its kind in layer order: ``mamba_layers``, ``layers`` (the
    feed-forward ones: router, latent projections, shared expert, the experts
    held) and ``attn_layers``; every layer has ONE norm, ``norm``. The
    Mamba mixer's scalars are drawn as the family initialises them, so that
    a random stack's states neither vanish nor saturate: ``dt_bias`` the
    inverse softplus of a step log-uniform in [time_step_min, time_step_max]
    (floored at time_step_floor), ``A_log = log U[1, 16]``, ``D_skip`` 1, the
    taps uniform in +-1 / sqrt(K), their bias 0, the gated norm's gain 1."""
    dtype = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    D, H, K, hd, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size
    Lm, La, Ls = cfg.n_mamba_layers, cfg.n_attn_layers, cfg.n_sparse_layers
    fold = lambda i: jax.random.fold_in(key, 100 + i)

    def ones(name, shape, stack, as_type=dtype):
        return t(name, jnp.ones(shape, as_type, device=sharding(stack + name)))

    def drawn(name, stack, fn, shape, k):
        out = jax.jit(fn, static_argnames=("shape",), out_shardings=sharding(stack + name))
        return t(name, out(k, shape=shape))

    params = {
        "embed": normal("embed", fold(0), (V, D), D),
        "final_norm": t("final_norm", jnp.ones((D,), dtype, device=sharding("final_norm"))),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal("head", fold(1), (D, V), D)
    if Lm:
        st = "mamba_layers."
        inner, C, Hm, Kc = cfg.mamba_inner, cfg.conv_width, cfg.mamba_n_heads, cfg.conv_kernel
        lo, hi, floor = cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor

        def dt_bias(k, shape):
            u = jax.random.uniform(k, shape, f32)
            step = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
            return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)

        params["mamba_layers"] = {
            "norm": ones("norm", (Lm, D), st),
            "w_in": normal("w_in", fold(2), (Lm, D, inner + C + Hm), D, stack=st),
            "conv_w": drawn(
                "conv_w", st,
                lambda k, shape: jax.random.uniform(k, shape, f32, -1.0, 1.0).astype(dtype) * Kc**-0.5,
                (Lm, C, Kc), fold(3),
            ),
            "conv_b": t("conv_b", jnp.zeros((Lm, C), dtype, device=sharding(st + "conv_b"))),
            "dt_bias": drawn("dt_bias", st, dt_bias, (Lm, Hm), fold(4)),
            "A_log": drawn(
                "A_log", st, lambda k, shape: jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0)),
                (Lm, Hm), fold(5),
            ),
            "D_skip": ones("D_skip", (Lm, Hm), st, f32),
            "gate_norm": ones("gate_norm", (Lm, inner), st),
            "w_out": normal("w_out", fold(6), (Lm, inner, D), inner, stack=st),
        }
    if La:
        st = "attn_layers."
        params["attn_layers"] = {
            "norm": ones("norm", (La, D), st),
            # Heads merged on the matmul's own axis: a layer's row of the stack
            # feeds its dot as it lies (a [D, H, hd] row is transposed first).
            "wq": normal("wq", fold(7), (La, D, H * hd), D, stack=st),
            "wk": normal("wk", fold(8), (La, D, K * hd), D, stack=st),
            "wv": normal("wv", fold(9), (La, D, K * hd), D, stack=st),
            "wo": normal("wo", fold(10), (La, H * hd, D), H * hd, stack=st),
        }
    if Ls:
        E, Fe, Fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared_expert
        Dl = cfg.moe_latent_size or D
        layers = {
            "norm": ones("norm", (Ls, D), ""),
            "router": normal("router", fold(11), (Ls, D, cfg.n_experts), D),
            "shared_up": normal("shared_up", fold(12), (Ls, D, Fs), D),
            "shared_down": normal("shared_down", fold(13), (Ls, Fs, D), Fs),
            "w_up": normal("w_up", fold(14), (Ls, E, Dl, Fe), Dl, by_layer=True),
            "w_down": normal("w_down", fold(15), (Ls, E, Fe, Dl), Fe, by_layer=True),
        }
        if cfg.router_bias_scale:
            layers["router_bias"] = normal(
                "router_bias", fold(16), (Ls, cfg.n_experts), cfg.router_bias_scale**-2, as_type=f32
            )
        if cfg.moe_latent_size:
            layers["latent_down"] = normal("latent_down", fold(17), (Ls, D, Dl), D)
            layers["latent_up"] = normal("latent_up", fold(18), (Ls, Dl, D), Dl)
        params["layers"] = layers
    return params


def _init_mixer_ffn(cfg: GemmaConfig, key: jax.Array, normal, sharding, t) -> Params:
    """``init_params`` of an ``L`` / ``S`` pattern: two stacks, a row a layer
    of its kind in layer order, ``linear_layers`` and ``block_layers``, each
    layer its mixer's leaves (heads merged on the matmul's own axis), its two
    norms (``norm`` before the mixer, ``mlp_norm`` before the feed-forward)
    and the dense gated feed-forward. Gains 1; the decay of a linear layer is
    no leaf (``GemmaConfig.linear_decay``)."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, K, hd, F, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size
    fold = lambda i: jax.random.fold_in(key, 200 + i)

    def ones(name, shape, stack):
        return t(name, jnp.ones(shape, dtype, device=sharding(stack + name)))

    def shared(n, st, base):  # the leaves both kinds have
        leaves = {
            "norm": ones("norm", (n, D), st),
            "mlp_norm": ones("mlp_norm", (n, D), st),
            "wq": normal("wq", fold(base), (n, D, H * hd), D, stack=st),
            "wo": normal("wo", fold(base + 1), (n, H * hd, D), H * hd, stack=st),
            "w_gate": normal("w_gate", fold(base + 2), (n, D, F), D, stack=st),
            "w_up": normal("w_up", fold(base + 3), (n, D, F), D, stack=st),
            "w_down": normal("w_down", fold(base + 4), (n, F, D), F, stack=st),
        }
        if cfg.attn_gate:
            leaves["w_attn_gate"] = normal("w_attn_gate", fold(base + 5), (n, D, H * hd), D, stack=st)
        return leaves

    params = {
        "embed": normal("embed", fold(0), (V, D), D),
        "final_norm": t("final_norm", jnp.ones((D,), dtype, device=sharding("final_norm"))),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal("head", fold(1), (D, V), D)
    Ll, Lb = cfg.n_linear_layers, cfg.n_block_layers
    if Ll:
        st = "linear_layers."
        params["linear_layers"] = {
            **shared(Ll, st, 10),
            "wk": normal("wk", fold(16), (Ll, D, H * hd), D, stack=st),
            "wv": normal("wv", fold(17), (Ll, D, H * hd), D, stack=st),
            "o_norm": ones("o_norm", (Ll, H * hd), st),
        }
        if cfg.qk_norm:
            params["linear_layers"]["q_norm"] = ones("q_norm", (Ll, hd), st)
            params["linear_layers"]["k_norm"] = ones("k_norm", (Ll, hd), st)
    if Lb:
        st = "block_layers."
        params["block_layers"] = {
            **shared(Lb, st, 30),
            "wk": normal("wk", fold(36), (Lb, D, K * hd), D, stack=st),
            "wv": normal("wv", fold(37), (Lb, D, K * hd), D, stack=st),
        }
    return params


def _init_conv_ffn(cfg: GemmaConfig, key: jax.Array, normal, sharding, t) -> Params:
    """``init_params`` of a ``C`` / ``A`` pattern: a mixer's stack a kind, a row
    a layer of its kind in layer order, ``conv_layers`` (``w_in`` [D, 3 D] the
    thirds b | c | x, the taps ``conv_w`` [D, K] uniform in +-1 / sqrt(K),
    ``w_out``) and ``attn_layers`` (heads merged on the matmul's own axis, one
    gain of ``head_dim`` each for q and k), each with the layer's first norm
    ``norm``; and a feed-forward's stack a kind, a row a layer of ITS kind in
    layer order, ``dense_layers`` (the ``n_dense_layers`` leading ones) and
    ``layers`` (the routed ones: router, its float32 bias, the experts held),
    each with the layer's second norm ``pre_mlp_norm``. Gains 1."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, K, hd, F, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size
    Lc, La, Ls = cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_sparse_layers
    Ld = cfg.n_layers - Ls
    Kc = cfg.conv_kernel
    fold = lambda i: jax.random.fold_in(key, 300 + i)

    def ones(name, shape, stack):
        return t(name, jnp.ones(shape, dtype, device=sharding(stack + name)))

    params = {
        "embed": normal("embed", fold(0), (V, D), D),
        "final_norm": t("final_norm", jnp.ones((D,), dtype, device=sharding("final_norm"))),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal("head", fold(1), (D, V), D)
    if Lc:
        st = "conv_layers."
        taps = jax.jit(
            lambda k, shape: jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0).astype(dtype) * Kc**-0.5,
            static_argnames=("shape",), out_shardings=sharding(st + "conv_w"),
        )
        params["conv_layers"] = {
            "norm": ones("norm", (Lc, D), st),
            "w_in": normal("w_in", fold(2), (Lc, D, 3 * D), D, stack=st),
            "conv_w": t("conv_w", taps(fold(3), shape=(Lc, D, Kc))),
            "w_out": normal("w_out", fold(4), (Lc, D, D), D, stack=st),
        }
    if La:
        st = "attn_layers."
        params["attn_layers"] = {
            "norm": ones("norm", (La, D), st),
            "wq": normal("wq", fold(5), (La, D, H * hd), D, stack=st),
            "wk": normal("wk", fold(6), (La, D, K * hd), D, stack=st),
            "wv": normal("wv", fold(7), (La, D, K * hd), D, stack=st),
            "wo": normal("wo", fold(8), (La, H * hd, D), H * hd, stack=st),
            "q_norm": ones("q_norm", (La, hd), st),
            "k_norm": ones("k_norm", (La, hd), st),
        }
    if Ld:
        st = "dense_layers."
        params["dense_layers"] = {
            "pre_mlp_norm": ones("pre_mlp_norm", (Ld, D), st),
            "w_gate": normal("w_gate", fold(9), (Ld, D, F), D, stack=st),
            "w_up": normal("w_up", fold(10), (Ld, D, F), D, stack=st),
            "w_down": normal("w_down", fold(11), (Ld, F, D), F, stack=st),
        }
    if Ls:
        E, Fe = cfg.n_experts_held, cfg.d_expert
        layers = {
            "pre_mlp_norm": ones("pre_mlp_norm", (Ls, D), ""),
            "router": normal("router", fold(12), (Ls, D, cfg.n_experts), D),
            "w_gate": normal("w_gate", fold(13), (Ls, E, D, Fe), D, by_layer=True),
            "w_up": normal("w_up", fold(14), (Ls, E, D, Fe), D, by_layer=True),
            "w_down": normal("w_down", fold(15), (Ls, E, Fe, D), Fe, by_layer=True),
        }
        if cfg.router_bias_scale:
            layers["router_bias"] = normal(
                "router_bias", fold(16), (Ls, cfg.n_experts), cfg.router_bias_scale**-2,
                as_type=jnp.float32,
            )
        params["layers"] = layers
    return params


def _init_scan_ffn(cfg: GemmaConfig, key: jax.Array, normal, sharding, t) -> Params:
    """``init_params`` of a ``J`` / ``Q`` pattern: two stacks, a row a layer of
    its kind in layer order, ``scan_layers`` (the Mamba-1 mixer: ``w_in`` [D,
    2 I] the halves x | z, the taps ``conv_w`` [I, K] and their bias, ``w_x``
    [I, R + 2 N], the three inner gains, ``w_dt`` [R, I] and its float32 bias,
    ``A_log`` [N, I] and ``D_skip`` [I] float32, ``w_out``) and ``attn_layers``
    (heads merged on the matmul's own axis), each layer with its two norms
    (``norm`` before the mixer, ``mlp_norm`` before the feed-forward) and the
    dense gated feed-forward. The mixer's scalars are drawn as the Mamba
    family initialises them, so that a random stack's states neither vanish
    nor saturate: ``A_log[n, c] = log(n + 1)`` (the state's N on the leading
    axis: the pool's and the kernel's layout), ``D_skip`` 1, ``dt_bias`` the
    inverse softplus of a step log-uniform in [time_step_min, time_step_max]
    (floored at time_step_floor), the taps uniform in +-1 / sqrt(K), their
    bias 0, every gain 1."""
    dtype = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    D, H, K, hd, F, V = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size
    I, N, R, Kc = cfg.scan_inner, cfg.ssm_state_size, cfg.mamba_dt_rank, cfg.conv_kernel
    Lj, Lq = cfg.n_scan_layers, cfg.n_attn_layers
    fold = lambda i: jax.random.fold_in(key, 400 + i)

    def ones(name, shape, stack, as_type=dtype):
        return t(name, jnp.ones(shape, as_type, device=sharding(stack + name)))

    def drawn(name, stack, fn, shape, k):
        out = jax.jit(fn, static_argnames=("shape",), out_shardings=sharding(stack + name))
        return t(name, out(k, shape=shape))

    def shared(n, st, base):  # the leaves both kinds have
        return {
            "norm": ones("norm", (n, D), st),
            "mlp_norm": ones("mlp_norm", (n, D), st),
            "w_gate": normal("w_gate", fold(base), (n, D, F), D, stack=st),
            "w_up": normal("w_up", fold(base + 1), (n, D, F), D, stack=st),
            "w_down": normal("w_down", fold(base + 2), (n, F, D), F, stack=st),
        }

    params = {
        "embed": normal("embed", fold(0), (V, D), D),
        "final_norm": t("final_norm", jnp.ones((D,), dtype, device=sharding("final_norm"))),
    }
    if not cfg.tie_embeddings:
        params["head"] = normal("head", fold(1), (D, V), D)
    if Lj:
        st = "scan_layers."
        lo, hi, floor = cfg.time_step_min, cfg.time_step_max, cfg.time_step_floor

        def dt_bias(k, shape):
            u = jax.random.uniform(k, shape, f32)
            step = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
            return step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)

        params["scan_layers"] = {
            **shared(Lj, st, 10),
            "w_in": normal("w_in", fold(2), (Lj, D, 2 * I), D, stack=st),
            "conv_w": drawn(
                "conv_w", st,
                lambda k, shape: jax.random.uniform(k, shape, f32, -1.0, 1.0).astype(dtype) * Kc**-0.5,
                (Lj, I, Kc), fold(3),
            ),
            "conv_b": t("conv_b", jnp.zeros((Lj, I), dtype, device=sharding(st + "conv_b"))),
            "w_x": normal("w_x", fold(4), (Lj, I, R + 2 * N), I, stack=st),
            "dt_norm": ones("dt_norm", (Lj, R), st),
            "b_norm": ones("b_norm", (Lj, N), st),
            "c_norm": ones("c_norm", (Lj, N), st),
            "w_dt": normal("w_dt", fold(5), (Lj, R, I), R, stack=st),
            "dt_bias": drawn("dt_bias", st, dt_bias, (Lj, I), fold(6)),
            "A_log": drawn(
                "A_log", st,
                lambda k, shape: jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32))[None, :, None], shape
                ),
                (Lj, N, I), fold(7),
            ),
            "D_skip": ones("D_skip", (Lj, I), st, f32),
            "w_out": normal("w_out", fold(8), (Lj, I, D), I, stack=st),
        }
    if Lq:
        st = "attn_layers."
        params["attn_layers"] = {
            **shared(Lq, st, 20),
            "wq": normal("wq", fold(30), (Lq, D, H * hd), D, stack=st),
            "wk": normal("wk", fold(31), (Lq, D, K * hd), D, stack=st),
            "wv": normal("wv", fold(32), (Lq, D, K * hd), D, stack=st),
            "wo": normal("wo", fold(33), (Lq, H * hd, D), H * hd, stack=st),
        }
    return params


def init_kv_cache(cfg: GemmaConfig, batch: int, max_len: int, dtype: str | None = None) -> KVCache:
    """The dense cache ``[L, B, S, K, width]``: a head's key and value, or
    under latent attention the shared rotated key (``k``) and the latent
    (``v``), ``GemmaConfig.kv_widths``."""
    d = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.n_attn_layers, batch, max_len, cfg.kv_pool_heads)
    k_width, v_width = cfg.kv_widths
    return {"k": jnp.zeros(shape + (k_width,), d), "v": jnp.zeros(shape + (v_width,), d)}


# ------------------------------------------------------------------- pieces
def rms_norm(
    x: jax.Array, scale: jax.Array, eps: float, plus_one: bool = True, out_dtype=None
) -> jax.Array:
    """``out_dtype``: x's own unless given (float32 where what follows is
    elementwise too, so that the chain rounds once, at its end)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * lax.rsqrt(var + eps)
    gain = scale.astype(jnp.float32)
    if plus_one:  # Gemma convention: scale is a residual around 1; else a plain gain
        gain = 1.0 + gain
    return (normed * gain).astype(out_dtype or x.dtype)


def layer_kinds(cfg: GemmaConfig, lo: int = 0, hi: "int | None" = None) -> dict[str, jax.Array]:
    """What differs from layer to layer, as data the layer scan scans
    beside the weights, for layers ``lo`` to ``hi``: ``inv_freq`` [L, hd/2]
    and ``rope_factor`` [L] (``GemmaConfig.rope_tables``), ``window`` [L]
    (``layer_windows``). Empty where every layer is alike: the scan then
    carries what it always did."""
    kinds = {}
    rope = cfg.rope_tables()
    if rope is not None:
        kinds["inv_freq"], kinds["rope_factor"] = (jnp.asarray(a[lo:hi]) for a in rope)
    windows = cfg.layer_windows()
    if windows is not None:
        kinds["window"] = jnp.asarray(windows[lo:hi])
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


def _attend(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, score_factor: float = 1.0
) -> jax.Array:
    """q: [B, T, K, G, hd]; k: [B, S, K, hd]; v: [B, S, K, hv]; mask:
    [B, T, S] (True=keep). ``score_factor`` multiplies the softmax scale.

    Returns [B, T, K, G, hv]. Softmax in float32.
    """
    scale = score_factor / math.sqrt(q.shape[-1])
    logits = jnp.einsum("btkgh,bskh->btkgs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", weights.astype(v.dtype), v)
    return out


# Queries a latent layer's expanded attention scores at a time: every head is
# its own key head there, so a 1,024-token window's float32 scores are 268 MB
# a row at 64 heads, and a cohort's would not fit beside the weights.
QUERY_BLOCK = 256


def _attend_query_blocks(q, k, v, mask, score_factor: float) -> jax.Array:
    """``_attend`` a block of ``QUERY_BLOCK`` queries at a time (any mask:
    every block sees every key its mask keeps)."""
    T = q.shape[1]
    if T <= QUERY_BLOCK:
        return _attend(q, k, v, mask, score_factor)
    blocks = [
        _attend(q[:, i : i + QUERY_BLOCK], k, v, mask[:, i : i + QUERY_BLOCK], score_factor)
        for i in range(0, T, QUERY_BLOCK)
    ]
    return jnp.concatenate(blocks, axis=1)


# What a block computes around its attention op, written once for the dense
# forward below and the paged one (``engine/paged_decode.py``): the two differ
# only in where K/V are written and what attends.
def attention_inputs(
    h: jax.Array, lp: dict[str, jax.Array], cfg: GemmaConfig, positions: jax.Array, kind
) -> tuple:
    """Normed input h [B, T, D] -> q [B, T, H, hd], k and v [B, T, K, hd] as
    the cache holds them: q and k normed per head where the block has a q/k
    norm, then rotated by this layer's rope; and the tokens' index queries
    (``latent_attention_inputs``; None for a block with no index). Latent
    attention: ``latent_attention_inputs``."""
    if cfg.latent:
        return latent_attention_inputs(h, lp, cfg, positions, kind)
    q = jnp.einsum("btd,dkh->btkh", h, lp["wq"])
    k = jnp.einsum("btd,dkh->btkh", h, lp["wk"])
    v = jnp.einsum("btd,dkh->btkh", h, lp["wv"])
    if cfg.qk_norm:
        # norm and rope are one elementwise chain in float32, rounded once
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps, cfg.norm_plus_one, jnp.float32)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps, cfg.norm_plus_one, jnp.float32)
    q = apply_rope(q, positions, cfg.rope_theta, kind).astype(h.dtype)
    k = apply_rope(k, positions, cfg.rope_theta, kind).astype(h.dtype)
    return q, k, v, None


def latent_attention_inputs(
    h: jax.Array, lp: dict[str, jax.Array], cfg: GemmaConfig, positions: jax.Array, kind
) -> tuple:
    """Normed input h [B, T, D] -> q [B, T, H, hd + dr] (a head's unrotated
    values, then its rotated ones), and what the cache holds of a token, ONE
    row for every head: ``k`` [B, T, 1, kv_widths[0]], the shared key after
    the rotation (zeros up to ``index_key_offset``, then the token's index
    key where the block has an index), and ``v`` [B, T, 1, rkv], the latent
    after its norm. The rotated dims pair half-split (value i with value i +
    dr/2), as ``apply_rope`` pairs them. Last, the tokens' side of the index
    (None without one): their index queries [B, T, Hi, di] and per-head
    weights [B, T, Hi] float32, ``w = (h W_w) Hi^-0.5 di^-0.5``; the index
    key is ``LayerNorm(h W_ki)``, and the first ``dr`` values of the key and
    of every index query are rotated as the shared key is."""
    hd, dr, rkv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    f32, dtype = jnp.float32, h.dtype
    # Each product is taken as accumulated (float32), and what follows it
    # elementwise (norm, rotation) runs on that: every tensor below is rounded
    # to the activations' type once, where a matmul or the cache takes it.
    c_q = rms_norm(
        jnp.einsum("btd,dr->btr", h, lp["w_dq"], preferred_element_type=f32),
        lp["q_lora_norm"], cfg.norm_eps, cfg.norm_plus_one, dtype,
    )
    q = jnp.einsum("btr,rhe->bthe", c_q, lp["w_uq"], preferred_element_type=f32)
    q_rope = apply_rope(q[..., hd:], positions, cfg.rope_theta, kind)
    q = jnp.concatenate([q[..., :hd], q_rope], axis=-1).astype(dtype)
    down = jnp.einsum("btd,dr->btr", h, lp["w_dkv"], preferred_element_type=f32)
    latent = rms_norm(
        down[..., :rkv], lp["kv_lora_norm"], cfg.norm_eps, cfg.norm_plus_one, dtype
    )
    k_rope = apply_rope(down[:, :, None, rkv:], positions, cfg.rope_theta, kind).astype(dtype)
    pad = cfg.index_key_offset - dr
    if pad:
        k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, 0), (0, pad)))
    index = None
    if cfg.index_topk:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim

        def rotate_head(x):  # [B, T, heads, di]: the first dr values rotate
            rotated = apply_rope(x[..., :dr], positions, cfg.rope_theta, kind)
            return jnp.concatenate([rotated, x[..., dr:]], axis=-1).astype(dtype)

        q_i = rotate_head(jnp.einsum("btr,rhe->bthe", c_q, lp["w_qi"], preferred_element_type=f32))
        k_i = jnp.einsum("btd,de->bte", h, lp["w_ki"], preferred_element_type=f32)
        mean = jnp.mean(k_i, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k_i - mean), axis=-1, keepdims=True)
        k_i = (k_i - mean) * lax.rsqrt(var + cfg.norm_eps) * lp["ki_norm"].astype(f32)
        k_i = rotate_head((k_i + lp["ki_norm_bias"].astype(f32))[:, :, None, :])
        w_i = jnp.einsum("btd,dh->bth", h, lp["w_wi"], preferred_element_type=f32)
        index = (q_i, w_i * (Hi**-0.5 * di**-0.5))
        k_rope = jnp.concatenate([k_rope, k_i], axis=-1)
    return q, k_rope, latent[:, :, None, :], index


def index_scores(q_i: jax.Array, w_i: jax.Array, k_i: jax.Array) -> jax.Array:
    """The index score of every (query, key) pair, float32 [B, T, S]: ``sum_h
    w[t, h] relu(q_i[t, h] . k_i[s])`` with q_i [B, T, Hi, di], w_i [B, T, Hi]
    float32, k_i [B, S, di]."""
    s = jnp.einsum("bthe,bse->bths", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w_i[..., None], axis=2)


def select_top(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The ``k`` best-scoring of the keys a query may see, as a mask like
    ``visible`` [..., S] (S >= k): exactly ``min(k, visible)`` keys a query,
    ties going to the lower position."""
    s = jnp.where(visible, scores, -jnp.inf)
    kth = lax.top_k(s, k)[0][..., -1:]
    over = s > kth
    tie = s == kth
    need = k - jnp.sum(over, axis=-1, keepdims=True)
    return visible & (over | (tie & (jnp.cumsum(tie, axis=-1) <= need)))


def index_mask(index, k_cache: jax.Array, mask: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """``mask`` [B, T, S] narrowed to each query's ``index_topk`` best keys:
    the dense forward's form of the selection, a block of queries at a time."""
    q_i, w_i = index
    k_i = k_cache[:, :, 0, cfg.index_key_offset :]
    blocks = [
        select_top(
            index_scores(q_i[:, i : i + QUERY_BLOCK], w_i[:, i : i + QUERY_BLOCK], k_i),
            mask[:, i : i + QUERY_BLOCK], cfg.index_topk,
        )
        for i in range(0, mask.shape[1], QUERY_BLOCK)
    ]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def latent_expand(
    k_rope: jax.Array, latent: jax.Array, lp: dict[str, jax.Array], cfg: GemmaConfig
) -> tuple[jax.Array, jax.Array]:
    """The EXPANDED form's keys and values from what the cache holds,
    ``k_rope`` [B, S, 1, kv_widths[0]] and ``latent`` [B, S, 1, rkv]: every
    head's key [B, S, H, hd + dr] (its own unrotated values from the latent,
    then the one rotated key all heads share) and value [B, S, H, dv]."""
    hd, dr = cfg.head_dim, cfg.qk_rope_head_dim
    kv = jnp.einsum("bsr,rhe->bshe", latent[:, :, 0], lp["w_ukv"])
    shared = jnp.broadcast_to(k_rope[..., :dr], kv.shape[:3] + (dr,))
    return jnp.concatenate([kv[..., :hd], shared], axis=-1), kv[..., hd:]


def attention_residual(
    x: jax.Array, h: jax.Array, attn: jax.Array, lp: dict[str, jax.Array], cfg: GemmaConfig
) -> jax.Array:
    """x + the attention branch: ``attn`` [B, T, H * hd] gated by
    ``sigmoid(Wg h)`` where the block has an output gate, through Wo, normed
    where the block norms its branches' outputs. ``lp["wo"]`` is the layer's
    slice of ``layer_stacks``' view, [H * hd, D]: no reshape stands between
    the scan's slice and the dot."""
    F = cfg.attn_out_width
    if cfg.attn_gate:
        gate = jnp.einsum("btd,df->btf", h, lp["w_attn_gate"].reshape(cfg.d_model, F))
        attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)
    out = jnp.einsum(
        "btf,fd->btd", attn, lp["wo"],
        preferred_element_type=jnp.float32 if cfg.branches_float32 else None,
    )
    if cfg.post_norms:
        return _add_normed(x, out, lp["post_attn_norm"], cfg)
    return _join(x, out)


def _join(x: jax.Array, branch: jax.Array) -> jax.Array:
    """x + branch in the branch's type (x's own, or float32 as accumulated:
    ``GemmaConfig.branches_float32``), rounded to x's type once."""
    return (x.astype(branch.dtype) + branch).astype(x.dtype)


def _add_normed(x: jax.Array, branch32: jax.Array, gain: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """x + RMSNorm(branch) g, the branch's float32 output normed and added in
    float32: the sum is rounded to x's type once."""
    normed = rms_norm(branch32, gain, cfg.norm_eps, cfg.norm_plus_one)
    return (x.astype(jnp.float32) + normed).astype(x.dtype)


def gated_mlp(h: jax.Array, w_gate, w_up, w_down, cfg: GemmaConfig, out_dtype=None) -> jax.Array:
    """The dense feed-forward: a layer's own, or a sparse layer's shared expert."""
    ff = activation(cfg, jnp.einsum("btd,df->btf", h, w_gate)) * jnp.einsum("btd,df->btf", h, w_up)
    return jnp.einsum("btf,fd->btd", ff, w_down, preferred_element_type=out_dtype)


def feed_forward_residual(
    x: jax.Array, lp: dict[str, jax.Array], cfg: GemmaConfig, moe: "tuple | None" = None,
    *, use_pallas: bool = False, interpret: bool = False, prefill: bool = False,
) -> tuple:
    """x + the feed-forward branch, by the layer's KIND, which its leaves
    say: with a ``router`` the routed experts held here (``moe``: expert
    stacks, SPARSE layer index, live [B, T]; ``use_pallas`` / ``interpret`` /
    ``prefill``: ``moe_forward``'s) plus the shared expert, else the dense MLP. -> (x, the
    layer's expert counters, the experts chosen), the last two None for a
    dense layer."""
    h = rms_norm(x, lp["pre_mlp_norm"], cfg.norm_eps, cfg.norm_plus_one)
    stats = chosen = None
    # A branch that is normed before it joins stays float32 until it has.
    branch_dtype = jnp.float32 if cfg.branches_float32 else h.dtype
    if "router" in lp:
        experts, layer, live = moe
        ff, stats, chosen = moe_forward(
            h, lp["router"], experts, layer, cfg, live, lp.get("router_bias"),
            use_pallas=use_pallas, interpret=interpret, prefill=prefill,
        )
        ff = ff.astype(branch_dtype)
        if cfg.d_shared_expert:
            shared = (lp["shared_gate"], lp["shared_up"], lp["shared_down"])
            ff = ff + gated_mlp(h, *shared, cfg, out_dtype=branch_dtype)
    else:
        ff = gated_mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"], cfg, out_dtype=branch_dtype)
    if cfg.post_norms:
        return _add_normed(x, ff, lp["post_mlp_norm"], cfg), stats, chosen
    return _join(x, ff), stats, chosen


def layer_stacks(cfg: GemmaConfig, params: Params) -> tuple[list, dict]:
    """The layer scan's runs in layer order, ``[(scanned leaves, first
    layer, one past the last)]``, and the expert stacks the scan closes
    over. One run, but for a sparse model with leading dense layers: those
    are a stack of their own (``params["dense_layers"]``), so the same body
    runs over two stacks with its carry handed across.

    A run's ``wo`` is the stored ``[n, H, dv, D]`` leaf VIEWED as
    ``[n, H * dv, D]`` (``_merge_heads``): the reshape is of the whole
    stack, here, outside the scan, so the body's ``btf,fd->btd`` takes its
    layer's slice as it is and the dot reads it out of the stack. Reshaped
    inside the body, the slice is materialised before the dot: on the core
    where it fits, and at 128 heads x 128 x 7,168 (235 MB) as an HBM -> HBM
    copy that the dot then reads again (PERF.md, PR 46)."""
    scanned, experts = split_layers(cfg, params["layers"])
    Ld, L = cfg.n_dense_layers, cfg.n_layers
    if not Ld:
        return [(_merge_heads(scanned), 0, L)], experts
    return [(_merge_heads(params["dense_layers"]), 0, Ld), (_merge_heads(scanned), Ld, L)], experts


def _merge_heads(stack: dict) -> dict:
    """``stack`` with ``wo`` [n, H, dv, D] as [n, H * dv, D], H-major (a
    head-sharded leaf stays sharded on the merged axis). An int8 leaf
    (``quant.py``) is a dict of two arrays: its scale [n, 1, 1, D] goes to
    [n, 1, D] with it."""
    wo = jax.tree.map(lambda a: a.reshape(a.shape[0], -1, a.shape[-1]), stack["wo"])
    return {**stack, "wo": wo}


def sparse_index(cfg: GemmaConfig, layer: jax.Array) -> jax.Array:
    """A layer's row in the sparse layers' stacks (what a leading dense
    layer gets names no row, and no dense layer reads it)."""
    return layer - cfg.n_dense_layers if cfg.n_dense_layers else layer


def _layer(
    x: jax.Array,
    lp: dict[str, jax.Array],
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
    mask: jax.Array,
    write_idx: jax.Array,
    cfg: GemmaConfig,
    kind: "dict[str, jax.Array] | None" = None,
    moe: "tuple | None" = None,
    *, use_pallas: bool = False, interpret: bool = False,
) -> tuple:
    """One transformer block over [B, T]; writes K/V at ``write_idx``.

    x: [B, T, D]; k_cache/v_cache: [B, S, K, hd]; positions: [B, T];
    mask: [B, T, S]; write_idx: [B, T] absolute cache slots for this chunk.
    ``kind``: this layer's slice of ``layer_kinds``. ``moe``, ``use_pallas``,
    ``interpret``: see ``feed_forward_residual``, which is told that the
    window is the dense forward's. -> (x, k_cache, v_cache, the layer's expert
    counters, the experts chosen), the last two None for a dense layer.
    """
    B, T, D = x.shape
    h = rms_norm(x, lp["pre_attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
    q, k, v, index = attention_inputs(h, lp, cfg, positions, kind)

    b_idx = jnp.arange(B)[:, None]  # [B, 1] broadcast with write_idx [B, T]
    k_cache = k_cache.at[b_idx, write_idx].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[b_idx, write_idx].set(v.astype(v_cache.dtype))

    if kind and "window" in kind:
        # A sliding layer's query sees itself and the window - 1 keys before.
        s_idx = jnp.arange(mask.shape[-1])
        mask = mask & (s_idx[None, None, :] > positions[:, :, None] - kind["window"])
    if index is not None and mask.shape[-1] > cfg.index_topk:
        # Only a cache longer than the selection can hold a key it drops.
        mask = index_mask(index, k_cache, mask, cfg)
    if cfg.latent:
        # The expanded form: every head's keys and values rebuilt from the
        # cached latents, one head a "KV head" (no sharing to group).
        keys, values = latent_expand(k_cache, v_cache, lp, cfg)
        attn = _attend_query_blocks(q[:, :, :, None, :], keys, values, mask, cfg.attn_score_factor)
    else:
        qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
        attn = _attend(qg, k_cache, v_cache, mask)
    x = attention_residual(x, h, attn.reshape(B, T, cfg.attn_out_width), lp, cfg)
    x, stats, chosen = feed_forward_residual(
        x, lp, cfg, moe, use_pallas=use_pallas, interpret=interpret, prefill=True
    )
    return x, k_cache, v_cache, stats, chosen


# ------------------------------------------- a layer that is one thing alone
def pattern_rows(cfg: GemmaConfig) -> list[tuple[str, int]]:
    """``layer_pattern`` as (kind, the layer's row in its kind's stack)."""
    seen = dict.fromkeys("ME*LSCAJQ", 0)
    rows = []
    for kind in cfg.layer_pattern:
        rows.append((kind, seen[kind]))
        seen[kind] += 1
    return rows


def stack_row(stack: dict, j: int) -> dict:
    """Row ``j`` of every leaf of a stack (a static slice: the walk over a
    ``layer_pattern`` is unrolled)."""
    return {k: v[j] for k, v in stack.items()}


def hybrid_attention_inputs(n: jax.Array, lp: dict, cfg: GemmaConfig) -> tuple:
    """n [B, T, D] -> q [B, T, H, hd], k and v [B, T, K, hd]: no rotation
    (position reaches this model through its recurrent layers)."""
    heads = lambda a, n_heads: a.reshape(a.shape[:2] + (n_heads, cfg.head_dim))
    q = heads(jnp.einsum("btd,de->bte", n, lp["wq"]), cfg.n_heads)
    k = heads(jnp.einsum("btd,de->bte", n, lp["wk"]), cfg.n_kv_heads)
    v = heads(jnp.einsum("btd,de->bte", n, lp["wv"]), cfg.n_kv_heads)
    return q, k, v


def hybrid_feed_forward(
    x: jax.Array, layers: dict, j: int, cfg: GemmaConfig, live, *,
    use_pallas: bool = False, interpret: bool = False, prefill: bool = False,
) -> tuple:
    """An ``E`` layer, ``x + f(norm(x))``: the routed experts held here,
    which read and write the latent (``latent_down`` before them, ONE
    ``latent_up`` after their weighted sum) where the model has one, beside
    the shared expert on the full width. ``layers`` is the whole stack, ``j``
    the layer's row. -> (x, the layer's expert counters, the experts
    chosen)."""
    scanned, experts = split_layers(cfg, layers)
    lp = stack_row(scanned, j)
    n = rms_norm(x, lp["norm"], cfg.norm_eps, cfg.norm_plus_one)
    rows = None
    if cfg.moe_latent_size:
        rows = jnp.einsum("btd,dl->btl", n, lp["latent_down"])
    routed, stats, chosen = moe_forward(
        n, lp["router"], experts, j, cfg, live, lp.get("router_bias"),
        use_pallas=use_pallas, interpret=interpret, rows=rows, prefill=prefill,
    )
    if cfg.moe_latent_size:
        routed = jnp.einsum(
            "btl,ld->btd", routed.astype(n.dtype), lp["latent_up"], preferred_element_type=jnp.float32
        )
    shared = activation(cfg, jnp.einsum("btd,df->btf", n, lp["shared_up"]))
    shared = jnp.einsum("btf,fd->btd", shared, lp["shared_down"], preferred_element_type=jnp.float32)
    return _join(x, routed + shared), stats, chosen


def join_scaled(x: jax.Array, branch32: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """x + ``residual_scale`` times the branch, in float32: an ``L`` / ``S``
    pattern carries its residual stream in float32 (``mixer_stream``) and
    rounds to the activations' type where a matmul reads it. The embedding's
    scale makes the stream large beside what a scaled-down branch adds, so a
    stream rounded to bfloat16 at every join loses the branches' low bits: on
    the CPU at 1,024 wide the step's distance from the reference is 0.0134
    with the stream in bfloat16 and 0.0011 in float32 (PERF.md, PR 51)."""
    return x.astype(jnp.float32) + cfg.residual_scale * branch32.astype(jnp.float32)


def mixer_stream(params: Params, cfg: GemmaConfig, tokens: jax.Array) -> jax.Array:
    """The embedded tokens as the float32 residual stream of an ``L`` / ``S``
    pattern."""
    return embed_tokens(params, cfg, tokens).astype(jnp.float32)


def mixer_norm(x: jax.Array, gain: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """RMSNorm of the float32 stream, rounded once to the activations' type:
    what a layer's matmuls read."""
    return rms_norm(x, gain, cfg.norm_eps, cfg.norm_plus_one, jnp.dtype(cfg.dtype))


def gated_attention_out(attn: jax.Array, n: jax.Array, lp: dict, cfg: GemmaConfig) -> jax.Array:
    """An ``S`` layer's attention [B, T, H * hd] -> its branch [B, T, D]
    float32: the output gate out of the layer's normed input, then W_o."""
    f32 = jnp.float32
    if cfg.attn_gate:
        gate = jnp.einsum("btd,de->bte", n, lp["w_attn_gate"], preferred_element_type=f32)
        attn = (attn.astype(f32) * jax.nn.sigmoid(gate)).astype(n.dtype)
    return jnp.einsum("bte,ed->btd", attn, lp["wo"], preferred_element_type=f32)


def mixer_feed_forward(x: jax.Array, lp: dict, cfg: GemmaConfig) -> jax.Array:
    """The second half of an ``L`` / ``S`` layer, and of a ``J`` / ``Q`` one
    (whose ``residual_scale`` is 1): x + scale x MLP(norm(x))."""
    return join_scaled(x, gated_mlp_float32(mixer_norm(x, lp["mlp_norm"], cfg), lp, cfg), cfg)


def gated_mlp_float32(n: jax.Array, lp: dict, cfg: GemmaConfig) -> jax.Array:
    """The dense gated feed-forward of a float32 residual stream, on its normed
    input ``n`` -> [B, T, D] float32: gate and up as accumulated, their product
    rounded ONCE where W_down reads it."""
    f32 = jnp.float32
    gate = jnp.einsum("btd,df->btf", n, lp["w_gate"], preferred_element_type=f32)
    up = jnp.einsum("btd,df->btf", n, lp["w_up"], preferred_element_type=f32)
    return jnp.einsum(
        "btf,fd->btd", (activation(cfg, gate) * up).astype(n.dtype), lp["w_down"], preferred_element_type=f32
    )


def _block_attention_dense(q, k_c, v_c, mask, positions, cfg: GemmaConfig) -> jax.Array:
    """An ``S`` layer's attention over the dense cache: q [B, T, H, hd], the
    caches [B, S, K, hd], ``mask`` [B, T, S] -> [B, T, H * hd]. Plain causal
    attention where the cache holds no block a query could drop; else each KV
    head's queries read their own selection (``sparse.py``, the masked form)."""
    from mcpx.models.gemma import sparse

    B, T = q.shape[:2]
    S = k_c.shape[1]
    qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
    if not sparse.selects(cfg, S):
        return _attend(qg, k_c, v_c, mask).reshape(B, T, -1)
    p = cfg.pool_stride
    whole = S // p * p
    kc = sparse.pooled_keys(sparse.page_sums(k_c[:, :whole], p), p)
    chosen = sparse.token_mask(sparse.selected_blocks(qg, kc, positions, cfg), cfg.block_size, S)
    chosen = jnp.pad(chosen, ((0, 0),) * 3 + ((0, S - chosen.shape[-1]),))
    heads = [
        _attend_query_blocks(
            qg[:, :, g : g + 1], k_c[:, :, g : g + 1], v_c[:, :, g : g + 1],
            mask & chosen[:, :, g], 1.0,
        )
        for g in range(cfg.n_kv_heads)
    ]
    return jnp.concatenate(heads, axis=2).reshape(B, T, -1)


def _mixer_ffn_forward(
    params: Params, cfg: GemmaConfig, tokens, seq_lens, kv_cache, mask, logits_at
) -> tuple:
    """``forward`` for an ``L`` / ``S`` pattern from an EMPTY state (the dense
    prefill): the cache it returns holds ``k`` and ``v`` [S layers, ...] and
    ``ssm``: the state AT each row's length, a linear layer."""
    from mcpx.models.gemma.ssm import linear_prefill

    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = mixer_stream(params, cfg, tokens)
    ks, vs, finals = [], [], []
    for kind, j in pattern_rows(cfg):
        lp = stack_row(params["linear_layers" if kind == "L" else "block_layers"], j)
        n = mixer_norm(x, lp["norm"], cfg)
        if kind == "L":
            out, final = linear_prefill(n, lp, cfg, seq_lens)
            finals.append(final)
        else:
            q, k, v = hybrid_attention_inputs(n, lp, cfg)
            k_c = kv_cache["k"][j].at[:, :T].set(k.astype(kv_cache["k"].dtype))
            v_c = kv_cache["v"][j].at[:, :T].set(v.astype(kv_cache["v"].dtype))
            attn = _block_attention_dense(q, k_c, v_c, mask, positions, cfg)
            out = gated_attention_out(attn, n, lp, cfg)
            ks.append(k_c)
            vs.append(v_c)
        x = mixer_feed_forward(join_scaled(x, out, cfg), lp, cfg)
    x = mixer_norm(x, params["final_norm"], cfg)
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    return output_logits(params, cfg, x), {"k": jnp.stack(ks), "v": jnp.stack(vs), "ssm": finals}


def _hybrid_forward(
    params: Params, cfg: GemmaConfig, tokens, seq_lens, kv_cache, mask, logits_at, live,
    routing: bool, moe_stats: bool, use_pallas: bool = False, interpret: bool = False,
) -> tuple:
    """``forward`` for a ``layer_pattern`` model, from an EMPTY state (the
    dense prefill; a dense ``decode_step`` has no state to continue from):
    the cache it returns holds, beside ``k`` and ``v`` [attention layers, ...],
    ``ssm``: ``(state, tail)`` AT each row's length, a Mamba layer."""
    from mcpx.models.gemma.ssm import mamba_prefill

    B, T = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    stats = moe_stats_init(cfg) if cfg.n_experts else None
    ks, vs, finals, chosen_all = [], [], [], []
    for kind, j in pattern_rows(cfg):
        if kind == "M":
            lp = stack_row(params["mamba_layers"], j)
            n = rms_norm(x, lp["norm"], cfg.norm_eps, cfg.norm_plus_one)
            out, final = mamba_prefill(n, lp, cfg, seq_lens)
            x = _join(x, out)
            finals.append(final)
        elif kind == "E":
            x, layer_stats, chosen = hybrid_feed_forward(
                x, params["layers"], j, cfg, live, use_pallas=use_pallas, interpret=interpret, prefill=True
            )
            stats = add_layer_stats(stats, layer_stats)
            chosen_all.append(chosen)
        else:
            lp = stack_row(params["attn_layers"], j)
            n = rms_norm(x, lp["norm"], cfg.norm_eps, cfg.norm_plus_one)
            q, k, v = hybrid_attention_inputs(n, lp, cfg)
            k_c = kv_cache["k"][j].at[:, :T].set(k.astype(kv_cache["k"].dtype))
            v_c = kv_cache["v"][j].at[:, :T].set(v.astype(kv_cache["v"].dtype))
            qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            attn = _attend(qg, k_c, v_c, mask).reshape(B, T, cfg.attn_out_width)
            x = _join(x, jnp.einsum("btf,fd->btd", attn, lp["wo"]))
            ks.append(k_c)
            vs.append(v_c)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    out = output_logits(params, cfg, x), {"k": jnp.stack(ks), "v": jnp.stack(vs), "ssm": finals}
    if stats is not None:
        stats = add_forward_stats(cfg, stats, seq_lens, seq_lens)
    chosen = jnp.stack(chosen_all) if chosen_all else None
    return out + ((stats,) if moe_stats else ()) + ((chosen,) if routing else ())


# ------------------------ a short convolution or attention, then a feed-forward
def conv_attention_inputs(n: jax.Array, lp: dict, cfg: GemmaConfig, positions: jax.Array) -> tuple:
    """An ``A`` layer: n [B, T, D], positions [B, T] -> q [B, T, H, hd], k and
    v [B, T, K, hd] as the pages hold them: q and k normed per head (one gain
    of ``head_dim`` each), THEN rotated; norm and rope one float32 chain on
    the products as accumulated, rounded once."""
    f32 = jnp.float32
    heads = lambda w, n_heads: jnp.einsum("btd,de->bte", n, w, preferred_element_type=f32).reshape(
        n.shape[:2] + (n_heads, cfg.head_dim)
    )
    q = rms_norm(heads(lp["wq"], cfg.n_heads), lp["q_norm"], cfg.norm_eps, False, f32)
    k = rms_norm(heads(lp["wk"], cfg.n_kv_heads), lp["k_norm"], cfg.norm_eps, False, f32)
    q = apply_rope(q, positions, cfg.rope_theta).astype(n.dtype)
    k = apply_rope(k, positions, cfg.rope_theta).astype(n.dtype)
    return q, k, heads(lp["wv"], cfg.n_kv_heads).astype(n.dtype)


def pack_kv(a: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """k or v [B, T, K, hd] as a cache row holds it, ``kv_pack`` neighbouring
    KV heads to a row: [B, T, K / pack, pack x hd] (a reshape)."""
    return a.reshape(a.shape[:2] + (cfg.kv_pool_heads, -1))


def conv_norm(x: jax.Array, gain: jax.Array, cfg: GemmaConfig, kind: str = "") -> jax.Array:
    """RMSNorm (a plain gain) of a ``C`` / ``A`` pattern's float32 residual
    stream, rounded once to the activations' type: what a layer's matmuls
    read. For a ``C`` layer (``kind``) float32 as it is: the short convolution
    reads it unrounded (``models/gemma/ssm.py`` says why). The stream itself
    stays float32 from the embedding to the last norm (``mixer_stream``; every
    branch joins it as accumulated): rounded to bfloat16 at each of a
    ten-layer stack's twenty joins, the step's distance from the reference is
    0.035 on the CPU at 256 wide against the 0.02 of ``reference.tol``
    (PERF.md, PR 56)."""
    return rms_norm(x, gain, cfg.norm_eps, False, jnp.float32 if kind == "C" else jnp.dtype(cfg.dtype))


def conv_feed_forward(
    x: jax.Array, params: Params, layer: int, cfg: GemmaConfig, live, *,
    use_pallas: bool = False, interpret: bool = False, prefill: bool = False,
) -> tuple:
    """The second half of layer ``layer`` of a ``C`` / ``A`` pattern, on the
    float32 stream: the dense feed-forward in the leading layers, the routed
    experts after them (``moe_forward``, the default block's). -> (x, the
    layer's expert counters, the experts chosen), the last two None for a
    dense layer."""
    Ld = cfg.n_layers - cfg.n_sparse_layers
    if layer < Ld:
        lp = stack_row(params["dense_layers"], layer)
        return x + gated_mlp_float32(conv_norm(x, lp["pre_mlp_norm"], cfg), lp, cfg), None, None
    scanned, experts = split_layers(cfg, params["layers"])
    j = layer - Ld
    lp = stack_row(scanned, j)
    ff, stats, chosen = moe_forward(
        conv_norm(x, lp["pre_mlp_norm"], cfg), lp["router"], experts, j, cfg, live,
        lp.get("router_bias"), use_pallas=use_pallas, interpret=interpret, prefill=prefill,
    )
    return x + ff.astype(jnp.float32), stats, chosen


def _conv_forward(
    params: Params, cfg: GemmaConfig, tokens, seq_lens, kv_cache, mask, logits_at, live,
    routing: bool, moe_stats: bool, use_pallas: bool = False, interpret: bool = False,
) -> tuple:
    """``forward`` for a ``C`` / ``A`` pattern from an EMPTY state (the dense
    prefill): the cache it returns holds ``k`` and ``v`` [A layers, ...]
    (``kv_pack`` heads a row) and ``ssm``: ``(the tail AT each row's length,
    every position's u)`` a ``C`` layer."""
    from mcpx.models.gemma.ssm import conv_prefill

    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = mixer_stream(params, cfg, tokens)
    stats = moe_stats_init(cfg) if cfg.n_experts else None
    ks, vs, finals, chosen_all = [], [], [], []
    for layer, (kind, j) in enumerate(pattern_rows(cfg)):
        lp = stack_row(params["conv_layers" if kind == "C" else "attn_layers"], j)
        n = conv_norm(x, lp["norm"], cfg, kind)
        if kind == "C":
            out, final = conv_prefill(n, lp, cfg, seq_lens)
            finals.append(final)
        else:
            q, k, v = conv_attention_inputs(n, lp, cfg, positions)
            S = kv_cache["k"].shape[2]
            pad = lambda a: jnp.pad(a, ((0, 0), (0, S - T), (0, 0), (0, 0)))
            qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            attn = _attend(qg, pad(k), pad(v), mask).reshape(B, T, cfg.attn_out_width)
            out = jnp.einsum("btf,fd->btd", attn, lp["wo"], preferred_element_type=jnp.float32)
            ks.append(kv_cache["k"][j].at[:, :T].set(pack_kv(k, cfg).astype(kv_cache["k"].dtype)))
            vs.append(kv_cache["v"][j].at[:, :T].set(pack_kv(v, cfg).astype(kv_cache["v"].dtype)))
        x, layer_stats, chosen = conv_feed_forward(
            x + out, params, layer, cfg, live,
            use_pallas=use_pallas, interpret=interpret, prefill=True,
        )
        if layer_stats is not None:
            stats = add_layer_stats(stats, layer_stats)
            chosen_all.append(chosen)
    x = conv_norm(x, params["final_norm"], cfg)
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    out = output_logits(params, cfg, x), {"k": jnp.stack(ks), "v": jnp.stack(vs), "ssm": finals}
    if stats is not None:
        stats = add_forward_stats(cfg, stats, seq_lens, seq_lens)
    chosen = jnp.stack(chosen_all) if chosen_all else None
    return out + ((stats,) if moe_stats else ()) + ((chosen,) if routing else ())


# ------------- a selective scan or attention, then the dense feed-forward:
# ------------- the walk SCANNED over each run of like layers
def pattern_runs(cfg: GemmaConfig) -> list[tuple[str, int, int]]:
    """``layer_pattern`` as its runs of like layers, ``(kind, the run's first
    row in its kind's stack, one past its last)``: ``JJQJ`` is ``[("J", 0, 2),
    ("Q", 0, 1), ("J", 2, 3)]``."""
    runs: list[tuple[str, int, int]] = []
    for kind, j in pattern_rows(cfg):
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], j + 1)
        else:
            runs.append((kind, j, j + 1))
    return runs


def walk_runs(cfg: GemmaConfig, bodies: dict, carry):
    """The walk over a ``J`` / ``Q`` pattern: each run of like layers is ONE
    ``lax.scan`` of its kind's body over the run's rows, so a body is traced
    and compiled once a run, not once a layer. ``bodies[kind](carry, row) ->
    (carry, what the layer hands out)`` takes the layer's row in its kind's
    stack as a TRACED number: it indexes the whole stack (and the state
    pool's stacked arrays) by it, as a scan over the stack itself would, with
    no slice of the stack ever made. -> (carry, {kind: [what each run handed
    out, stacked over its rows]})."""
    out: dict[str, list] = {kind: [] for kind in bodies}
    for kind, lo, hi in pattern_runs(cfg):
        carry, ys = lax.scan(bodies[kind], carry, jnp.arange(lo, hi, dtype=jnp.int32))
        out[kind].append(ys)
    return carry, out


def scan_norm(x: jax.Array, gain: jax.Array, cfg: GemmaConfig) -> jax.Array:
    """RMSNorm (a plain gain) of a ``J`` / ``Q`` pattern's float32 residual
    stream as a ``J`` layer's mixer reads it: float32 as it is, unrounded
    (``models/gemma/ssm.py``, "the selective scan", says why). Every other
    reader of the stream (a ``Q`` layer, the feed-forward, the head) takes
    ``mixer_norm``'s, rounded once to the activations' type."""
    return rms_norm(x, gain, cfg.norm_eps, False, jnp.float32)


def stack_at(stack: dict, j: jax.Array) -> dict:
    """Row ``j`` (traced) of every leaf of a stack."""
    return {k: lax.dynamic_index_in_dim(v, j, keepdims=False) for k, v in stack.items()}


def _scan_forward(
    params: Params, cfg: GemmaConfig, tokens, seq_lens, kv_cache, mask, logits_at,
    use_pallas: bool = False, interpret: bool = False,
) -> tuple:
    """``forward`` for a ``J`` / ``Q`` pattern from an EMPTY state (the dense
    prefill): the cache it returns holds ``k`` and ``v`` [Q layers, ...] and
    ``ssm``: ``(the states [J layers, B, N, I], the tails [J layers, B, K - 1,
    I])`` AT each row's length. ``use_pallas``: the recurrence through
    ``kernels/selective_scan.selective_scan_prefill``."""
    from mcpx.models.gemma.ssm import selective_prefill

    B, T = tokens.shape
    kernel = None
    if use_pallas:
        from mcpx.engine.kernels.selective_scan import selective_scan_prefill

        kernel = functools.partial(selective_scan_prefill, interpret=interpret)

    def scan_layer(x, j):
        lp = stack_at(params["scan_layers"], j)
        out, final = selective_prefill(scan_norm(x, lp["norm"], cfg), lp, cfg, seq_lens, kernel=kernel)
        return mixer_feed_forward(x + out, lp, cfg), final

    def attn_layer(x, j):
        lp = stack_at(params["attn_layers"], j)
        n = mixer_norm(x, lp["norm"], cfg)  # (the pages hold keys of the activations' type)
        q, k, v = hybrid_attention_inputs(n, lp, cfg)
        k_c = lax.dynamic_index_in_dim(kv_cache["k"], j, keepdims=False).at[:, :T].set(k.astype(kv_cache["k"].dtype))
        v_c = lax.dynamic_index_in_dim(kv_cache["v"], j, keepdims=False).at[:, :T].set(v.astype(kv_cache["v"].dtype))
        qg = q.reshape(B, T, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
        attn = _attend_query_blocks(qg, k_c, v_c, mask, 1.0).reshape(B, T, cfg.attn_out_width)
        out = jnp.einsum("btf,fd->btd", attn, lp["wo"], preferred_element_type=jnp.float32)
        return mixer_feed_forward(x + out, lp, cfg), (k_c, v_c)

    x, ys = walk_runs(cfg, {"J": scan_layer, "Q": attn_layer}, mixer_stream(params, cfg, tokens))
    x = mixer_norm(x, params["final_norm"], cfg)
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    join = lambda runs, i: jnp.concatenate([r[i] for r in runs])
    cache = {"k": kv_cache["k"], "v": kv_cache["v"], "ssm": None}
    if ys["Q"]:
        cache["k"], cache["v"] = join(ys["Q"], 0), join(ys["Q"], 1)
    if ys["J"]:
        cache["ssm"] = (join(ys["J"], 0), join(ys["J"], 1))
    return output_logits(params, cfg, x), cache


def embed_tokens(params: Params, cfg: GemmaConfig, tokens: jax.Array) -> jax.Array:
    from mcpx.models.gemma.quant import embed_lookup

    x = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    return x


def output_logits(
    params: Params, cfg: GemmaConfig, x: jax.Array, subset: "jax.Array | None" = None
) -> jax.Array:
    """Float32 logits of final-normed hidden states: against the embedding
    matrix where it is tied, else against the ``head`` leaf [D, V].
    ``subset`` [C] restricts to those vocabulary entries."""
    from mcpx.models.gemma.quant import unembed

    if cfg.logit_divisor != 1.0:
        x = (x.astype(jnp.float32) / cfg.logit_divisor).astype(x.dtype)
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
    logits_at: "jax.Array | None" = None,
    live: "jax.Array | None" = None,
    routing: bool = False,
    moe_stats: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple:
    """Core forward over a [B, T] token chunk against a [L, B, S, K, hd]
    cache. ``positions`` are absolute (double as cache write slots);
    ``mask`` is [B, T, S] (True = attend).
    ``logits_at`` [B]: unembed only that position per row -> [B, V].
    ``live`` [B, T]: the slots that are tokens and not padding; a sparse
    feed-forward routes the others nowhere. ``routing``: also return the
    experts chosen in the sparse layers, [Ls, B, T, k]. ``moe_stats``: also
    the forward's expert counters (``moe_stats_init``), before ``routing``'s.
    ``use_pallas`` / ``interpret``: the sparse layers' experts through their
    kernel calls (``moe_forward``'s; ONE device holds the rows and the
    stacks). Nothing else of this forward has a kernel."""
    from mcpx.models.gemma.quant import dequant_layer

    if cfg.hybrid:
        if live is None:
            raise ValueError("a layer_pattern model's dense forward is its prefill (prefill())")
        seq_lens = jnp.sum(live, axis=1).astype(jnp.int32)
        if cfg.dense_pattern:
            if cfg.scan_ffn:
                out = _scan_forward(
                    params, cfg, tokens, seq_lens, kv_cache, mask, logits_at, use_pallas, interpret
                )
            else:
                out = _mixer_ffn_forward(params, cfg, tokens, seq_lens, kv_cache, mask, logits_at)
            stats = None
            if moe_stats:
                stats = add_forward_stats(cfg, moe_stats_init(cfg), seq_lens, seq_lens)
            return out + ((stats,) if moe_stats else ()) + ((None,) if routing else ())
        return (_conv_forward if cfg.conv_ffn else _hybrid_forward)(
            params, cfg, tokens, seq_lens, kv_cache, mask, logits_at, live, routing, moe_stats,
            use_pallas, interpret,
        )
    # Weight-only int8 serving mode (quant.py): identity plumbing on plain
    # params. The quantized leaves stay the HBM-resident buffers — embed
    # rows gather as int8 + per-row scales, and the layer stack dequantizes
    # PER LAYER inside the scan body (see dequant_layer's docstring for why
    # position matters).
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params, cfg, tokens)
    stacks, experts = layer_stacks(cfg, params)

    def body(carry, scanned):
        lp, kind, k_c, v_c = scanned
        lp = dequant_layer(lp, dtype)
        # A sparse model's carry counts the layers (the expert stacks are
        # sliced by it) and sums the expert counters. A dense layer's
        # ``layer_stats`` and ``chosen`` are None.
        x, layer, stats = carry if cfg.n_experts else (carry, None, None)
        moe = (experts, sparse_index(cfg, layer), live) if cfg.n_experts else None
        x, k_c, v_c, layer_stats, chosen = _layer(
            x, lp, k_c, v_c, positions, mask, positions, cfg, kind, moe,
            use_pallas=use_pallas, interpret=interpret,
        )
        if layer_stats is not None:
            stats = add_layer_stats(stats, layer_stats)
        return ((x, layer + 1, stats) if cfg.n_experts else x), (k_c, v_c, chosen)

    carry = (x, jnp.asarray(0, jnp.int32), moe_stats_init(cfg)) if cfg.n_experts else x
    runs = []  # one scan a stack: (k [n, ...], v [n, ...], chosen or None)
    for scanned, lo, hi in stacks:
        k_rows, v_rows = kv_cache["k"], kv_cache["v"]
        if len(stacks) > 1:
            k_rows, v_rows = k_rows[lo:hi], v_rows[lo:hi]
        carry, ys = lax.scan(body, carry, (scanned, layer_kinds(cfg, lo, hi), k_rows, v_rows))
        runs.append(ys)
    x = carry[0] if cfg.n_experts else carry
    if len(runs) == 1:
        k_new, v_new, chosen = runs[0]
    else:
        k_new, v_new = (jnp.concatenate([ys[i] for ys in runs]) for i in (0, 1))
        chosen = runs[-1][2]  # [Ls, B, T, k]: the sparse run's
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
    stats = carry[2] if cfg.n_experts else None  # as decode_chunk_paged: None from a dense model
    if stats is not None:
        n_live = jnp.sum(live, axis=1) if live is not None else jnp.full(tokens.shape[:1], tokens.shape[1])
        stats = add_forward_stats(cfg, stats, positions[:, 0] + n_live, n_live)
    return out + ((stats,) if moe_stats else ()) + ((chosen,) if routing else ())


# -------------------------------------------------------------- entrypoints
def prefill(
    params: Params,
    cfg: GemmaConfig,
    tokens: jax.Array,
    seq_lens: jax.Array,
    kv_cache: KVCache,
    last_only: bool = False,
    routing: bool = False,
    moe_stats: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple:
    """Prefill a padded [B, T] batch. ``seq_lens`` [B] masks right-padding.

    Returns logits [B, T, V] and the filled cache — or [B, V] (each row's
    last valid position only) with ``last_only``, the serving path's shape;
    with ``moe_stats`` also the forward's expert counters, with ``routing``
    also the experts each slot chose, [Ls, B, T, k]. ``use_pallas`` /
    ``interpret``: ``forward``'s.
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
        moe_stats=moe_stats,
        use_pallas=use_pallas,
        interpret=interpret,
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
