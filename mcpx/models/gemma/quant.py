"""Weight-only int8 quantization for serving — TPU-first rationale.

Decode on TPU is weight-load-bound: every forward streams the full
parameter set from HBM while the MXU sits mostly idle, so halving the
bytes-at-rest halves the decode bandwidth bill AND the HBM footprint —
int8 weights put the Gemma-7B geometry (~17 GB bf16) on a single 16 GB
v5e chip (~8.6 GB + scales). The reference has no model code at all (its
LLM is a remote API call, reference ``control_plane.py:69-73``); this is
a serving-framework component built for the in-tree backend.

Scheme: symmetric absmax per OUTPUT channel of each matmul (the scale
axis is every non-contracted dimension of the weight's serving einsum),
weights stored int8 + float32 scale. Dequantization happens INSIDE the
jitted forward (``maybe_dequant`` at the two param choke points:
``model.forward`` and ``engine.paged_decode.decode_chunk_paged``), so the
int8 buffers are what lives in HBM and XLA fuses ``int8 -> scale *
bfloat16`` into the consuming matmuls where profitable. Exactness is NOT
claimed: this is an opt-in serving mode (``model.quantize="int8"``),
default off, with numerics pinned by tests to stay close to bf16.

Representation: each quantized leaf becomes ``{"int8": i8, "scale": f32}``
— a plain dict, so the params object remains an ordinary pytree
(device_put/donation/sharding all work unchanged; scales reduce over the
contraction axes only, so a ``model``-axis-sharded weight keeps a
consistently sharded scale under GSPMD).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# Contraction axes of each weight's serving einsum (model.py/_layer):
# scales broadcast over these, per-channel over the rest.
_CONTRACT_AXES: dict[str, tuple[int, ...]] = {
    "embed": (1,),        # [V, D]: unembed contracts D; lookup scales per row V
    "wq": (1,),           # [L, D, K, hd]: contracts D
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),         # [L, H, hd, D]: contracts H*hd (scanned as [L, H*hd, D], the
                          # scale [L, 1, 1, D] as [L, 1, D]: model.layer_stacks)
    "w_gate": (1,),       # [L, D, F]: contracts D
    "w_up": (1,),
    "w_down": (1,),       # [L, F, D]: contracts F
}


def _quantize_leaf(w: jax.Array, axes: tuple[int, ...]) -> dict[str, jax.Array]:
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"int8": q, "scale": scale.astype(jnp.float32)}


def quantize_params(params: Params) -> Params:
    """bf16/f32 params pytree -> int8-weight pytree (norms stay as-is:
    they are O(D) and their +1-residual convention is precision-relevant)."""
    out: Params = {"embed": _quantize_leaf(params["embed"], _CONTRACT_AXES["embed"])}
    layers = {}
    for name, w in params["layers"].items():
        if name in _CONTRACT_AXES:
            layers[name] = _quantize_leaf(w, _CONTRACT_AXES[name])
        else:
            layers[name] = w  # norm scales
    out["layers"] = layers
    out["final_norm"] = params["final_norm"]
    return out


def _is_qleaf(node: Any) -> bool:
    return (
        isinstance(node, dict)
        and set(node.keys()) == {"int8", "scale"}
    )


def is_quantized(params: Params) -> bool:
    return _is_qleaf(params.get("embed"))


def dequant_params(params: Params, dtype: Any = jnp.float32) -> Params:
    """Full-tree dequantization — for tests, converters and offline tools
    ONLY. The serving forwards never call this: they dequantize per layer
    inside the scan body (``dequant_layer``) and handle the embedding with
    ``embed_lookup``/``unembed`` so the full-precision tree never
    materialises in HBM."""

    def walk(node: Any) -> Any:
        if _is_qleaf(node):
            return (node["int8"].astype(jnp.float32) * node["scale"]).astype(dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)


def dequant_layer(lp: dict[str, Any], dtype: Any) -> dict[str, Any]:
    """Per-layer dequant, called INSIDE the layer-scan body (identity on
    plain layers). Position matters: the scan's xs stay int8 in HBM, and
    inside the body the dequant is an elementwise producer feeding this
    layer's matmuls directly — the fusion XLA cannot do across a scan
    boundary (a pre-scan dequant would materialise the whole bf16 stack
    as the scan operand, costing MORE traffic than the bf16 baseline)."""
    return {
        k: (v["int8"].astype(jnp.float32) * v["scale"]).astype(dtype)
        if _is_qleaf(v)
        else v
        for k, v in lp.items()
    }


def embed_lookup(embed: Any, tokens: jax.Array, dtype: Any) -> jax.Array:
    """Embedding rows for ``tokens`` — gathers int8 rows + their per-row
    scales (never rebuilding the full-vocab bf16 table) on a quantized
    embed; plain gather otherwise."""
    if _is_qleaf(embed):
        rows = embed["int8"][tokens].astype(jnp.float32)
        return (rows * embed["scale"][tokens]).astype(dtype)
    return embed[tokens].astype(dtype)


def unembed(x: jax.Array, embed: Any, subset: "jax.Array | None" = None) -> jax.Array:
    """Logits = x @ embed.T in float32. Quantized path applies the per-row
    scale on the OUTPUT (s_v * sum_d x_d q_vd == sum_d x_d (s_v q_vd)), so
    no dequantized copy of the table is ever a required intermediate — the
    int8->dtype cast on the dot operand is left for XLA to fuse. ``subset``
    [C] restricts to those vocab rows (compact-column decode path)."""
    if _is_qleaf(embed):
        q, s = embed["int8"], embed["scale"]
        if subset is not None:
            q, s = q[subset], s[subset]
        logits = jnp.einsum(
            "...d,vd->...v", x, q.astype(x.dtype), preferred_element_type=jnp.float32
        )
        return logits * s[..., 0]
    w = embed if subset is None else embed[subset]
    return jnp.einsum("...d,vd->...v", x, w, preferred_element_type=jnp.float32)


def quant_pspecs(cfg, mesh) -> Params:
    """PartitionSpec tree matching the QUANTIZED param structure: int8
    leaves keep ``param_pspecs``'s layout; scales drop the sharding on the
    contraction axes (their keepdims-1 dims), staying consistent with the
    sharded weight under GSPMD."""
    from jax.sharding import PartitionSpec as P

    from mcpx.parallel.mesh import param_pspecs

    base = param_pspecs(cfg, mesh)

    def q(name: str, spec):
        if name not in _CONTRACT_AXES:
            return spec
        axes = _CONTRACT_AXES[name]
        scale_spec = P(*[None if i in axes else s for i, s in enumerate(spec)])
        return {"int8": spec, "scale": scale_spec}

    return {
        "embed": q("embed", base["embed"]),
        "layers": {k: q(k, v) for k, v in base["layers"].items()},
        "final_norm": base["final_norm"],
    }


def leaf_quantizer(name: str, w: jax.Array) -> Any:
    """Per-leaf transform for ``init_params(leaf_transform=...)``: quantize
    the named weight at CREATION time, so the full bf16 tree never exists —
    peak memory is the int8 tree plus one bf16 leaf (the 7B-on-one-v5e
    path; a post-hoc quantize_params needs 1.5x the bf16 footprint)."""
    if name in _CONTRACT_AXES:
        return _quantize_leaf(w, _CONTRACT_AXES[name])
    return w


def quantized_param_bytes(cfg) -> int:
    """Bytes-at-rest of the int8 serving params for a GemmaConfig, computed
    from shapes alone (jax.eval_shape — nothing materialises). The capacity
    claim behind ``quantize="int8"``: Gemma-7B fits a 16 GB v5e chip."""
    import math

    from mcpx.models.gemma.model import init_params

    tree = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), leaf_transform=leaf_quantizer)
    )
    # math.prod: Python arbitrary-precision — 7B's stacked w_gate sits at
    # 98% of int32 max, one config bump would silently wrap a jnp.prod.
    return sum(
        math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(tree)
    )
