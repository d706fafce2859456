"""mcpx's one decoder block, as a configuration's block module: the bridge
from a configuration file's published keys to the program's model-config
object, and the block's plain reference.

The block: pre-norm decoder, RMSNorm with a (1 + scale) gain, RoPE over
half-split head dims, grouped-query attention with a causal mask, gated
tanh-GELU MLP (GeGLU), embeddings tied and scaled by sqrt(hidden). The
configuration files name where this departs from each source model.

The reference is independent of ``mcpx/models`` and ``mcpx/engine``: it reads
only the parameter arrays (names and layouts of ``init_params``).
"""

from __future__ import annotations

import math

# Kernel path (of ``/healthz`` ``engine_queue.pallas.paths``) -> the fewest
# dispatches a run must show on it; every path named has to be engaged.
# Prefill-path dispatches are reported, not required: distinct prompts rarely
# share a page-aligned prefix.
kernel_paths = {"decode": 1, "prefill": 0}

# Published key -> GemmaConfig field.
_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "dtype": "dtype",
}
_FLOATS = ("rope_theta", "norm_eps")
# Published keys the block has no knob for: a file may state only what the
# block does (its ``reduced`` says what the source publishes instead).
_BLOCK_IS = {
    "hidden_act": "gelu_pytorch_tanh",  # GeGLU
    "tie_word_embeddings": True,  # one embedding matrix
    "sliding_window": None,  # full causal attention
}


def gemma_dims(config: dict, vocab_size: int) -> dict:
    """The configuration file's published keys -> ``GemmaConfig`` fields.
    A key that is neither consumed nor a stated property of the block is an
    error, so none is silently dropped."""
    if config["vocab_size"] != vocab_size:
        raise ValueError(
            f"config says vocab_size {config['vocab_size']}, the repo's "
            f"tokenizer has {vocab_size}"
        )
    for key, value in _BLOCK_IS.items():
        if key in config and config[key] != value:
            raise ValueError(
                f"{key}={config[key]!r}: mcpx's decoder block is {value!r} and has no other"
            )
    unknown = sorted(set(config) - set(_FIELDS) - set(_BLOCK_IS) - {"vocab_size"})
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in _FLOATS:
        dims[field] = float(dims[field])
    return dict(vocab_size=vocab_size, **dims)


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(**gemma_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size: rehearsals only."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig.named("test", vocab_size=vocab_size)


def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]."""
    import jax
    import jax.numpy as jnp

    H, K, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    D, theta, eps = dims["d_model"], dims["rope_theta"], dims["norm_eps"]
    f32 = jnp.float32
    T = tokens.shape[0]

    def norm(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(f32))

    def rope(x):  # [T, heads, hd]
        half = hd // 2
        freq = jnp.exp(-math.log(theta) * (2.0 * jnp.arange(half, dtype=f32) / hd))
        ang = jnp.arange(T, dtype=f32)[:, None] * freq[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(f32), lp)
        h = norm(x, lp["pre_attn_norm"])
        q = rope(jnp.einsum("td,dhe->the", h, lp["wq"]))
        k = rope(jnp.einsum("td,dke->tke", h, lp["wk"]))
        v = jnp.einsum("td,dke->tke", h, lp["wv"])
        k = jnp.repeat(k, H // K, axis=1)  # each KV head serves H/K query heads
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("the,she->hts", q, k) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("the,hed->td", a, lp["wo"])
        h = norm(x, lp["pre_mlp_norm"])
        ff = jax.nn.gelu(h @ lp["w_gate"], approximate=True) * (h @ lp["w_up"])
        return x + ff @ lp["w_down"], None

    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(f32)
        x = embed[tokens] * math.sqrt(D)
        # scan only to cast one layer's weights to float32 at a time (a
        # 16-layer 7B stack in float32 would not fit beside the served one).
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = norm(x, params["final_norm"])
        return x @ embed.T
