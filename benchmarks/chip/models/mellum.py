"""The Mellum 2 block (JetBrains/Mellum2-12B-A2.5B-Instruct), as a
configuration's block module: the bridge from the published keys to the
program's model-config object, the block's plain reference, and the
program's step of the comparison.

The layer, as the source's config gives it (h hidden, no biases, SiLU,
embeddings untied and not scaled, RMSNorm with a plain gain g drawn 1):

  a = x + Wo Attn_l(RoPE_l(Wq n1), RoPE_l(Wk n1), Wv n1),   n1 = RMSNorm(x) g1
  y = a + MoE(RMSNorm(a) g2)
  logits = (RMSNorm(x_L) g_f) W_head

  Attn_l   softmax(q k / sqrt(head_dim)) over keys j <= i, and on a
           ``sliding_attention`` layer also j > i - sliding_window.
  RoPE_l   half-split rotation. Sliding layers: inv_freq_k = theta^(-2k/dim),
           factor 1. Full layers, YaRN: corr(r) = dim ln(orig / (2 pi r)) /
           (2 ln theta); low = floor(corr(beta_fast)), high =
           ceil(corr(beta_slow)), clipped to [0, dim - 1]; ramp_k = clip((k -
           low) / (high - low), 0, 1); inv_freq_k = (1 - ramp_k) theta^(-2k/dim)
           + ramp_k theta^(-2k/dim) / factor; cos and sin times
           attention_factor.
  MoE(n)   p = softmax(n Wr) over all experts, float32; the
           num_experts_per_tok largest p; w = p_top / sum(p_top)
           (norm_topk_prob); sum_k w_k Wd_e(silu(Wg_e n) * (Wu_e n)).

The reference below is that, in plain ``jax.numpy`` float32 at ``highest``,
every expert computed densely and weighted by the routing; it reads only the
parameter arrays (names and layouts of ``init_params``) and the model config
as a dict, and derives rope, windows and routing itself.

**The comparison runs the reference under the step's routing.** A block that
picks its 8 of 64 experts picks differently in bfloat16 and in float32
wherever the 8th and 9th probabilities are close, and one swapped expert
moves that token's layer output by about a third: against the reference's
OWN routing a sound step reads an order over the tolerance (``CONTROLS``,
``follow_step_routing`` off, shows it). So ``step_functions`` runs the
program's step with its routing output on and records, per row, the experts
every position chose, keyed by the row's token ids; ``reference_logits``
finds that record by the token ids it is given (the records are constants of
its trace, which ``compare_with_engine_step`` makes after the step has run;
the harness's child has no CPU backend for a host callback), uses the step's
choice at the positions the step ran and its own top-k elsewhere, and CHECKS
the choice: at every (layer, position) the step ran, each chosen
expert's reference probability is at least (1 - DELTA) of the reference's
k-th largest and each unchosen one's at most (1 + DELTA) of it. A row with a
position that breaks this gets NaN logits, which never pass. The record
stands in for a channel ``reference.compare_with_engine_step`` lacks (PERF.md,
Open questions).
"""

from __future__ import annotations

import math

kernel_paths = {"decode": 1, "prefill": 0}

# How far a chosen expert's reference probability may lie under the
# reference's k-th largest (or an unchosen one's over it), as a share of it.
# Read on the chip (TPU v5 lite, PR 33, the slab's shape, 12 layers, seeds
# 3000000301-312; PERF.md section 6): the largest such distance a seed was
# 0.0233-0.0319, and the two sides chose another set in 4.6-5.5% of the
# 5,508-9,096 (layer, position) pairs a seed; at prompts of 747 and 1,716
# tokens (29,628 pairs, one seed) 0.0382 (ROUTING_READ). DELTA lies between
# the two readings the contract asks for: 2.6 times the largest sound
# distance (3.1 times the slab shape's), and under what a step in the next
# precision below reads: the int8-weights control at 4 layers 0.133 and
# 0.180 (sound there: 0.017-0.019), which also fails rms and max. A step on
# a router of flipped sign reads 31 and 48 (the control ``wrong_experts``).
DELTA = 0.1
ROUTING_READ = {"largest_distance": 0.0382, "flip_share": (0.046, 0.055),
                "int8_control_smallest_distance": 0.133}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # False: the reference keeps its own top-k everywhere (what a comparison
    # without the routing channel would do): a sound step then fails.
    "follow_step_routing": True,
    # True: the program's step runs on a router whose sign is flipped, so it
    # chooses the reference's LAST k experts: fails the routing check.
    "wrong_experts": False,
}
# The step's routing, one record a row: {"ids" [n], "chosen" [L, n, k]}.
_RECORD: list[dict] = []

# Published key -> GemmaConfig field.
_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",  # used by no layer: every mlp_layer_types entry is sparse
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "dtype": "dtype",
    "sliding_window": "sliding_window",
    "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "d_expert",
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "attention_bias": False,
    "hidden_act": "silu",
    "tie_word_embeddings": False,
    "norm_topk_prob": True,
    "use_sliding_window": True,
    "max_window_layers": 0,
    "model_type": "mellum",
}
_ROPE_KEYS = {
    "full_attention": {"rope_type", "rope_theta", "factor", "original_max_position_embeddings",
                       "beta_fast", "beta_slow", "attention_factor"},
    "sliding_attention": {"rope_type", "rope_theta"},
}
# Keys that say which experts of each layer this chip holds (the cut of the
# model-configs guide, section 4); absent = all of them.
_SHARE = {"expert_first": "expert_first", "experts_held": "experts_held"}


def mellum_dims(config: dict, vocab_size: int) -> dict:
    """The configuration file's published keys -> ``GemmaConfig`` fields. A
    key that is neither consumed nor a stated property of the block is an
    error, so none is silently dropped."""
    if config["vocab_size"] != vocab_size:
        raise ValueError(
            f"config says vocab_size {config['vocab_size']}, the repo's tokenizer has {vocab_size}"
        )
    for key, value in _BLOCK_IS.items():
        if config.get(key) != value:
            raise ValueError(f"{key}={config.get(key)!r}: this block is {value!r} and has no other")
    known = set(_FIELDS) | set(_BLOCK_IS) | set(_SHARE) | {
        "vocab_size", "layer_types", "mlp_layer_types", "rope_parameters"}
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    n = int(config["num_hidden_layers"])
    # The file copies the source's per-layer lists whole; a cut in depth
    # keeps their first num_hidden_layers entries (whole periods).
    layer_types = tuple(config["layer_types"][:n])
    if len(layer_types) != n or set(config["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("layer_types / mlp_layer_types: need an entry a layer, every MLP sparse")
    rope = config["rope_parameters"]
    for kind, keys in _ROPE_KEYS.items():
        if set(rope.get(kind, {})) != keys:
            raise ValueError(f"rope_parameters.{kind}: expected exactly {sorted(keys)}")
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
        raise ValueError("rope_parameters: full layers yarn, sliding layers default")
    if full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("rope_parameters: the block has one rope_theta for both kinds")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    dims["norm_eps"] = float(dims["norm_eps"])
    dims.update({field: int(config[key]) for key, field in _SHARE.items() if key in config})
    return dict(
        vocab_size=vocab_size, **dims,
        rope_theta=float(full["rope_theta"]), layer_types=layer_types,
        yarn_factor=float(full["factor"]),
        yarn_original_max_pos=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]), yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "n_experts_held"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("mellum: this mcpx has no sparse-expert, windowed block (GemmaConfig)")
    return GemmaConfig(**mellum_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (one period of the layer pattern, 8
    experts top-2, window 8): rehearsals and tests only."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, rope_theta=500000.0, norm_eps=1e-6, max_seq_len=2048,
        layer_types=("sliding_attention",) * 3 + ("full_attention",), sliding_window=8,
        yarn_factor=16.0, yarn_original_max_pos=64, yarn_attention_factor=1.2772588722239782,
        n_experts=8, n_experts_per_tok=2, d_expert=64,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block: the program's dense
    prefill committed to pages and its paged decode through the ragged
    kernel, each with the routing output on; what every live position chose
    is recorded by row for ``reference_logits``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.model import init_kv_cache, prefill

    _RECORD.clear()

    @jax.jit
    def prefill_j(params, tokens, lens, table):
        dense = init_kv_cache(model_cfg, B, T)
        last, dense, chosen = prefill(
            params, model_cfg, tokens, lens, dense, last_only=True, routing=True
        )
        pools = init_paged_kv(model_cfg, n_pages, page_size)
        pools = commit_prefill_to_pages(pools, dense, table, lens, page_size)
        return last, pools, chosen

    @jax.jit
    def decode_j(params, tok, pos, table, pools):
        return decode_chunk_paged(
            params, model_cfg, tok[:, None], pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.zeros((B,), jnp.int32), q_lens=jnp.ones((B,), jnp.int32),
            routing=True,
        )

    def as_run(params):
        if not CONTROLS["wrong_experts"]:
            return params
        # Only the router leaf is new: the tree is too large to copy.
        return dict(params, layers=dict(params["layers"], router=-params["layers"]["router"]))

    def sys_prefill(params, tokens, lens, table):
        last, pools, chosen = prefill_j(as_run(params), tokens, lens, table)
        chosen, tokens_h = np.asarray(chosen), np.asarray(tokens)  # [L, B, T, k]
        for b, n in enumerate(np.asarray(lens)):
            _RECORD.append({"ids": tokens_h[b, :n], "chosen": chosen[:, b, :n]})
        return last, pools

    def sys_decode(params, tok, pos, table, pools):
        logits, pools, chosen = decode_j(as_run(params), tok, pos, table, pools)
        chosen, tok_h = np.asarray(chosen), np.asarray(tok)  # [L, B, 1, k]
        for b, rec in enumerate(_RECORD):
            rec["ids"] = np.append(rec["ids"], tok_h[b])
            rec["chosen"] = np.concatenate([rec["chosen"], chosen[:, b]], axis=1)
        return logits, pools

    return sys_prefill, sys_decode


def _recorded_routing(tokens, n_layers: int, k: int):
    """The experts the step chose for the sequence whose first tokens are a
    recorded row's, ``[L, T, k]``, -1 at the positions the step did not run
    (and everywhere, for a sequence the step never saw or with
    ``follow_step_routing`` off). The records enter as constants."""
    import jax.numpy as jnp
    import numpy as np

    T = tokens.shape[0]
    records = [r for r in _RECORD if len(r["ids"]) <= T] if CONTROLS["follow_step_routing"] else []
    if not records:
        return jnp.full((n_layers, T, k), -1, jnp.int32)
    ids = np.full((len(records), T), -1, np.int32)
    chosen = np.full((len(records), n_layers, T, k), -1, np.int32)
    for r, rec in enumerate(records):
        n = len(rec["ids"])
        ids[r, :n], chosen[r, :, :n] = rec["ids"], rec["chosen"]
    n = jnp.asarray([len(rec["ids"]) for rec in records], jnp.int32)
    same = jnp.all((tokens[None, :] == ids) | (jnp.arange(T)[None, :] >= n[:, None]), axis=1)
    score = jnp.where(same, n, -1)  # the longest recorded prefix of these tokens
    best = jnp.argmax(score)
    return jnp.where(score[best] > 0, jnp.asarray(chosen)[best], -1)


def routing_readings(params, dims: dict) -> list[dict]:
    """What the routing check reads on each recorded row (the positions the
    last step ran): the largest distance, the (layer, position) pairs where
    the reference's own top-k is another set, and the pairs checked."""
    import jax
    import jax.numpy as jnp

    out = []
    for rec in list(_RECORD):
        parts = jax.jit(lambda p, t: _reference(p, dims, t)[1:])(params, jnp.asarray(rec["ids"]))
        distance, flipped, checked = (float(x) for x in parts)
        out.append({"distance": distance, "flipped": int(flipped), "checked": int(checked)})
    return out


# ------------------------------------------------------------- the reference
def _rope_tables(dims: dict):
    """Inverse frequencies [L, hd/2] and the cos/sin factor [L], from the
    formulas in this file's header."""
    import numpy as np

    dim, theta = dims["head_dim"], dims["rope_theta"]
    plain = [theta ** (-2.0 * k / dim) for k in range(dim // 2)]

    def corr(r):
        return dim * math.log(dims["yarn_original_max_pos"] / (2 * math.pi * r)) / (2 * math.log(theta))

    low = min(max(math.floor(corr(dims["yarn_beta_fast"])), 0), dim - 1)
    high = min(max(math.ceil(corr(dims["yarn_beta_slow"])), 0), dim - 1)
    yarn = []
    for k, f in enumerate(plain):
        ramp = min(max((k - low) / (high - low), 0.0), 1.0)
        yarn.append((1 - ramp) * f + ramp * f / dims["yarn_factor"])
    full = [t == "full_attention" for t in dims["layer_types"]]
    inv_freq = np.asarray([yarn if f else plain for f in full], np.float32)
    factor = np.asarray([dims["yarn_attention_factor"] if f else 1.0 for f in full], np.float32)
    return inv_freq, factor


def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded routing breaks the routing check."""
    import jax.numpy as jnp

    logits, distance, _flipped, _checked = _reference(params, dims, tokens)
    return jnp.where(distance <= DELTA, logits, jnp.nan)


def _reference(params, dims: dict, tokens):
    """-> (logits [T, V], the routing check's largest distance, the (layer,
    position) pairs the step ran where the reference's own top-k is another
    set, the pairs the step ran)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, K, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    L, E, k, eps = dims["n_layers"], dims["n_experts"], dims["n_experts_per_tok"], dims["norm_eps"]
    first = dims["expert_first"]
    held = dims["experts_held"] or E
    f32 = jnp.float32
    T = tokens.shape[0]
    half = hd // 2

    step_choice = _recorded_routing(tokens, L, k)
    inv_freq, factor = _rope_tables(dims)
    window = np.asarray(
        [dims["sliding_window"] if t == "sliding_attention" else T for t in dims["layer_types"]],
        np.int32,
    )
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def norm(x, gain):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * gain

    def layer(carry, xs):
        x, distance, flipped, checked = carry
        lp, freq, fac, win, choice = xs
        lp = jax.tree.map(lambda w: w.astype(f32), lp)

        def rope(t):  # [T, heads, hd]
            ang = jnp.arange(T, dtype=f32)[:, None] * freq[None, :]
            cos, sin = (jnp.cos(ang) * fac)[:, None, :], (jnp.sin(ang) * fac)[:, None, :]
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

        n1 = norm(x, lp["pre_attn_norm"])
        q = rope(jnp.einsum("td,dhe->the", n1, lp["wq"]))
        kk = rope(jnp.einsum("td,dke->tke", n1, lp["wk"]))
        v = jnp.einsum("td,dke->tke", n1, lp["wv"])
        kk = jnp.repeat(kk, H // K, axis=1)  # each KV head serves H/K query heads
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("the,she->hts", q, kk) / math.sqrt(hd)
        s = jnp.where(((j <= i) & (j > i - win))[None], s, -jnp.inf)
        a = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("the,hed->td", a, lp["wo"])

        n2 = norm(x, lp["pre_mlp_norm"])
        p = jax.nn.softmax(n2 @ lp["router"], axis=-1)  # [T, E]
        own_p, own = jax.lax.top_k(p, k)
        ran = choice[:, 0] >= 0  # the positions the step ran
        idx = jnp.where(ran[:, None], choice, own)
        sel = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)  # [T, E]
        # The check: no chosen expert far under the reference's k-th
        # probability, no unchosen one far over it.
        kth = own_p[:, k - 1]
        under = 1.0 - jnp.min(jnp.where(sel, p, jnp.inf), axis=-1) / kth
        over = jnp.max(jnp.where(sel, 0.0, p), axis=-1) / kth - 1.0
        distance = jnp.maximum(distance, jnp.max(jnp.where(ran, jnp.maximum(under, over), 0.0)))
        own_sel = jnp.any(own[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
        flipped += jnp.sum(ran & jnp.any(sel != own_sel, axis=-1))
        checked += jnp.sum(ran)
        w = jnp.where(sel, p, 0.0)
        w = (w / jnp.sum(w, axis=-1, keepdims=True))[:, first : first + held]  # this chip's experts
        # Every expert held, densely; the routing is only the weights.
        act = jax.nn.silu(jnp.einsum("td,edf->etf", n2, lp["w_gate"]))
        act = act * jnp.einsum("td,edf->etf", n2, lp["w_up"]) * w.T[:, :, None]
        return (x + jnp.einsum("etf,efd->td", act, lp["w_down"]), distance, flipped, checked), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens]
        zero = jnp.asarray(0, jnp.int32)
        # scan only to cast one layer's weights to float32 at a time (the
        # float32 copy of a layer's 64 experts is 1.6 GB).
        (x, distance, flipped, checked), _ = jax.lax.scan(
            layer, (x, jnp.asarray(0.0, f32), zero, zero),
            (params["layers"], jnp.asarray(inv_freq), jnp.asarray(factor), jnp.asarray(window),
             step_choice),
        )
        logits = norm(x, params["final_norm"].astype(f32)) @ params["head"].astype(f32)
    return logits, distance, flipped, checked
