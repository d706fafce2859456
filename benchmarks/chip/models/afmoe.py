"""The AFMoE block (arcee-ai/Trinity-Mini, ``model_type`` ``afmoe``), as a
configuration's block module: the bridge from the published keys to the
program's model-config object, the block's plain reference, and the
program's step of the comparison.

The layer (D hidden, no biases on the projections, SiLU, an untied head,
RMSNorm with a plain gain g drawn 1):

  x_0    = Embed[token] * sqrt(D)                                (mup_enabled)
  n1     = RMSNorm(x) g_in
  q,k,v  = Wq n1, Wk n1, Wv n1;   gate = Wg n1  [heads * head_dim]
  q, k   = RMSNorm_head(q) g_q, RMSNorm_head(k) g_k      (over head_dim)
  q, k   = RoPE(q), RoPE(k) on a ``sliding_attention`` layer (half-split,
           theta^(-2k/dim)); UNROTATED on a ``full_attention`` layer
  o      = softmax(q k / sqrt(head_dim)) v over keys j <= i, and on a
           sliding layer also j > i - sliding_window
  a      = x + RMSNorm(Wo (o * sigmoid(gate))) g_post_attn
  n2     = RMSNorm(a) g_pre_mlp
  ff     = Wd(silu(Wgate n2) * (Wup n2))        the num_dense_layers leading layers
         = Shared(n2) + Routed(n2)              every later layer
  y      = a + RMSNorm(ff) g_post_mlp
  Routed   s = sigmoid(n2 Wr), float32, over all experts; chosen = the
           num_experts_per_tok largest of (s + b); w = s[chosen]; w = w /
           (sum w + 1e-20) (route_norm); w = w * route_scale;
           sum_k w_k Wd_e(silu(Wg_e n2) * (Wu_e n2))
  Shared   one SwiGLU of width num_shared_experts * moe_intermediate_size,
           every token, weight 1
  logits = (RMSNorm(x_L) g_f) W_head

The config's keys give the router's scoring, norm and scale, the shared
expert, the dense lead, the widths and the layer pattern. The attention's
output gate, the per-head q/k norm, rotation in window layers only, the four
norms a layer and the bias b entering the choice alone are the AFMoE block as
published with the checkpoint (transformers ``modeling_afmoe.py``) and have no
key in ``config.json``: the configuration file lists each under ``assumed``.

The reference below is that, in plain ``jax.numpy`` float32 at ``highest``,
every expert held computed densely (in groups of 16, so that the float32 copy
it holds is 16 experts' and not a layer's 3.2 GB) and weighted by the
routing; it reads only the parameter arrays (names and layouts of
``init_params``: the leading dense layers under ``dense_layers``, the sparse
ones under ``layers``) and the model config as a dict, and derives rope,
windows and routing itself.

**The comparison runs the reference under the step's routing**, as the
Mellum block's does and for its reason (``models/mellum.py``): top-8 of 128
sigmoid scores flips between bfloat16 and float32 wherever the 8th and 9th
``s + b`` are close, and a flipped expert moves that token's layer output by
far more than rounding. ``step_functions`` records what every position chose
in every sparse layer; ``reference_logits`` finds the record by the token
ids, uses the step's choice where the step ran, and CHECKS it on what is
compared, ``s + b``: each chosen expert's reference ``s + b`` is at least
the reference's k-th largest less MARGIN, each unchosen one's at most that
plus MARGIN. A row with a position that breaks this gets NaN logits, which
never pass.
"""

from __future__ import annotations

import math

kernel_paths = {"decode": 1, "prefill": 0}

# How far (absolute, in s + b) a chosen expert may lie under the reference's
# k-th largest, or an unchosen one over it. Absolute, not a share as Mellum's
# DELTA: sigmoid scores of 128 experts plus a bias lie 0.01-0.02 apart near the
# 8th, where softmax probabilities of 64 spread over decades. Read on the chip
# (TPU v5 lite, PR 36, the slab's shape, 8 layers, every expert held, seeds
# 3000003601-636; PERF.md section 6): the largest such distance a seed was
# 0.0053-0.0103, and the two sides chose another set in 6.1-8.2% of the
# 3,090-4,446 (sparse layer, position) pairs a seed. MARGIN lies between the two
# readings the contract asks for: 1.9 times the largest sound distance, and 1.9
# times under what a step in the next precision below reads: the int8-weights
# control, at the cell's configuration (every expert held, the weights rounded
# in place, judged not correct by ``reference.compare_with_engine_step``
# itself: ``tests/test_trinity_readings.py``, one seed) 0.0458, with another
# set in 33% of the pairs, rms 0.066 against 0.02 and max 0.28 against 0.12;
# at 64 of 128 experts held (a builder's script, three seeds) 0.0373-0.0420,
# 32%, 0.061-0.063 and 0.26-0.28: it fails each of the three limits.
MARGIN = 0.02
ROUTING_READ = {"largest_distance": 0.0103, "flip_share": (0.061, 0.082),
                "int8_control_smallest_distance": 0.0373}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # False: the reference keeps its own top-k everywhere: a sound step fails.
    "follow_step_routing": True,
}


def _harness_file(name: str):
    """A file beside ``reference.py``, imported by path as the harness imports
    this one (a block module is not found through ``sys.path``)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), name + ".py")
    spec = importlib.util.spec_from_file_location("chip_harness_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# The step's routing, one record a row: {"ids" [n], "chosen" [Ls, n, k]}.
_ROUTING = _harness_file("routing_record").RoutingRecord()

# Published key -> GemmaConfig field.
_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",  # the leading dense layers' width
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "num_dense_layers": "n_dense_layers",
    "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "d_expert",
    "route_scale": "router_scale",
    # not the source's: stated by the configuration file under ``assumed``
    "dtype": "dtype",
    "router_bias_scale": "router_bias_scale",
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "afmoe",
    "hidden_act": "silu",
    "tie_word_embeddings": False,
    "mup_enabled": True,
    "score_func": "sigmoid",
    "route_norm": True,
    "rope_scaling": None,
    "num_shared_experts": 1,
    "global_attn_every_n_layers": 4,
    # no group limit on the choice
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
    # training's (the update of b) and an implementation's choice: stated, not built
    "load_balance_coeff": 0.001, "use_grouped_mm": True,
}
# Keys that say which experts of each sparse layer this chip holds (the cut
# of the model-configs guide, section 4); absent = all of them.
_SHARE = {"expert_first": "expert_first", "experts_held": "experts_held"}


def afmoe_dims(config: dict, vocab_size: int) -> dict:
    """The configuration file's keys -> ``GemmaConfig`` fields. A key that is
    neither consumed nor a stated property of the block is an error, so none
    is silently dropped."""
    if config["vocab_size"] != vocab_size:
        raise ValueError(
            f"config says vocab_size {config['vocab_size']}, the repo's tokenizer has {vocab_size}"
        )
    for key, value in _BLOCK_IS.items():
        if key not in config or config[key] != value:
            raise ValueError(f"{key}={config.get(key)!r}: this block is {value!r} and has no other")
    known = set(_FIELDS) | set(_BLOCK_IS) | set(_SHARE) | {"vocab_size", "layer_types"}
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    n = int(config["num_hidden_layers"])
    # The file copies the source's layer_types whole; a cut in depth keeps
    # its first num_hidden_layers entries (whole periods).
    layer_types = tuple(config["layer_types"][:n])
    every = config["global_attn_every_n_layers"]
    if layer_types != tuple(
        "full_attention" if (i + 1) % every == 0 else "sliding_attention" for i in range(n)
    ):
        raise ValueError("layer_types: a full_attention layer every global_attn_every_n_layers")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in ("norm_eps", "rope_theta", "router_scale", "router_bias_scale"):
        dims[field] = float(dims[field])
    dims.update({field: int(config[key]) for key, field in _SHARE.items() if key in config})
    return dict(
        vocab_size=vocab_size, **dims, layer_types=layer_types,
        d_shared_expert=int(config["num_shared_experts"]) * int(config["moe_intermediate_size"]),
        router_scoring="sigmoid", rope_full_layers=False,
        qk_norm=True, attn_gate=True, post_norms=True,
        activation="silu", tie_embeddings=False, scale_embeddings=True, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "n_sparse_layers"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("afmoe: this mcpx has no gated, QK-normed, shared-expert block (GemmaConfig)")
    return GemmaConfig(**afmoe_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (two periods of the layer pattern: 2 dense
    lead layers and 6 sparse ones, 8 experts top-2, one shared expert, window
    8): rehearsals and tests only. 256 wide, not the other blocks' 128: at
    128 eight layers read 0.020-0.021 against the 0.02 of ``reference.tol``
    on the CPU (Mellum's four read 0.016-0.020 there), at 256 0.017."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=64,
        d_ff=512, rope_theta=10000.0, norm_eps=1e-5, max_seq_len=2048,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 2, sliding_window=8,
        rope_full_layers=False, qk_norm=True, attn_gate=True, post_norms=True,
        n_experts=8, n_experts_per_tok=2, d_expert=128, n_dense_layers=2, d_shared_expert=128,
        router_scoring="sigmoid", router_bias_scale=0.1, router_scale=2.826,
        activation="silu", tie_embeddings=False, scale_embeddings=True, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block: the program's step with
    the routing output on, what every live position chose in every sparse
    layer recorded by row for ``reference_logits`` (``routing_record.py``)."""
    return _ROUTING.step_functions(
        model_cfg, mesh, B=B, T=T, n_pages=n_pages, page_size=page_size, interpret=interpret
    )


def routing_readings(params, dims: dict) -> list[dict]:
    """What the routing check reads on each recorded row (the positions the
    last step ran): the largest distance, the (sparse layer, position) pairs
    where the reference's own top-k is another set, and the pairs checked."""
    return _ROUTING.readings(lambda p, t: _reference(p, dims, t)[1:], params)


# ------------------------------------------------------------- the reference
def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded routing breaks the routing check."""
    import jax.numpy as jnp

    logits, distance, _flipped, _checked = _reference(params, dims, tokens)
    return jnp.where(distance <= MARGIN, logits, jnp.nan)


def _reference(params, dims: dict, tokens):
    """-> (logits [T, V], the routing check's largest distance, the (sparse
    layer, position) pairs the step ran where the reference's own top-k is
    another set, the pairs the step ran)."""
    import jax
    import jax.numpy as jnp

    D, H, K, hd = dims["d_model"], dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    L, Ld, eps = dims["n_layers"], dims["n_dense_layers"], dims["norm_eps"]
    E, k = dims["n_experts"], dims["n_experts_per_tok"]
    first = dims["expert_first"]
    held = dims["experts_held"] or E
    group = math.gcd(held, 16)  # experts whose float32 copy is held at once
    f32 = jnp.float32
    T = tokens.shape[0]
    half = hd // 2

    step_choice = _ROUTING.chosen_for(tokens, L - Ld, k, CONTROLS["follow_step_routing"])
    # A sliding layer rotates by theta^(-2k/dim); a full layer does not.
    plain = [dims["rope_theta"] ** (-2.0 * i / hd) for i in range(half)]
    sliding = [t == "sliding_attention" for t in dims["layer_types"]]
    inv_freq = jnp.asarray([plain if s else [0.0] * half for s in sliding], f32)
    window = jnp.asarray([dims["sliding_window"] if s else T for s in sliding], jnp.int32)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def norm(x, gain):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * gain

    def swiglu(n, w_gate, w_up, w_down):
        return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down

    def attention(x, lp, freq, win):
        def rope(t):  # [T, heads, hd]
            ang = jnp.arange(T, dtype=f32)[:, None] * freq[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

        n1 = norm(x, lp["pre_attn_norm"])
        q = rope(norm(jnp.einsum("td,dhe->the", n1, lp["wq"]), lp["q_norm"]))
        kk = rope(norm(jnp.einsum("td,dke->tke", n1, lp["wk"]), lp["k_norm"]))
        v = jnp.einsum("td,dke->tke", n1, lp["wv"])
        gate = jnp.einsum("td,dhe->the", n1, lp["w_attn_gate"])
        kk = jnp.repeat(kk, H // K, axis=1)  # each KV head serves H/K query heads
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("the,she->hts", q, kk) / math.sqrt(hd)
        s = jnp.where(((j <= i) & (j > i - win))[None], s, -jnp.inf)
        o = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        out = jnp.einsum("the,hed->td", o * jax.nn.sigmoid(gate), lp["wo"])
        return x + norm(out, lp["post_attn_norm"])

    def small(lp):  # everything of a layer but its routed experts, in float32
        return {name: w.astype(f32) for name, w in lp.items() if name not in ("w_gate", "w_up", "w_down")}

    def dense_layer(x, xs):
        lp, freq, win = xs
        lp = jax.tree.map(lambda w: w.astype(f32), lp)
        a = attention(x, lp, freq, win)
        ff = swiglu(norm(a, lp["pre_mlp_norm"]), lp["w_gate"], lp["w_up"], lp["w_down"])
        return a + norm(ff, lp["post_mlp_norm"]), None

    def sparse_layer(carry, xs):
        x, distance, flipped, checked = carry
        lp, freq, win, choice = xs
        sm = small(lp)
        a = attention(x, sm, freq, win)
        n2 = norm(a, sm["pre_mlp_norm"])
        s = jax.nn.sigmoid(n2 @ sm["router"])  # [T, E]
        pick = s + sm["router_bias"]  # what is compared: the bias enters the choice
        own_pick, own = jax.lax.top_k(pick, k)
        ran = choice[:, 0] >= 0  # the positions the step ran
        idx = jnp.where(ran[:, None], choice, own)
        sel = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)  # [T, E]
        # The check: no chosen expert far under the reference's k-th s + b,
        # no unchosen one far over it.
        kth = own_pick[:, k - 1]
        under = kth - jnp.min(jnp.where(sel, pick, jnp.inf), axis=-1)
        over = jnp.max(jnp.where(sel, -jnp.inf, pick), axis=-1) - kth
        distance = jnp.maximum(distance, jnp.max(jnp.where(ran, jnp.maximum(under, over), 0.0)))
        own_sel = jnp.any(own[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
        flipped += jnp.sum(ran & jnp.any(sel != own_sel, axis=-1))
        checked += jnp.sum(ran)
        w = jnp.where(sel, s, 0.0)  # the weights are the scores: b weighs nothing
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)  # route_norm
        w = (w * dims["router_scale"])[:, first : first + held]  # this chip's experts

        def experts(acc, ws):  # a group of the experts held, densely
            w_gate, w_up, w_down, w_g = ws
            act = jax.nn.silu(jnp.einsum("td,edf->etf", n2, w_gate.astype(f32)))
            act = act * jnp.einsum("td,edf->etf", n2, w_up.astype(f32)) * w_g[:, :, None]
            return acc + jnp.einsum("etf,efd->td", act, w_down.astype(f32)), None

        grouped = lambda a: a.reshape(held // group, group, *a.shape[1:])
        routed, _ = jax.lax.scan(
            experts, jnp.zeros((T, D), f32),
            (grouped(lp["w_gate"]), grouped(lp["w_up"]), grouped(lp["w_down"]), grouped(w.T)),
        )
        ff = swiglu(n2, sm["shared_gate"], sm["shared_up"], sm["shared_down"]) + routed
        return (a + norm(ff, sm["post_mlp_norm"]), distance, flipped, checked), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens] * math.sqrt(D)
        zero = jnp.asarray(0, jnp.int32)
        # scans only to take one layer's weights at a time
        x, _ = jax.lax.scan(dense_layer, x, (params["dense_layers"], inv_freq[:Ld], window[:Ld]))
        (x, distance, flipped, checked), _ = jax.lax.scan(
            sparse_layer, (x, jnp.asarray(0.0, f32), zero, zero),
            (params["layers"], inv_freq[Ld:], window[Ld:], step_choice),
        )
        logits = norm(x, params["final_norm"].astype(f32)) @ params["head"].astype(f32)
    return logits, distance, flipped, checked
