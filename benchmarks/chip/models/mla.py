"""The latent-attention block of skt/A.X-K1 (``model_type`` ``axk1``; the
DeepSeek-V2/V3 family's MLA and routed feed-forward), as a configuration's
block module: the bridge from the published keys to the program's
model-config object, the block's plain reference, and the program's step of
the comparison.

With ``h = RMSNorm(x) g`` (plain gain drawn 1, eps ``rms_norm_eps``), D
hidden, H heads, no biases, unscaled embeddings, an untied head, no
multi-token-prediction head (the source row has none):

  c_q    = RMSNorm(h W_dq) g_q                       [q_lora_rank]
  q      = c_q W_uq                                  [H, nope + rope]
           = [q_nope | q_rope], q_rope rotated
  [c|k_r]= h W_dkv                                   [kv_lora_rank + rope]
  c_kv   = RMSNorm(c) g_kv;  k_rope = RoPE(k_r), ONE vector all heads share
           THE CACHE HOLDS [c_kv | k_rope] a token a layer, after the norm
           and the rotation (512 + 64 = 576 values at the published widths)
  expanded (the program's prefill, and this reference):
           [k_nope_i | v_i] = c_kv W_ukv,i           [nope + v_head_dim] a head
           score_i = (q_nope_i . k_nope_i + q_rope_i . k_rope) * scale
           o = concat_i(softmax(score_i) v_i) W_o    W_o [H * v_head_dim, D]
  absorbed (the program's decode and suffix prefill, against pages):
           q~_i = q_nope_i W_uk,i^T                  [kv_lora_rank]
           score_i = (q~_i . c_kv + q_rope_i . k_rope) * scale
           u_i = sum_t p_t c_kv,t;  o_i = u_i W_uv,i   (the same mathematics)
  scale  = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
           (YaRN as this family applies it: ``mscale`` = ``mscale_all_dim``,
           so cos and sin are NOT scaled and m^2 multiplies the scores)
  RoPE   over the ``qk_rope_head_dim`` decoupled values only, theta
           ``rope_theta``, YaRN (``rope_scaling``: factor over
           original_max_position_embeddings, beta_fast / beta_slow); the
           rotated values pair HALF-SPLIT (value i with value i + rope/2).
           The source pairs them interleaved: under random weights that is a
           permutation of W_uq's and W_dkv's rope columns (``assumed``).
  a      = x + o;  n2 = RMSNorm(a) g_mlp
  ff     = Wd(silu(Wg n2) * (Wu n2))       the ``first_k_dense_replace`` leading
                                           layers, width ``intermediate_size``
         = Shared(n2) + Routed(n2)         every later layer
  Routed   s = sigmoid(n2 W_r), float32, over all ``n_routed_experts_published``
           experts; chosen = the ``num_experts_per_tok`` largest (``topk_method``
           "none": no grouping and no correction bias, ``assumed``); w =
           s[chosen] / sum(s[chosen]) (``norm_topk_prob``) * routed_scaling_factor;
           sum over the chosen experts HELD HERE of w_e SwiGLU_e(n2): nothing
           stands in for the absent ones, here and in the program alike
  Shared   one SwiGLU of width n_shared_experts * moe_intermediate_size, weight 1
  y      = a + ff;  logits = (RMSNorm(x_L) g_f) W_head

The reference below is that, in plain ``jax.numpy`` float32 at ``highest``,
in the EXPANDED form with no cache; it reads only the parameter arrays (names
and layouts of ``init_params``) and the model config as a dict, and derives
rope, YaRN and routing itself. It runs UNDER THE STEP'S ROUTING and checks
it, as the AFMoE block's does and for its reason (``models/afmoe.py``).
"""

from __future__ import annotations

import math

kernel_paths = {"decode": 1, "prefill": 0}

# How far (absolute, in the sigmoid score s) a chosen expert may lie under the
# reference's k-th largest, or an unchosen one over it: the AFMoE block's
# limit, for the same scoring. Read on the chip (TPU v5 lite, PR 42, the
# cell's configuration at the timed sizes: 8 rows, prompts of 247-952 tokens
# at the 1,024 bucket, three decoded positions; PERF.md section 6): the
# largest such distance a seed was 0.0088-0.0092 (seeds 3000004201-202), the
# two sides choosing another set in 10.1-10.3% of the 35,448-39,683 (sparse
# layer, position) pairs a seed; every run of the cell since is a seed more
# that stayed under it (a row past it reads NaN, which never passes). MARGIN
# lies between the two readings the contract asks for: 2.2 times the largest
# sound distance, and 3.0 times under what a step in the next precision below
# reads: the int8-weights control (the weights rounded in place, judged not
# correct by ``reference.compare_with_engine_step`` itself:
# ``tests/test_axk1_readings.py``, seed 3000004201) 0.0600, another set in 54%
# of the pairs, rms 0.100 against 0.02 and max 0.409 against 0.12: it fails
# each of the three limits.
MARGIN = 0.02
ROUTING_READ = {"largest_distance": 0.0092, "flip_share": (0.101, 0.103),
                "int8_control_smallest_distance": 0.0600}

# Switches of the negative controls (tests set them; a benchmark run never does).
CONTROLS = {"follow_step_routing": True}

# Tokens a prefill cohort holds at most (EngineConfig.max_prefill_tokens): the
# comparison's prefill runs in cohorts of that size, as the engine's does.
PREFILL_TOKENS = 4096


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
    "qk_nope_head_dim": "head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "intermediate_size": "d_ff",  # the leading dense layer's width
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "first_k_dense_replace": "n_dense_layers",
    "n_routed_experts_published": "n_experts",  # the router's width
    "n_routed_experts": "experts_held",  # this chip's share of them
    "expert_first": "expert_first",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "d_expert",
    "routed_scaling_factor": "router_scale",
    "dtype": "dtype",  # not the source's: stated under ``assumed``
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "axk1",
    "hidden_act": "silu",
    "tie_word_embeddings": False,
    "attention_bias": False,
    "scoring_func": "sigmoid",
    "norm_topk_prob": True,
    "topk_method": "none",  # no grouping, no correction bias (``assumed``)
    "n_shared_experts": 1,
    "moe_layer_freq": 1,  # every layer after the dense lead is sparse
    # declared and unused under topk_method "none"; a training loss; the
    # checkpoint's own expert-parallel degree (the deployment is the file's)
    "n_group": 8, "topk_group": 4, "seq_aux": True, "ep_size": 1,
    # every head has its own keys and values once expanded; the cache holds none
    "num_key_value_heads": 64,
}


def yarn_score_factor(rope_scaling: dict) -> float:
    """m^2: what YaRN multiplies the softmax scale by in this family."""
    if rope_scaling["mscale"] != rope_scaling["mscale_all_dim"]:
        raise ValueError("rope_scaling: mscale != mscale_all_dim would scale cos and sin; not built")
    m = 0.1 * rope_scaling["mscale_all_dim"] * math.log(rope_scaling["factor"]) + 1.0
    return m * m


def mla_dims(config: dict, vocab_size: int) -> dict:
    """The configuration file's keys -> ``GemmaConfig`` fields. A key that is
    neither consumed nor a stated property of the block is an error."""
    if config["vocab_size"] != vocab_size:
        raise ValueError(
            f"config says vocab_size {config['vocab_size']}, the repo's tokenizer has {vocab_size}"
        )
    for key, value in _BLOCK_IS.items():
        if key not in config or config[key] != value:
            raise ValueError(f"{key}={config.get(key)!r}: this block is {value!r} and has no other")
    unknown = sorted(set(config) - set(_FIELDS) - set(_BLOCK_IS) - {"vocab_size", "rope_scaling"})
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    yarn = config["rope_scaling"]
    if yarn.get("type") != "yarn" or set(yarn) != {
        "type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
        "mscale", "mscale_all_dim",
    }:
        raise ValueError(f"rope_scaling {yarn!r}: this block's rope is YaRN with exactly these keys")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in ("norm_eps", "rope_theta", "router_scale"):
        dims[field] = float(dims[field])
    return dict(
        vocab_size=vocab_size, **dims, n_kv_heads=1, attention="latent",
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_pos=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]), yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=1.0, attn_score_factor=yarn_score_factor(yarn),
        d_shared_expert=int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
        router_scoring="sigmoid",
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if "attention" not in GemmaConfig.__dataclass_fields__:
        # A program from before this block: nothing to build it with.
        raise SystemExit("mla: this mcpx has no latent attention (GemmaConfig.attention)")
    return GemmaConfig(**mla_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (the dense lead layer and ONE sparse layer,
    16 experts of which experts 4..7 are held, top-2, a shared expert, YaRN
    over a 32-token original context so that the rehearsal's prompts lie past
    it): rehearsals and tests only. Two layers, not the other blocks' four or
    eight: on the CPU at this width the comparison reads 0.013-0.016 at two
    layers, 0.016-0.019 at three and 0.019-0.021 at four against the 0.02 of
    ``reference.tol`` (the chip at the published widths and 8 layers: 0.015)."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=2, n_heads=4, n_kv_heads=1, head_dim=32,
        d_ff=512, rope_theta=10000.0, norm_eps=1e-6, max_seq_len=2048,
        attention="latent", q_lora_rank=96, kv_lora_rank=64, qk_rope_head_dim=16, v_head_dim=32,
        yarn_factor=32.0, yarn_original_max_pos=32, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        attn_score_factor=yarn_score_factor({"mscale": 1, "mscale_all_dim": 1, "factor": 32}),
        n_experts=16, n_experts_per_tok=2, d_expert=128, expert_first=4, experts_held=4,
        n_dense_layers=1, d_shared_expert=128, router_scoring="sigmoid", router_scale=2.5,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block. The decode step is
    ``routing_record.py``'s (the program's paged decode through the latent
    kernel, its routing recorded by row). The prefill is the program's dense
    (expanded) prefill committed to the latent pages with its routing
    recorded, in cohorts of at most ``PREFILL_TOKENS`` tokens as the engine
    admits them: 8 rows at the 1,024 bucket are two cohorts of four, and one
    program over all eight would hold buffers the engine never does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
    from mcpx.models.gemma.model import init_kv_cache, prefill

    _, sys_decode = _ROUTING.step_functions(
        model_cfg, mesh, B=B, T=T, n_pages=n_pages, page_size=page_size, interpret=interpret
    )
    cohort = max(1, min(B, PREFILL_TOKENS // T))
    while B % cohort:
        cohort -= 1

    @jax.jit
    def prefill_j(params, tokens, lens, table, pools):
        dense = init_kv_cache(model_cfg, cohort, T)
        last, dense, chosen = prefill(
            params, model_cfg, tokens, lens, dense, last_only=True, routing=True
        )
        return last, commit_prefill_to_pages(pools, dense, table, lens, page_size), chosen

    def sys_prefill(params, tokens, lens, table):
        pools = jax.jit(lambda: init_paged_kv(model_cfg, n_pages, page_size))()
        lasts, routed, tokens_h, lens_h = [], [], np.asarray(tokens), np.asarray(lens)
        for lo in range(0, B, cohort):
            rows = slice(lo, lo + cohort)
            last, pools, chosen = prefill_j(params, tokens[rows], lens[rows], table[rows], pools)
            lasts.append(last)
            routed.append(chosen)  # [Ls, cohort, T, k]
        chosen = np.concatenate(jax.device_get(routed), axis=1)  # one fetch: [Ls, B, T, k]
        for b, n in enumerate(lens_h):
            _ROUTING.rows.append({"ids": tokens_h[b, :n], "chosen": chosen[:, b, :n]})
        return jnp.concatenate(lasts), pools

    return sys_prefill, sys_decode


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


def _yarn_inv_freq(dims: dict):
    """YaRN's inverse frequencies over the rotated values, from the published
    keys: a frequency whose wavelength fits ``beta_fast`` times or more into
    the original context stays, one that fits ``beta_slow`` times or fewer is
    divided by the factor, those between are ramped."""
    dim, theta = dims["qk_rope_head_dim"], dims["rope_theta"]
    orig, factor = dims["yarn_original_max_pos"], dims["yarn_factor"]

    def corr(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(dims["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(dims["yarn_beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append((1.0 - ramp) * plain + ramp * plain / factor)
    return out


def _reference(params, dims: dict, tokens):
    """-> (logits [T, V], the routing check's largest distance, the (sparse
    layer, position) pairs the step ran where the reference's own top-k is
    another set, the pairs the step ran)."""
    import jax
    import jax.numpy as jnp

    D, H, L, Ld = dims["d_model"], dims["n_heads"], dims["n_layers"], dims["n_dense_layers"]
    nope, rope, rkv, eps = dims["head_dim"], dims["qk_rope_head_dim"], dims["kv_lora_rank"], dims["norm_eps"]
    E, k = dims["n_experts"], dims["n_experts_per_tok"]
    first = dims["expert_first"]
    held = dims["experts_held"] or E
    group = math.gcd(held, 4)  # experts whose float32 copy is held at once
    f32 = jnp.float32
    T = tokens.shape[0]
    half = rope // 2
    scale = dims["attn_score_factor"] / math.sqrt(nope + rope)

    step_choice = _ROUTING.chosen_for(tokens, L - Ld, k, CONTROLS["follow_step_routing"])
    ang = jnp.arange(T, dtype=f32)[:, None] * jnp.asarray(_yarn_inv_freq(dims), f32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)  # [T, rope / 2]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def norm(x, gain):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * gain

    def rotate(t):  # [T, ..., rope], half-split pairs
        c, s = (a.reshape((T,) + (1,) * (t.ndim - 2) + (half,)) for a in (cos, sin))
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)

    def swiglu(n, w_gate, w_up, w_down):
        return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down

    def attention(x, lp):
        n1 = norm(x, lp["pre_attn_norm"])
        q = jnp.einsum("tr,rhe->the", norm(n1 @ lp["w_dq"], lp["q_lora_norm"]), lp["w_uq"])
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:])
        down = n1 @ lp["w_dkv"]
        c_kv, k_rope = norm(down[:, :rkv], lp["kv_lora_norm"]), rotate(down[:, rkv:])
        kv = jnp.einsum("tr,rhe->the", c_kv, lp["w_ukv"])  # expanded: a head's own keys and values
        k_nope, v = kv[..., :nope], kv[..., nope:]
        s = jnp.einsum("the,she->hts", q_nope, k_nope) + jnp.einsum("the,se->hts", q_rope, k_rope)
        s = jnp.where(causal[None], s * scale, -jnp.inf)
        o = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        return x + jnp.einsum("the,hed->td", o, lp["wo"])

    def small(lp):  # everything of a layer but its feed-forward's wide matrices, in float32
        return {name: w.astype(f32) for name, w in lp.items() if name not in ("w_gate", "w_up", "w_down")}

    def dense_layer(x, lp):
        a = attention(x, small(lp))
        n2 = norm(a, lp["pre_mlp_norm"].astype(f32))
        # the wide feed-forward a quarter of its width at a time: its float32
        # copy whole would be 1.6 GB beside the served weights
        F = lp["w_gate"].shape[1]
        parts = math.gcd(F, 4)
        cols = lambda w: w.reshape(D, parts, F // parts).transpose(1, 0, 2)

        def part(acc, ws):
            w_gate, w_up, w_down = (w.astype(f32) for w in ws)
            return acc + swiglu(n2, w_gate, w_up, w_down), None

        ff, _ = jax.lax.scan(
            part, jnp.zeros((T, D), f32),
            (cols(lp["w_gate"]), cols(lp["w_up"]), lp["w_down"].reshape(parts, F // parts, D)),
        )
        return a + ff, None

    def sparse_layer(carry, xs):
        x, distance, flipped, checked = carry
        lp, choice = xs
        sm = small(lp)
        a = attention(x, sm)
        n2 = norm(a, sm["pre_mlp_norm"])
        s = jax.nn.sigmoid(n2 @ sm["router"])  # [T, E]: what is compared, and what weighs
        own_s, own = jax.lax.top_k(s, k)
        ran = choice[:, 0] >= 0  # the positions the step ran
        idx = jnp.where(ran[:, None], choice, own)
        sel = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)  # [T, E]
        # The check: no chosen expert far under the reference's k-th score,
        # no unchosen one far over it.
        kth = own_s[:, k - 1]
        under = kth - jnp.min(jnp.where(sel, s, jnp.inf), axis=-1)
        over = jnp.max(jnp.where(sel, -jnp.inf, s), axis=-1) - kth
        distance = jnp.maximum(distance, jnp.max(jnp.where(ran, jnp.maximum(under, over), 0.0)))
        own_sel = jnp.any(own[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
        flipped += jnp.sum(ran & jnp.any(sel != own_sel, axis=-1))
        checked += jnp.sum(ran)
        w = jnp.where(sel, s, 0.0)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)  # norm_topk_prob, over all chosen
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
        return (a + ff, distance, flipped, checked), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens]  # unscaled
        zero = jnp.asarray(0, jnp.int32)
        # scans only to take one layer's weights at a time
        x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
        (x, distance, flipped, checked), _ = jax.lax.scan(
            sparse_layer, (x, jnp.asarray(0.0, f32), zero, zero), (params["layers"], step_choice),
        )
        logits = norm(x, params["final_norm"].astype(f32)) @ params["head"].astype(f32)
    return logits, distance, flipped, checked
