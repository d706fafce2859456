"""The Nemotron-H block (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type`` ``nemotron_h``), as a configuration's block module: the bridge
from the published keys to the program's model-config object, the block's
plain reference, and the program's step of the comparison.

A layer is ONE of three things, by its letter in ``hybrid_override_pattern``,
``x <- x + f(RMSNorm(x) g)`` (D hidden; every norm a plain gain drawn 1, eps
``layer_norm_epsilon``; no bias but the convolution's; embeddings untied and
not scaled):

  M  Mamba-2 (H heads of P, G groups, N state, K taps; inner = H P):
       [z | xBC | dt] = n W_in        widths inner | inner + 2 G N | H
       xBC = silu(conv(xBC))          causal, depthwise, K taps, with bias
       x [H, P], B [G, N], C [G, N] = split(xBC); head h reads group h // (H/G)
       dt  = softplus(dt + dt_bias);  A = -exp(A_log), one scalar a head
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t     [H, P, N], float32
       y_t = h_t C_t + D_skip x_t
       f   = RMSNorm_groups(y * silu(z)) g_n W_out      the mean square over
             each group's inner / G values, one gain of inner
  E  the latent expert layer:
       s = sigmoid(n W_r), float32, over all experts; chosen = the
       num_experts_per_tok largest of (s + b); w = s[chosen] / (sum + 1e-20)
       (norm_topk_prob) x routed_scaling_factor
       l = n W_dn [moe_latent_size]; expert e: relu(l U_e)^2 V_e, NO gate
       f = (sum_e w_e expert_e(l)) W_up + relu(n S_u)^2 S_v   (the shared
           expert on the full width, every token, weight 1)
  *  attention: 32 query heads on 2 KV heads (16 : 1), softmax scale
     head_dim^-0.5, causal, NO rotation (position reaches the model through
     its state-space layers; ``rope_theta`` and ``partial_rotary_factor`` are
     in the source's config and read by nothing in its model code).
  logits = (RMSNorm(x_L) g_f) W_head

The multi-token-prediction module (``num_nextn_predict_layers`` 1,
``mtp_hybrid_override_pattern`` ``*E``) is stated and not built: it follows
the last layer and the main model's logits do not read it.

The reference below is that in plain ``jax.numpy`` float32 at ``highest``,
the recurrence TOKEN BY TOKEN (a ``lax.scan`` over positions that carries
``h``; the program computes it a chunk at a time, and a decode window as one
chunk from the stored state, ``mcpx/models/gemma/ssm.py``), every expert held
computed densely, one at a time. It reads only the parameter arrays (names
and layouts of ``init_params``: ``mamba_layers``, ``layers``,
``attn_layers``, a row a layer of the kind) and the model config as a dict.

**The comparison runs the reference under the step's routing**, as every
sparse block's does (``models/afmoe.py``): top-22 of 512 sigmoid scores plus
a bias flips between bfloat16 and float32 wherever the 22nd and 23rd lie
close. The check is on what is compared, ``s + b``, with ``MARGIN``.

**The step** (``step_functions``) is the program's own path at the timed
sizes: the dense prefill into pages AND state slots, then decode WINDOWS of 8
slots through ``ssm_window`` and the ragged kernel: ``[the token, 7
proposals that are wrong]`` with uneven ``q_lens`` (1..8 live slots, by row
and by step), of which every row keeps ONE token: its state has to move by
that one and not by the window. The logits compared are slot 0's. (The
comparison hands the step one token a call, so a row cannot keep more: a
window of which a row keeps some and not all is tier-1's,
``tests/test_ssm_block.py``.)

**The state's precision is held apart from the logits** (``STATE_COARSE``):
the configuration states a float32 recurrent state, and the logits of a block
whose activations are bfloat16 cannot see whether it is kept so.
"""

from __future__ import annotations

import math

kernel_paths = {"decode": 1, "prefill": 0, "ssm": 1}

# How far (absolute, in s + b) a chosen expert may lie under the reference's
# k-th largest, or an unchosen one over it. Absolute, as ``afmoe``'s MARGIN and
# for its reason (sigmoid scores plus a bias). Read on the chip (TPU v5 lite,
# PR 48, the slab's shape: 8 rows, prompts of 25-124 tokens at the 128 bucket,
# three decode windows; 11 layers, 128 of 512 experts held; seeds
# 3000004831-842; ``tests/test_nemotron_readings.py``, PERF.md section 6): the
# largest such distance a seed was 0.0063-0.0091, and the two sides chose
# another set of 22 in 16.0-19.1% of the 2,380-3,560 (expert layer, position)
# pairs a seed (top-22 of 512: the 22nd and 23rd lie closer than the 8th and
# 9th of 128). MARGIN lies between the two readings the contract asks for: 2.2
# times the largest sound distance, and 2.1 times under what a step in the next
# precision below reads: the int8-weights control (the weights rounded in
# place, judged not correct by ``reference.compare_with_engine_step`` itself,
# seed ...831) 0.0418, with another set in 72% of the pairs, rms 0.0716
# against 0.02 and max 0.297 against 0.12: it fails each of the three limits.
MARGIN = 0.02
ROUTING_READ = {"largest_distance": 0.0091, "flip_share": (0.160, 0.191),
                "int8_control_smallest_distance": 0.0418}

# The share of a row's recurrent-state values (its slots of every Mamba layer
# after the step's last window, zeros apart) whose lowest 8 mantissa bits are
# all 0: what the stored values say of the precision they were kept in. A
# float32 state reads 2^-8 = 0.0039 (the bits of a sum of float32 products are
# as good as drawn); a state that went through any type of 15 mantissa bits or
# fewer (bfloat16: 7, float16: 10) reads 1.0, however briefly it was widened
# again. Why this and not a distance: the state's DISTANCE from the
# reference's ``h`` is what bfloat16 activations make it, with or without
# such a rounding (the CPU rehearsal, 4 rows x 5 layers x 2 seeds, relative
# Frobenius distance after the third window: a sound step 0.0042-0.0050 in the
# first Mamba layer and 0.0104-0.0288 in the four after it; the state rounded
# to bfloat16 after every forward 0.0047-0.0054 and 0.0107-0.0266: no limit
# lies between), and the logits do not move at all (rms 0.02231 and 0.01615
# at the rehearsal's two seeds, with and without). Both readings of the limit, on the chip
# (TPU v5 lite, PR 48, ``tests/test_nemotron_readings.py``, seeds
# 3000004931-937; PERF.md section 2): a sound step 0.00648-0.00695 over 7
# seeds x 8 rows (the low bits are not quite uniform), the
# ``state_in_bfloat16`` control 1.0 on every row, not ``correct`` by this
# limit alone. The limit lies 7 times over the one and 20 under the other. A
# row over it reads NaN.
STATE_COARSE = 0.05
STATE_READ = {"sound_largest": 0.00695, "bfloat16_control_smallest": 1.0}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # False: the reference keeps its own top-k everywhere: a sound step fails.
    "follow_step_routing": True,
    # True: after each decode window the step says the row kept the whole live
    # window, not the one token it kept: the state moves by the window.
    "state_moves_by_the_window": False,
    # True: the step rounds every state slot to bfloat16 after each forward
    # (the precision below the one the configuration states for the state):
    # it moves no printed digit of the logits and fails STATE_COARSE.
    "state_in_bfloat16": False,
}

WINDOW = 8  # the decode window's slots, the engine's speculate_k


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
    "layer_norm_epsilon": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",  # read by nothing: the attention is unrotated (``assumed``)
    "mamba_num_heads": "mamba_n_heads",
    "mamba_head_dim": "mamba_head_dim",
    "n_groups": "mamba_n_groups",
    "ssm_state_size": "ssm_state_size",
    "conv_kernel": "conv_kernel",
    "chunk_size": "ssm_chunk_size",
    "time_step_min": "time_step_min",
    "time_step_max": "time_step_max",
    "time_step_floor": "time_step_floor",
    "n_routed_experts_published": "n_experts",  # the router's width
    "n_routed_experts": "experts_held",  # this chip's share of them
    "expert_first": "expert_first",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "d_expert",
    "moe_latent_size": "moe_latent_size",
    "moe_shared_expert_intermediate_size": "d_shared_expert",
    "routed_scaling_factor": "router_scale",
    # not the source's: stated by the configuration file under ``assumed``
    "dtype": "dtype",
    "router_bias_scale": "router_bias_scale",
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "nemotron_h",
    "attention_bias": False, "mlp_bias": False, "use_bias": False, "mamba_proj_bias": False,
    "use_conv_bias": True,
    "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2",
    "tie_word_embeddings": False,
    "norm_topk_prob": True,
    "n_shared_experts": 1,
    "sliding_window": None,
    "expand": 2,  # inner = expand x hidden = mamba_num_heads x mamba_head_dim
    "norm_eps": 1e-05,  # the same eps under its second name
    "intermediate_size": 2688,  # a dense MLP layer's width: the pattern has none ('-')
    # no group limit on the choice
    "n_group": 1, "topk_group": 1,
    # stated and read by nothing here: the rotation the model code does not
    # apply; the prediction module (not built); training's and an
    # implementation's choices
    "partial_rotary_factor": 1,
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
    "num_logits_to_keep": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "moe_shared_expert_overlap": False, "use_mamba_kernels": True,
}


def nemotron_dims(config: dict, vocab_size: int) -> dict:
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
    known = set(_FIELDS) | set(_BLOCK_IS) | {"vocab_size", "hybrid_override_pattern"}
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    n = int(config["num_hidden_layers"])
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != n or set(pattern) - set("ME*"):
        raise ValueError("hybrid_override_pattern: one of M, E, * for each of num_hidden_layers")
    if config["expand"] * config["hidden_size"] != config["mamba_num_heads"] * config["mamba_head_dim"]:
        raise ValueError("expand x hidden_size is not mamba_num_heads x mamba_head_dim")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in ("norm_eps", "rope_theta", "router_scale", "router_bias_scale",
                  "time_step_min", "time_step_max", "time_step_floor"):
        dims[field] = float(dims[field])
    return dict(
        vocab_size=vocab_size, **dims, layer_pattern=pattern, d_ff=0,
        router_scoring="sigmoid", rope_full_layers=False, activation="relu2",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "layer_pattern"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("nemotron_h: this mcpx has no layer pattern, recurrent state or latent experts (GemmaConfig)")
    return GemmaConfig(**nemotron_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (the pattern's own first 11 letters: 5
    Mamba, 5 expert layers of 16 experts top-3 in a 64-wide latent, 8 of them
    held, one attention layer): rehearsals and tests only. 256 wide, as
    ``afmoe``'s rehearsal and for its reason."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=11, n_heads=4, n_kv_heads=2, head_dim=64,
        d_ff=0, norm_eps=1e-5, max_seq_len=2048, layer_pattern="MEMEMEM*EME",
        mamba_n_heads=16, mamba_head_dim=32, mamba_n_groups=2, ssm_state_size=32, conv_kernel=4,
        ssm_chunk_size=32, n_experts=16, n_experts_per_tok=3, d_expert=96, expert_first=0,
        experts_held=8, d_shared_expert=192, moe_latent_size=64, router_scoring="sigmoid",
        router_bias_scale=0.1, router_scale=5.0, rope_full_layers=False, activation="relu2",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block: the dense prefill into
    pages and state slots, then decode windows (this file's header), with the
    routing output on; what every compared position chose in every expert
    layer is recorded by row for ``reference_logits``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import (
        commit_prefill_to_pages, init_paged_kv, init_state_pool, write_prefill_state,
    )
    from mcpx.engine.paged_decode import decode_chunk_paged, keep_window
    from mcpx.models.gemma.model import init_kv_cache, prefill

    rows = jnp.arange(B, dtype=jnp.int32)
    _ROUTING.rows.clear()
    calls = [0]

    @jax.jit
    def prefill_j(params, tokens, lens, table):
        dense = init_kv_cache(model_cfg, B, T)
        last, dense, chosen = prefill(
            params, model_cfg, tokens, lens, dense, last_only=True, routing=True
        )
        pools = init_paged_kv(model_cfg, n_pages, page_size)
        pools = commit_prefill_to_pages(pools, dense, table, lens, page_size)
        pools["state"] = write_prefill_state(init_state_pool(model_cfg, B, WINDOW), rows, dense["ssm"])
        return last, pools, chosen

    @jax.jit
    def decode_j(params, window, pos, table, pools, q_lens, kept):
        logits, pools, chosen = decode_chunk_paged(
            params, model_cfg, window, pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.zeros((B,), jnp.int32), q_lens=q_lens, routing=True,
        )
        state = keep_window(pools["state"], rows, kept, q_lens > 0)
        if CONTROLS["state_in_bfloat16"]:
            # (an explicit rounding: the compiler drops a cast to bfloat16 and back)
            low = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            state = {**state, "ssm": low(state["ssm"])}
        # what the rows' stored states say of their precision (STATE_COARSE)
        bits = jax.lax.bitcast_convert_type(state["ssm"][:, :B], jnp.uint32)  # [Lm, B, N, H P]
        held = jnp.sum(bits != 0, axis=(0, 2, 3))
        coarse = jnp.sum((bits != 0) & ((bits & 0xFF) == 0), axis=(0, 2, 3)) / jnp.maximum(held, 1)
        return logits, {**pools, "state": state}, chosen, coarse

    def sys_prefill(params, tokens, lens, table):
        calls[0] = 0
        last, pools, chosen = prefill_j(params, tokens, lens, table)
        chosen, tokens_h = np.asarray(chosen), np.asarray(tokens)  # [Ls, B, T, k]
        for b, n in enumerate(np.asarray(lens)):
            _ROUTING.rows.append({"ids": tokens_h[b, :n], "chosen": chosen[:, b, :n]})
        return last, pools

    def sys_decode(params, tok, pos, table, pools):
        i = calls[0]
        calls[0] += 1
        tok_h = np.asarray(tok)
        # Uneven live widths, 1..WINDOW by row and by step; the proposals
        # behind the token are wrong (another token of the vocabulary), so the
        # row keeps the token alone.
        q_lens = np.asarray([1 + (3 * b + 5 * i + 2) % WINDOW for b in range(B)], np.int32)
        wrong = (tok_h[:, None] + 1 + 7 * np.arange(1, WINDOW)[None, :] + i) % model_cfg.vocab_size
        window = np.concatenate([tok_h[:, None], wrong], axis=1).astype(np.int32)
        kept = q_lens if CONTROLS["state_moves_by_the_window"] else np.ones((B,), np.int32)
        logits, pools, chosen, coarse = decode_j(
            params, jnp.asarray(window), pos, table, pools, jnp.asarray(q_lens), jnp.asarray(kept)
        )
        chosen, coarse = np.asarray(chosen), np.asarray(coarse)  # [Ls, B, WINDOW, k]: slot 0 is the token's
        for b, rec in enumerate(_ROUTING.rows):
            rec["ids"] = np.append(rec["ids"], tok_h[b])
            rec["chosen"] = np.concatenate([rec["chosen"], chosen[:, b, :1]], axis=1)
            rec["state_coarse"] = float(coarse[b])  # the last window's stands
        return logits, pools

    return sys_prefill, sys_decode


def state_readings() -> list[float]:
    """STATE_COARSE's reading on each recorded row of the last step."""
    return [rec.get("state_coarse", 0.0) for rec in _ROUTING.rows]


def _state_coarse(tokens):
    """The reading of the recorded row whose tokens these begin with (0 for a
    sequence the step never saw). The records enter as constants, as the
    routing's do (``routing_record.RoutingRecord.chosen_for``)."""
    import jax.numpy as jnp
    import numpy as np

    T = tokens.shape[0]
    records = [r for r in _ROUTING.rows if len(r["ids"]) <= T and "state_coarse" in r]
    if not records:
        return jnp.asarray(0.0, jnp.float32)
    ids = np.full((len(records), T), -1, np.int32)
    for r, rec in enumerate(records):
        ids[r, : len(rec["ids"])] = rec["ids"]
    n = jnp.asarray([len(rec["ids"]) for rec in records], jnp.int32)
    same = jnp.all((tokens[None, :] == ids) | (jnp.arange(T)[None, :] >= n[:, None]), axis=1)
    score = jnp.where(same, n, -1)  # the longest recorded prefix of these tokens
    best = jnp.argmax(score)
    read = jnp.asarray([rec["state_coarse"] for rec in records], jnp.float32)[best]
    return jnp.where(score[best] > 0, read, 0.0)


def routing_readings(params, dims: dict) -> list[dict]:
    """What the routing check reads on each recorded row (the positions the
    last step ran): the largest distance, the (expert layer, position) pairs
    where the reference's own top-k is another set, and the pairs checked."""
    return _ROUTING.readings(lambda p, t: _reference(p, dims, t)[1:], params)


# ------------------------------------------------------------- the reference
def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded routing breaks the routing check, or its
    recorded state the precision the configuration states."""
    import jax.numpy as jnp

    logits, distance, _flipped, _checked = _reference(params, dims, tokens)
    sound = (distance <= MARGIN) & (_state_coarse(tokens) <= STATE_COARSE)
    return jnp.where(sound, logits, jnp.nan)


def _reference(params, dims: dict, tokens):
    """-> (logits [T, V], the routing check's largest distance, the (expert
    layer, position) pairs the step ran where the reference's own top-k is
    another set, the pairs the step ran)."""
    import jax
    import jax.numpy as jnp

    D, Hq, Kv, hd = dims["d_model"], dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    H, P, G, N = (dims["mamba_n_heads"], dims["mamba_head_dim"], dims["mamba_n_groups"],
                  dims["ssm_state_size"])
    taps, eps, pattern = dims["conv_kernel"], dims["norm_eps"], dims["layer_pattern"]
    E, k = dims["n_experts"], dims["n_experts_per_tok"]
    first, held = dims["expert_first"], dims["experts_held"] or dims["n_experts"]
    inner = H * P
    f32 = jnp.float32
    T = tokens.shape[0]

    step_choice = _ROUTING.chosen_for(tokens, pattern.count("E"), k, CONTROLS["follow_step_routing"])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def row(stack, r, x):
        """Layer ``r`` of a stack in float32, but for its routed experts. The
        barrier ties the casts to the activations they meet: the walk below
        is unrolled, and without it the compiler is free to make every
        layer's float32 copy at once (6.6 GB at the published widths)."""
        lp = {name: w[r] for name, w in stack.items() if name not in ("w_up", "w_down")}
        x, lp = jax.lax.optimization_barrier((x, lp))
        return x, {name: w.astype(f32) for name, w in lp.items()}

    relu2 = lambda a: jnp.square(jax.nn.relu(a))

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def mamba(x, lp):
        n = norm(x, lp["norm"])
        zxd = n @ lp["w_in"]
        z, xbc, dt = zxd[:, :inner], zxd[:, inner : inner + inner + 2 * G * N], zxd[:, -H:]
        # causal, depthwise: output t is taps over inputs t - taps + 1 .. t
        padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), f32), xbc])
        conv = lp["conv_b"] + sum(padded[t : t + T] * lp["conv_w"][:, t] for t in range(taps))
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :inner].reshape(T, H, P)
        bs = jnp.repeat(xbc[:, inner : inner + G * N].reshape(T, G, N), H // G, axis=1)  # [T, H, N]
        cs = jnp.repeat(xbc[:, inner + G * N :].reshape(T, G, N), H // G, axis=1)
        dt = jax.nn.softplus(dt + lp["dt_bias"])  # [T, H]
        a = -jnp.exp(lp["A_log"])

        def one_token(h, t):  # h [H, P, N]
            dt_t, x_t, b_t, c_t = t
            h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, c_t) + lp["D_skip"][:, None] * x_t

        _, y = jax.lax.scan(one_token, jnp.zeros((H, P, N), f32), (dt, xs, bs, cs))
        g = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return x + (g.reshape(T, inner) * lp["gate_norm"]) @ lp["w_out"]

    def attention(x, lp):
        n = norm(x, lp["norm"])
        q = (n @ lp["wq"]).reshape(T, Hq, hd)  # the leaves hold the heads merged, head-major
        kk = jnp.repeat((n @ lp["wk"]).reshape(T, Kv, hd), Hq // Kv, axis=1)
        v = jnp.repeat((n @ lp["wv"]).reshape(T, Kv, hd), Hq // Kv, axis=1)
        s = jnp.einsum("the,she->hts", q, kk) / math.sqrt(hd)
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        o = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        return x + o.reshape(T, Hq * hd) @ lp["wo"]

    def experts_layer(x, stack, r, choice, carry):
        distance, flipped, checked = carry
        x, lp = row(stack, r, x)
        n = norm(x, lp["norm"])
        s = jax.nn.sigmoid(n @ lp["router"])  # [T, E]
        pick = s + lp["router_bias"] if "router_bias" in lp else s
        own_pick, own = jax.lax.top_k(pick, k)
        ran = choice[:, 0] >= 0  # the positions the step ran
        idx = jnp.where(ran[:, None], choice, own)
        sel = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)  # [T, E]
        kth = own_pick[:, k - 1]
        under = kth - jnp.min(jnp.where(sel, pick, jnp.inf), axis=-1)
        over = jnp.max(jnp.where(sel, -jnp.inf, pick), axis=-1) - kth
        distance = jnp.maximum(distance, jnp.max(jnp.where(ran, jnp.maximum(under, over), 0.0)))
        own_sel = jnp.any(own[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
        flipped += jnp.sum(ran & jnp.any(sel != own_sel, axis=-1))
        checked += jnp.sum(ran)
        w = jnp.where(sel, s, 0.0)  # the weights are the scores: b weighs nothing
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = (w * dims["router_scale"])[:, first : first + held]  # this chip's experts
        lat = n @ lp["latent_down"] if "latent_down" in lp else n

        def one_expert(acc, e):  # every expert held, densely, its two matrices sliced out of the stacks
            take = lambda a: jax.lax.dynamic_slice(a, (r, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(f32)
            act = relu2(lat @ take(stack["w_up"])) * jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)
            return acc + act @ take(stack["w_down"]), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(lat), jnp.arange(held))
        if "latent_up" in lp:
            routed = routed @ lp["latent_up"]
        shared = relu2(n @ lp["shared_up"]) @ lp["shared_down"]
        return x + routed + shared, (distance, flipped, checked)

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens]
        zero = jnp.asarray(0, jnp.int32)
        carry = (jnp.asarray(0.0, f32), zero, zero)
        seen = {"M": 0, "E": 0, "*": 0}
        for kind in pattern:
            r = seen[kind]
            seen[kind] += 1
            if kind == "M":
                x = mamba(*row(params["mamba_layers"], r, x))
            elif kind == "*":
                x = attention(*row(params["attn_layers"], r, x))
            else:
                x, carry = experts_layer(x, params["layers"], r, step_choice[r], carry)
        logits = norm(x, params["final_norm"].astype(f32)) @ params["head"].astype(f32)
    return (logits,) + carry
