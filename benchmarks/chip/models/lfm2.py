"""The LFM2-MoE block (LiquidAI/LFM2-24B-A2B, ``model_type`` ``lfm2_moe``), as a
configuration's block module: the bridge from the published keys to the
program's model-config object, the block's plain reference, and the program's
step of the comparison.

A layer is a MIXER followed by a FEED-FORWARD, each ``x + f(RMSNorm(x))`` with
``RMSNorm(x) = g * x / sqrt(mean(x^2) + norm_eps)`` (a plain gain drawn 1):

  h0     = Embed[token]                                   (not scaled)
  h     += mixer(RMSNorm_op(h));  h += ffn(RMSNorm_ffn(h))
  logits = Embed^T RMSNorm_out(h)                         (tied)

  ``conv`` mixer (D hidden, K = conv_L_cache = 3), on its normed input n:
      [b | c | x] = n W_in           W_in [D, 3 D], the thirds in that order
      u_t = b_t (.) x_t
      v_t = sum_{k=0..K-1} w[:, k] (.) u_{t-K+1+k}     depthwise, causal, u
                                      before the sequence = 0, no bias
                                      (conv_bias false), NO activation
      y_t = W_out (c_t (.) v_t)
    The layer's whole state after token t is (u_{t-1}, u_t).
  ``full_attention`` mixer: q = RMSNorm_hd(n W_q) a head, k = RMSNorm_hd(n W_k)
      (one gain of head_dim each), THEN rope (theta, every value of the head,
      half-split: value i pairs with value i + hd/2), v = n W_v; causal
      softmax attention at 1/sqrt(hd), H/K query heads a KV head; y = W_o
      attention. No bias, no gate.
  dense ffn (the num_dense_layers leading layers): W_2 (silu(W_1 n) (.) W_3 n)
  routed ffn (every later layer): s = sigmoid(n W_r), float32, over all
      experts; the num_experts_per_tok largest of s + bias are chosen (ties to
      the lower expert); weights s_e / (sum of the chosen s + 1e-6)
      (norm_topk_prob), x routed_scaling_factor; y = sum_e weight_e W_2e
      (silu(W_1e n) (.) W_3e n). No shared expert.

What ``config.json`` does not carry is the family's published model code
(transformers ``modeling_lfm2_moe.py``) and stands under ``assumed`` in the
configuration file: tied embeddings, the thirds' order, the ``1e-6``, the bias
entering the choice alone, q/k normed before the rope.

The reference below is that, in plain ``jax.numpy`` float32 at ``highest``: no
cache, no kernel, no page, no tail (the convolution reads the whole sequence's
``u``), every expert held computed densely, one at a time, and weighted by the
routing. It reads only the parameter arrays (names and layouts of
``init_params``: ``conv_layers`` / ``attn_layers`` a mixer kind, ``dense_layers``
/ ``layers`` a feed-forward kind, each a row a layer of its kind in layer
order) and the model config as a dict.

The program's conv mixer runs in float32 between its two weight matrices
(``mcpx/models/gemma/ssm.py``: the float32 normed input read by ``W_in`` as two
bfloat16 operands, ``u``, the taps, the gate and the TAIL float32): precision
above the stated bfloat16, never below. The reference knows nothing of it.

**Departures of the reference from the published code, all of layout:** the
heads' projections are stored merged ([D, H hd]); ``W_in`` is one [D, 3 D]
matrix as published; the experts are stacked [E, D, F].

**The comparison runs the reference under the step's routing** (PERF.md Open
question 20, as ``models/afmoe.py``): the top-4 of 64 sigmoid scores flips
between bfloat16 and float32 wherever the 4th and 5th ``s + bias`` are close.
``step_functions`` records what every compared position chose;
``reference_logits`` uses the step's choice where the step ran and CHECKS it on
``s + bias`` within MARGIN; a row that breaks the check gets NaN logits, which
never pass. With no record (the CPU tests' direct calls) it keeps its own
``top_k``.

**The step of the comparison takes the route a radix hit takes.** Its prefill
is the engine's two programs: every row's whole-prompt prefill, which commits
keys, values and PAGE TAILS; and, for every second row, that prefill stops at
a page boundary ``P`` inside the prompt and a SUFFIX prefill
(``decode_chunk_paged(commit=True)``) starts every ``conv`` layer from
``tails[:, page_table[row, P/16 - 1]]``. Then decode windows of uneven live
widths whose proposals are wrong, so a row keeps one token of each
(``keep_window``). A step that starts a hit's row from zeros, or that keeps a
rejected slot's ``u``, fails the comparison (``CONTROLS``).
"""

from __future__ import annotations

import math

# The 64-lane heads through the ragged kernel on both routes: decode windows,
# and the suffix prefill a radix hit takes (engaged; its dispatches are
# reported, not required: distinct prompts share a page in a minority of rows).
# No state kernel: the convolution's gates, taps and tail are elementwise work
# XLA fuses between two products.
kernel_paths = {"decode": 1, "prefill": 0}

# How far (absolute, in s + bias) a chosen expert may lie under the reference's
# k-th largest, or an unchosen one over it (``models/afmoe.py``'s MARGIN, whose
# scores these are: sigmoid of a unit-variance logit plus a bias of N(0, 0.1^2)).
# Read on the chip (TPU v5 lite, PR 56, the slab's shape, 10 layers, every expert
# held, seeds 2147483704 and 3000005601-604; PERF.md section 6): the largest such
# distance a seed was 0.0051-0.0065, and the two sides chose another set in
# 2.6-2.8% of the 4,192-5,048 (routed layer, position) pairs a seed. MARGIN lies
# between the two readings the contract asks for: 3.1 times the largest sound
# distance, and 5 times under what a step in the next precision below reads: the
# int8-weights control at the cell's configuration (the weights rounded in
# place, judged not correct by ``reference.compare_with_engine_step`` itself:
# ``tests/test_lfm2_readings.py``) 0.103-0.129 (0.124 on the program as sent), with
# another set in 35-37% of the pairs, rms 0.18 against 0.02 and max 0.74-0.80 against 0.12: it fails each of
# the three limits. (The first form of the conv mixer, on the plain bfloat16
# recipe, read 0.0082-0.0123 here and rms 0.0214-0.0230: not ``correct`` by the
# logits' limit, sound by this one.)
MARGIN = 0.02
ROUTING_READ = {"largest_distance": 0.0065, "flip_share": (0.026, 0.028),
                "int8_control_smallest_distance": 0.103}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # False: the reference keeps its own top-k everywhere.
    "follow_step_routing": True,
    # False: a hit's row starts its conv layers from ZEROS, not its page's tail.
    "tail_at_hit": True,
    # True: a row keeps every slot of its decode window, the rejected ones' u too.
    "state_moves_by_the_window": False,
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
    "intermediate_size": "d_ff",  # the leading dense layers' width
    "norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "conv_L_cache": "conv_kernel",
    "num_dense_layers": "n_dense_layers",
    "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "moe_intermediate_size": "d_expert",
    "routed_scaling_factor": "router_scale",
    # not the source's: stated by the configuration file under ``assumed``
    "dtype": "dtype",
    "router_bias_scale": "router_bias_scale",
    "router_norm_eps": "router_norm_eps",
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "lfm2_moe",
    "conv_bias": False,
    "norm_topk_prob": True,
    "use_expert_bias": True,
    "tie_word_embeddings": True,
}
_MIXERS = {"conv": "C", "full_attention": "A"}
# Keys that say which experts of each sparse layer this chip holds; absent = all.
_SHARE = {"expert_first": "expert_first", "experts_held": "experts_held"}


def lfm2_dims(config: dict, vocab_size: int) -> dict:
    """The configuration file's keys -> ``GemmaConfig`` fields. A key that is
    neither consumed nor a stated property of the block is an error."""
    if config["vocab_size"] != vocab_size:
        raise ValueError(
            f"config says vocab_size {config['vocab_size']}, the repo's tokenizer has {vocab_size}"
        )
    for key, value in _BLOCK_IS.items():
        if key not in config or config[key] != value:
            raise ValueError(f"{key}={config.get(key)!r}: this block is {value!r} and has no other")
    known = set(_FIELDS) | set(_BLOCK_IS) | set(_SHARE) | {"vocab_size", "layer_types", "rope_parameters"}
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    rope = config["rope_parameters"]
    if rope.get("rope_type") != "default" or set(rope) != {"rope_theta", "rope_type"}:
        raise ValueError(f"rope_parameters {rope!r}: this block rotates by theta alone")
    n = int(config["num_hidden_layers"])
    if len(config["layer_types"]) != n or set(config["layer_types"]) - set(_MIXERS):
        raise ValueError("layer_types: conv or full_attention, one a layer")
    H, D = int(config["num_attention_heads"]), int(config["hidden_size"])
    if D % H:
        raise ValueError("head_dim is hidden_size / num_attention_heads (the source gives no other)")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in ("norm_eps", "router_scale", "router_bias_scale", "router_norm_eps"):
        dims[field] = float(dims[field])
    dims.update({field: int(config[key]) for key, field in _SHARE.items() if key in config})
    return dict(
        vocab_size=vocab_size, **dims, head_dim=D // H, rope_theta=float(rope["rope_theta"]),
        layer_pattern="".join(_MIXERS[t] for t in config["layer_types"]),
        router_scoring="sigmoid", qk_norm=True, rope_full_layers=True,
        activation="silu", tie_embeddings=True, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "conv_ffn"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("lfm2: this mcpx has no gated short-convolution mixer (GemmaConfig.conv_ffn)")
    return GemmaConfig(**lfm2_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (the cell's ten-layer pattern: 2 leading dense
    layers and two periods of attention + three convolutions, 8 experts top-2,
    heads of 64 two to a pool row): rehearsals and tests only. 256 wide, as
    the other ten-layer sparse block's rehearsal (``models/afmoe.py`` says why
    not 128)."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=10, n_heads=4, n_kv_heads=2, head_dim=64,
        d_ff=512, rope_theta=1000000.0, norm_eps=1e-5, max_seq_len=2048,
        layer_pattern="CCACCCACCC", conv_kernel=3, qk_norm=True,
        n_experts=8, n_experts_per_tok=2, d_expert=128, n_dense_layers=2,
        router_scoring="sigmoid", router_bias_scale=0.1, router_scale=1.0, router_norm_eps=1e-6,
        activation="silu", tie_embeddings=True, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block (this file's header): the
    whole-prompt prefill into pages, page tails and state slots; for every
    second row a suffix prefill from its last whole page's tail; then decode
    windows. What every compared position chose in every routed layer is
    recorded by row for ``reference_logits``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import (
        commit_prefill_tails, commit_prefill_to_pages, init_paged_kv, init_state_pool,
        write_prefill_state,
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
        state = write_prefill_state(
            init_state_pool(model_cfg, B, WINDOW, n_pages), rows, dense["ssm"]
        )
        state["tails"] = commit_prefill_tails(state["tails"], dense["ssm"], table, page_size)
        return last, {**pools, "state": state}, chosen

    @jax.jit
    def suffix_j(params, tokens, pos, table, pools, q_lens):
        if not CONTROLS["tail_at_hit"]:
            pools = {**pools, "state": {**pools["state"], "tails": jnp.zeros_like(pools["state"]["tails"])}}
        return decode_chunk_paged(
            params, model_cfg, tokens, pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.maximum(q_lens - 1, 0), q_lens=q_lens, routing=True,
            state_slots=(None, rows), commit=True,
        )

    @jax.jit
    def decode_j(params, window, pos, table, pools, q_lens, kept):
        logits, pools, chosen = decode_chunk_paged(
            params, model_cfg, window, pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.zeros((B,), jnp.int32), q_lens=q_lens, routing=True,
        )
        return logits, {**pools, "state": keep_window(pools["state"], rows, kept, q_lens > 0)}, chosen

    def sys_prefill(params, tokens, lens, table):
        calls[0] = 0
        tokens_h, lens_h = np.asarray(tokens), np.asarray(lens)
        # Every second row is a HIT at a page boundary inside its prompt: its
        # whole prefill stops there, and a suffix prefill goes on from the tail
        # of the last page before it.
        # (whole pages that leave the row a token: the deepest a match may go)
        whole = (lens_h - 1) // page_size
        hit = (np.arange(B) % 2 == 1) & (whole >= 1)
        depth = np.where(np.arange(B) % 4 == 1, whole, np.maximum(whole // 2, 1))
        at = np.where(hit, depth * page_size, lens_h).astype(np.int32)
        last, pools, chosen = prefill_j(params, tokens, jnp.asarray(at), table)
        suffix = np.zeros_like(tokens_h)
        for b in np.flatnonzero(hit):
            suffix[b, : lens_h[b] - at[b]] = tokens_h[b, at[b] : lens_h[b]]
        q_lens = np.where(hit, lens_h - at, 0).astype(np.int32)
        last2, pools, chosen2 = suffix_j(
            # (an idle row's window lies past its prompt, where its decode writes next)
            params, jnp.asarray(suffix), jnp.asarray(at), table, pools, jnp.asarray(q_lens),
        )
        chosen, chosen2 = np.asarray(chosen), np.asarray(chosen2)  # [Ls, B, T, k]
        for b, n in enumerate(lens_h):
            picked = np.concatenate([chosen[:, b, : at[b]], chosen2[:, b, : n - at[b]]], axis=1)
            _ROUTING.rows.append({"ids": tokens_h[b, :n], "chosen": picked})
        return jnp.where(jnp.asarray(hit)[:, None], last2, last), pools

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
        logits, pools, chosen = decode_j(
            params, jnp.asarray(window), pos, table, pools, jnp.asarray(q_lens), jnp.asarray(kept)
        )
        chosen = np.asarray(chosen)  # [Ls, B, WINDOW, k]: slot 0 is the token's
        for b, rec in enumerate(_ROUTING.rows):
            rec["ids"] = np.append(rec["ids"], tok_h[b])
            rec["chosen"] = np.concatenate([rec["chosen"], chosen[:, b, :1]], axis=1)
        return logits, pools

    return sys_prefill, sys_decode


def routing_readings(params, dims: dict) -> list[dict]:
    """What the routing check reads on each recorded row (the positions the
    last step ran): the largest distance, the (routed layer, position) pairs
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
    """-> (logits [T, V], the routing check's largest distance, the (routed
    layer, position) pairs the step ran where the reference's own top-k is
    another set, the pairs the step ran)."""
    import jax
    import jax.numpy as jnp

    D, H, K, hd = dims["d_model"], dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    L, eps, Kc = dims["n_layers"], dims["norm_eps"], dims["conv_kernel"]
    E, k = dims["n_experts"], dims["n_experts_per_tok"]
    Ld = dims["n_dense_layers"] if E else L
    first = dims["expert_first"]
    held = dims["experts_held"] or E
    f32 = jnp.float32
    T = tokens.shape[0]
    half = hd // 2

    # (the expert stacks whole: an expert is cut out where it is read, so the float32 copy held at
    # once is one expert's and never a layer's 1.2 GB)
    stacks = {name: params["layers"][name] for name in ("w_gate", "w_up", "w_down")} if E else {}
    step_choice = _ROUTING.chosen_for(tokens, L - Ld, k, CONTROLS["follow_step_routing"])
    inv_freq = jnp.asarray([dims["rope_theta"] ** (-2.0 * i / hd) for i in range(half)], f32)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    row = lambda stack, r: jax.tree.map(lambda w: w[r].astype(f32), stack)

    def norm(x, gain):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * gain

    def conv_mixer(n, lp):
        bcx = n @ lp["w_in"]
        b, c, x = bcx[:, :D], bcx[:, D : 2 * D], bcx[:, 2 * D :]
        u = jnp.pad(b * x, ((Kc - 1, 0), (0, 0)))  # u before the sequence is 0
        v = sum(lp["conv_w"][:, t] * u[t : t + T] for t in range(Kc))
        return (c * v) @ lp["w_out"]

    def attention(n, lp):
        def rope(t):  # [T, heads, hd]
            ang = jnp.arange(T, dtype=f32)[:, None] * inv_freq[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

        q = rope(norm((n @ lp["wq"]).reshape(T, H, hd), lp["q_norm"]))
        kk = rope(norm((n @ lp["wk"]).reshape(T, K, hd), lp["k_norm"]))
        v = (n @ lp["wv"]).reshape(T, K, hd)
        kk = jnp.repeat(kk, H // K, axis=1)  # each KV head serves H/K query heads
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("the,she->hts", q, kk) / math.sqrt(hd)
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        o = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(T, H * hd) @ lp["wo"]

    def routed(n2, lp_small, layer, choice, carry):
        distance, flipped, checked = carry
        s = jax.nn.sigmoid(n2 @ lp_small["router"])  # [T, E]
        pick = s + lp_small["router_bias"] if "router_bias" in lp_small else s
        own_pick, own = jax.lax.top_k(pick, k)  # ties to the lower expert
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
        w = jnp.where(sel, s, 0.0)  # the weights are the scores: the bias weighs nothing
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + dims["router_norm_eps"])  # norm_topk_prob
        w = (w * dims["router_scale"])[:, first : first + held]  # this chip's experts

        def expert(acc, e):  # ONE of the experts held, densely, cut out of the stacks where it is read
            # (two-dimensional products on the stacks' own layout, as the program's own loop: a
            # batched product over a group of experts makes the compiler transpose the WHOLE stack
            # ahead of the loop, a 3.2 GB copy each of W_1 and W_3)
            cut = lambda a: jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(f32)
            act = jax.nn.silu(n2 @ cut(stacks["w_gate"])) * (n2 @ cut(stacks["w_up"]))
            w_e = jax.lax.dynamic_index_in_dim(w, e, axis=1, keepdims=True)  # [T, 1]
            return acc + (act * w_e) @ cut(stacks["w_down"]), None

        out, _ = jax.lax.scan(expert, jnp.zeros((T, D), f32), jnp.arange(held))
        return out, (distance, flipped, checked)

    with jax.default_matmul_precision("highest"):
        h = params["embed"].astype(f32)[tokens]
        zero = jnp.asarray(0, jnp.int32)
        check = (jnp.asarray(0.0, f32), zero, zero)
        seen = {"C": 0, "A": 0}
        for layer, kind in enumerate(dims["layer_pattern"]):
            r = seen[kind]
            seen[kind] += 1
            lp = row(params["conv_layers" if kind == "C" else "attn_layers"], r)
            n = norm(h, lp["norm"])
            h = h + (conv_mixer(n, lp) if kind == "C" else attention(n, lp))
            if layer < Ld:
                fp = row(params["dense_layers"], layer)
                n2 = norm(h, fp["pre_mlp_norm"])
                h = h + (jax.nn.silu(n2 @ fp["w_gate"]) * (n2 @ fp["w_up"])) @ fp["w_down"]
            else:
                s = layer - Ld
                small = {
                    name: w[s].astype(f32) for name, w in params["layers"].items() if name not in stacks
                }
                out, check = routed(norm(h, small["pre_mlp_norm"]), small, s, step_choice[s], check)
                h = h + out
        logits = norm(h, params["final_norm"].astype(f32)) @ params["embed"].astype(f32).T
    return (logits,) + check
