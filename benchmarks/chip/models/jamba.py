"""The Jamba block at ``num_experts`` 1 (ai21labs/AI21-Jamba2-3B, ``model_type``
``jamba``), as a configuration's block module: the bridge from the published
keys to the program's model-config object, the block's plain reference, and
the program's step of the comparison.

Residual stream (D hidden; every norm an RMSNorm with a plain gain drawn 1, eps
``rms_norm_eps``; embeddings tied, not scaled; NO position encoding anywhere):

  h_0 = E[token]
  a layer:  h += mixer(RMSNorm_in(h));   h += W_down(silu(W_gate n) (.) W_up n), n = RMSNorm_ff(h)
  logits = E^T RMSNorm_out(h_L)

in EVERY layer (``num_experts`` 1: ``expert_layer_period`` / ``offset`` are
declared and inert), and the mixer is one of two, attention where ``i %
attn_layer_period == attn_layer_offset`` (layers 7 and 21 of 28):

  Mamba (Mamba-1; I = mamba_expand x D inner, N = mamba_d_state, K =
  mamba_d_conv taps, R = mamba_dt_rank):
       [x | z] = n W_in                      W_in [D, 2 I], no bias, x first
       x_t = silu(b + sum_k w[:, k] (.) x_{t - K + 1 + k})   causal, depthwise,
                                             zeros before the sequence
       [r | B | C] = x W_x                   widths R | N | N
       r, B, C = RMSNorm_R(r), RMSNorm_N(B), RMSNorm_N(C)    a gain each
       dt = softplus(r W_dt + b_dt)          [I], float32
       A = -exp(A_log)                       [I, N] in the source; stored [N, I]
       h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
       y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]
       out = (y (.) silu(z)) W_out
  attention (H query heads on K = 1 KV head, head_dim = D / H): q, k, v = n
       W_q, n W_k, n W_v, no bias, no rotation, no norm; causal softmax at
       1 / sqrt(head_dim), all H query heads on the one KV head; out = W_o o.

What ``config.json`` does not carry and the family's model code
(``transformers``, ``modeling_jamba.py::JambaMambaMixer``) does is stated in
the configuration file under ``assumed``.

The reference below is that in plain ``jax.numpy`` float32 at ``highest``, the
recurrence TOKEN BY TOKEN (a ``lax.scan`` over positions that carries ``h``), no
cache, no kernel, no chunking, one unpadded sequence at a time. Its departures
from a layer-by-layer script, both of them about being a PROGRAM the chip can
hold and compile and neither about the arithmetic: (1) it ``lax.scan``s each
RUN of like layers over the layer's row in its stack (``M^7 A M^13 A M^6`` at the
published pattern), so it compiles a layer once a run; (2) a layer's weights
are cast to float32 INSIDE that loop, a layer at a time (the whole tree in
float32 is 11.5 GB beside the 5.7 GB it was cast from). It reads only the
parameter arrays (names and layouts of ``init_params``: ``scan_layers``,
``attn_layers``, a row a layer of the kind; ``A_log`` is stored ``[N, I]``)
and the model config as a dict.

**The step** (``step_functions``) is the program's own path at the timed
sizes: the dense prefill (the recurrence through ``selective_scan_prefill``)
into pages AND state slots, then decode WINDOWS of 8 slots through
``selective_scan_window`` and the ragged kernel: ``[the token, 7 proposals
that are wrong]`` with uneven ``q_lens`` (1..8 live slots, by row and by
step), of which every row keeps ONE token: its state has to move by that one
and not by the window. The logits compared are slot 0's.

**The state's precision is held apart from the logits** (``STATE_COARSE``),
as ``models/nemotron_h.py``'s and for its reason.
"""

from __future__ import annotations

import contextlib
import math

kernel_paths = {"decode": 1, "prefill": 0, "ssm": 1}

# The share of a row's recurrent-state values (its slots of every Mamba layer
# after the step's last window, zeros apart) whose lowest 8 mantissa bits are
# all 0 (``models/nemotron_h.py::STATE_COARSE``: a float32 state reads near
# 2^-8 = 0.0039, a state that went through bfloat16 reads 1.0, however briefly
# it was widened again). The logits cannot see it: on the CPU at the rehearsal's
# size the ``state_in_bfloat16`` control moves the comparison's rms by under a
# tenth of its limit (``tests/test_jamba_block.py``). Both readings of the
# limit, on the chip (TPU v5 lite, PR 58, the cell's configuration, all 28
# layers, at the timed sizes: 8 rows prefilled to 248-1,013 tokens at the 1,024
# bucket, three decode windows; ``benchmarks/chip/tests/test_jamba_readings.py``,
# seeds 3000005811-813; PERF.md section 6): a sound step 0.00652-0.00683 over 3
# seeds x 8 rows (the low bits of a sum of few products are not quite uniform,
# as nemotron's 0.0065-0.0070), the ``state_in_bfloat16`` control 1.0 on every
# row, not ``correct`` by this limit alone. The limit lies 7 times over the one
# and 20 under the other. A row over it reads NaN.
STATE_COARSE = 0.05
STATE_READ = {"sound_largest": 0.00683, "bfloat16_control_smallest": 1.0}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # True: after each decode window the step says the row kept the whole live
    # window, not the one token it kept: the rejected slots' tokens stay in h.
    "state_moves_by_the_window": False,
    # True: every decode window is run TWICE from where the row stands, the
    # row keeping its token each time: the token's commit lands in h twice.
    "pending_commit_twice": False,
    # True: the step rounds every state slot to bfloat16 after each forward
    # (the precision below the one the configuration states for the state), by
    # ``lax.reduce_precision`` (the compiler drops a cast to bfloat16 and back).
    "state_in_bfloat16": False,
    # True: the Mamba mixer's four matrices (``W_in``, ``W_x``, ``W_dt``,
    # ``W_out``) read their operand rounded ONCE to the weights' type, not as
    # the two operands of ``ssm.dot_split``: the precision below the float32
    # the configuration states between the mixer's matrices. Everything else
    # of the mixer (the convolution, the norms, the walk, the pool) stays
    # float32, so it is the NEAREST precision below.
    "mixer_in_bfloat16": False,
}

WINDOW = 8  # the decode window's slots, the engine's speculate_k

# Published key -> GemmaConfig field.
_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "rms_norm_eps": "norm_eps",
    "max_position_embeddings": "max_seq_len",
    "mamba_expand": "mamba_expand",
    "mamba_d_state": "ssm_state_size",
    "mamba_d_conv": "conv_kernel",
    "mamba_dt_rank": "mamba_dt_rank",
    "tie_word_embeddings": "tie_embeddings",
    "dtype": "dtype",  # not the source's: stated under ``assumed``
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "jamba",
    "hidden_act": "silu",
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "sliding_window": None,
    # ONE expert: every layer's feed-forward is the dense one, and the layers
    # that ``expert_layer_period`` / ``expert_layer_offset`` name are none
    "num_experts": 1, "num_experts_per_tok": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1,
    # stated and built by nothing: an implementation's choices
    "num_logits_to_keep": 1, "use_mamba_kernels": True,
}


def jamba_dims(config: dict, vocab_size: int) -> dict:
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
    known = set(_FIELDS) | set(_BLOCK_IS) | {"vocab_size", "attn_layer_period", "attn_layer_offset"}
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    n, period, offset = (int(config[k]) for k in ("num_hidden_layers", "attn_layer_period", "attn_layer_offset"))
    if not 0 <= offset < period or config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("attn_layer_offset lies inside attn_layer_period, and the heads divide hidden_size")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    dims["norm_eps"] = float(dims["norm_eps"])
    return dict(
        vocab_size=vocab_size, **dims,
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        layer_pattern="".join("Q" if i % period == offset else "J" for i in range(n)),
        activation="silu", rope_full_layers=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "mamba_dt_rank"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("jamba: this mcpx has no selective-scan layers (GemmaConfig.mamba_dt_rank)")
    return GemmaConfig(**jamba_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (two periods of ``M^2 A M``: attention at
    ``i % 4 == 2``; 5 query heads on ONE KV head, a group that is no power of
    two as the published 20 is none): rehearsals and tests only. 256 wide, as
    ``nemotron_h``'s rehearsal and for its reason."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=8, n_heads=5, n_kv_heads=1, head_dim=64,
        d_ff=512, norm_eps=1e-6, max_seq_len=2048, layer_pattern="JJQJJJQJ", mamba_expand=2,
        mamba_dt_rank=16, ssm_state_size=16, conv_kernel=4, activation="silu",
        rope_full_layers=False, tie_embeddings=True, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
# What the last step's rows ended with: {"ids" [n], "state_coarse"} a row.
_ROWS: list[dict] = []


def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block: the dense prefill into
    pages and state slots, then decode windows (this file's header); what each
    row's stored state says of its precision is recorded by row for
    ``reference_logits``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import (
        commit_prefill_to_pages, init_paged_kv, init_state_pool, write_prefill_state,
    )
    from mcpx.engine.paged_decode import decode_chunk_paged, keep_window
    from mcpx.models.gemma.model import init_kv_cache, prefill

    rows = jnp.arange(B, dtype=jnp.int32)
    _ROWS.clear()
    calls = [0]

    @contextlib.contextmanager
    def mixer_operands():  # in force where the step's programs are TRACED
        from mcpx.models.gemma import ssm

        def rounded_once(x32, w):
            info = jnp.finfo(w.dtype)
            hi = jax.lax.reduce_precision(x32, exponent_bits=info.nexp, mantissa_bits=info.nmant)
            return jnp.einsum("bte,ed->btd", hi.astype(w.dtype), w, preferred_element_type=jnp.float32)

        sound = ssm.dot_split
        if CONTROLS["mixer_in_bfloat16"]:
            ssm.dot_split = rounded_once
        try:
            yield
        finally:
            ssm.dot_split = sound

    @jax.jit
    def prefill_j(params, tokens, lens, table):
        dense = init_kv_cache(model_cfg, B, T)
        last, dense = prefill(
            params, model_cfg, tokens, lens, dense, last_only=True,
            use_pallas=True, interpret=interpret,
        )
        pools = init_paged_kv(model_cfg, n_pages, page_size)
        pools = commit_prefill_to_pages(pools, dense, table, lens, page_size)
        pools["state"] = write_prefill_state(init_state_pool(model_cfg, B, WINDOW), rows, dense["ssm"])
        return last, pools

    def decode(params, window, pos, table, pools, q_lens, kept):
        logits, pools = decode_chunk_paged(
            params, model_cfg, window, pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.zeros((B,), jnp.int32), q_lens=q_lens,
        )
        state = keep_window(pools["state"], rows, kept, q_lens > 0)
        if CONTROLS["state_in_bfloat16"]:
            low = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            state = {**state, "ssm": low(state["ssm"])}
        # what the rows' stored states say of their precision (STATE_COARSE)
        bits = jax.lax.bitcast_convert_type(state["ssm"][:, :B], jnp.uint32)  # [J layers, B, N, I]
        held = jnp.sum(bits != 0, axis=(0, 2, 3))
        coarse = jnp.sum((bits != 0) & ((bits & 0xFF) == 0), axis=(0, 2, 3)) / jnp.maximum(held, 1)
        return logits, {**pools, "state": state}, coarse

    decode_j = jax.jit(decode, donate_argnums=4)

    def sys_prefill(params, tokens, lens, table):
        calls[0] = 0
        with mixer_operands():
            last, pools = prefill_j(params, tokens, lens, table)
        tokens_h = np.asarray(tokens)
        for b, n in enumerate(np.asarray(lens)):
            _ROWS.append({"ids": tokens_h[b, :n]})
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
        args = (jnp.asarray(window), pos, table)
        for _ in range(2 if CONTROLS["pending_commit_twice"] else 1):
            with mixer_operands():
                logits, pools, coarse = decode_j(params, *args, pools, jnp.asarray(q_lens), jnp.asarray(kept))
        coarse = np.asarray(coarse)
        for b, rec in enumerate(_ROWS):
            rec["ids"] = np.append(rec["ids"], tok_h[b])
            rec["state_coarse"] = float(coarse[b])  # the last window's stands
        return logits, pools

    return sys_prefill, sys_decode


def state_readings() -> list[float]:
    """STATE_COARSE's reading on each recorded row of the last step."""
    return [rec.get("state_coarse", 0.0) for rec in _ROWS]


def _state_coarse(tokens):
    """The reading of the recorded row whose tokens these begin with (0 for a
    sequence the step never saw). The records enter as constants, as
    ``models/nemotron_h.py``'s do."""
    import jax.numpy as jnp
    import numpy as np

    T = tokens.shape[0]
    records = [r for r in _ROWS if len(r["ids"]) <= T and "state_coarse" in r]
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


# ------------------------------------------------------------- the reference
def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded state breaks the precision the configuration
    states."""
    import jax.numpy as jnp

    return jnp.where(_state_coarse(tokens) <= STATE_COARSE, _reference(params, dims, tokens), jnp.nan)


def _runs(pattern: str) -> list[tuple[str, int, int]]:
    """The pattern's runs of like layers: (letter, the run's first row in its
    kind's stack, one past its last)."""
    runs, seen = [], {}
    for kind in pattern:
        r = seen.get(kind, 0)
        seen[kind] = r + 1
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], r + 1)
        else:
            runs.append((kind, r, r + 1))
    return runs


def _reference(params, dims: dict, tokens):
    import jax
    import jax.numpy as jnp

    D, Hq, Kv, hd = dims["d_model"], dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    I, N, R = dims["mamba_expand"] * D, dims["ssm_state_size"], dims["mamba_dt_rank"]
    taps, eps = dims["conv_kernel"], dims["norm_eps"]
    f32 = jnp.float32
    T = tokens.shape[0]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def feed_forward(x, lp):
        n = norm(x, lp["mlp_norm"])
        return x + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) @ lp["w_down"]

    def mamba(x, lp):
        n = norm(x, lp["norm"])
        xz = n @ lp["w_in"]
        xs, z = xz[:, :I], xz[:, I:]
        # causal, depthwise: output t is taps over inputs t - taps + 1 .. t
        padded = jnp.concatenate([jnp.zeros((taps - 1, I), f32), xs])
        xs = jax.nn.silu(lp["conv_b"] + sum(padded[k : k + T] * lp["conv_w"][:, k] for k in range(taps)))
        rbc = xs @ lp["w_x"]
        r = norm(rbc[:, :R], lp["dt_norm"])
        bs, cs = norm(rbc[:, R : R + N], lp["b_norm"]), norm(rbc[:, R + N :], lp["c_norm"])
        dt = jax.nn.softplus(r @ lp["w_dt"] + lp["dt_bias"])  # [T, I]
        a = -jnp.exp(lp["A_log"])  # [N, I]: the leaf's layout

        def one_token(h, t):  # h [N, I]
            dt_t, x_t, b_t, c_t = t
            h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * x_t)[None, :] * b_t[:, None]
            return h, c_t @ h + lp["D_skip"] * x_t

        _, y = jax.lax.scan(one_token, jnp.zeros((N, I), f32), (dt, xs, bs, cs))
        return feed_forward(x + (y * jax.nn.silu(z)) @ lp["w_out"], lp)

    def attention(x, lp):
        n = norm(x, lp["norm"])
        q = (n @ lp["wq"]).reshape(T, Hq, hd)  # the leaves hold the heads merged, head-major
        k = jnp.repeat((n @ lp["wk"]).reshape(T, Kv, hd), Hq // Kv, axis=1)
        v = jnp.repeat((n @ lp["wv"]).reshape(T, Kv, hd), Hq // Kv, axis=1)
        s = jnp.einsum("the,she->hts", q, k) / math.sqrt(hd)
        s = jnp.where((j <= i)[None], s, -jnp.inf)
        o = jnp.einsum("hts,she->the", jax.nn.softmax(s, axis=-1), v)
        return feed_forward(x + o.reshape(T, Hq * hd) @ lp["wo"], lp)

    kinds = {"J": ("scan_layers", mamba), "Q": ("attn_layers", attention)}
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens]
        for kind, lo, hi in _runs(dims["layer_pattern"]):
            stack, layer = kinds[kind]

            def one_layer(x, r, stack=stack, layer=layer):
                # (a layer's weights in float32 a layer at a time: this file's header)
                lp = {name: jax.lax.dynamic_index_in_dim(w, r, keepdims=False).astype(f32)
                      for name, w in params[stack].items()}
                return layer(x, lp), None

            x, _ = jax.lax.scan(one_layer, x, jnp.arange(lo, hi))
        return norm(x, params["final_norm"].astype(f32)) @ params["embed"].astype(f32).T
