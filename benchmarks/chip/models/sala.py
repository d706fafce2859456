"""The MiniCPM-SALA block (openbmb/MiniCPM-SALA, ``model_type``
``minicpm_sala``), as a configuration's block module: the bridge from the
published keys to the program's model-config object, the block's plain
reference, and the program's step of the comparison.

Residual stream (D hidden; every norm an RMSNorm with a plain gain drawn 1,
eps ``rms_norm_eps``; no bias; embeddings untied), ``c = scale_depth /
sqrt(32)`` with the PUBLISHED 32 whatever depth is kept:

  h_0 = scale_emb x E[token]
  a layer:  h += c x mixer(RMSNorm(h));   h += c x W_down(silu(W_gate n) (.) W_up n), n = RMSNorm(h)
  logits = W_head (RMSNorm(h_L) / (hidden_size / dim_model_base))

and the mixer is one of two, by ``mixer_types``:

  lightning-attn (per head h of 32, d = 128):
       q, k = RMSNorm_d(n W_q), RMSNorm_d(n W_k), both rotated (theta 10,000,
       the halves paired);  v = n W_v
       S_t = lambda_h S_{t-1} + k_t^T v_t          S is [d, d], float32
       o_t = (q_t / sqrt(d)) S_t
       y   = W_o( sigmoid(n W_g) (.) RMSNorm_d(o) g_o )
       lambda_h = exp(-2^(-8 (h + 1) / 32)): ``assumed`` in the file.
  minicpm4 (32 query heads on 2 KV heads, G = 16, d = 128, NO rotation):
       Kc[j] = mean(K[16 j .. 16 j + 31]), visible to a query at t when 16 j + 31 <= t
       s[h, j] = softmax_j(q_h . Kc[j] / sqrt(d)) over the visible j
       S[j] = sum of s[h, j] over the 16 heads of the KV group
       B[b] = max S[4 b - 1 .. 4 b + 3]            a 64-token block's score
       block 0 and the 32 blocks reaching back 2,048 tokens from t forced; the
       query attends the tokens <= t of its 64 best blocks (ties to the lower
       block), one selection a (query, KV head), with softmax attention
       y = W_o( sigmoid(n W_g) (.) attention )
     A query that sees 64 blocks or fewer attends everything: dense attention.

Departures from the source, all of them in the configuration file too:
``dense_len`` (the source switches a REQUEST under 8,192 tokens to a dense
kernel; here the selection is per query, the same arithmetic under 4,096 and
for every request this cell serves); seeded random weights; the 3,072-token
vocabulary; 8 of 32 layers.

The reference below is that in plain ``jax.numpy`` float32 at ``highest``: the
linear recurrence TOKEN BY TOKEN (a ``lax.scan`` that carries ``S``; the
program computes it a chunk at a time and a decode window from a stored state,
``mcpx/models/gemma/ssm.py``), the selection by its definition, 128 queries at
a time, no cache, no kernel, no batching. It reads only the parameter arrays
(``linear_layers``, ``block_layers``: a row a layer of the kind) and the model
config as a dict.

**The comparison runs the reference under the step's selection**, as
``models/dsa.py`` runs its under the step's: the 64th and 65th block of ~200
lie closer than bfloat16 activations resolve, and a flipped block moves a
query's output by far more than rounding. The check is on what is compared,
the block scores ``B``, with ``SELECTION_MARGIN``.

**The step** (``step_functions``) is the program's own path at the timed
sizes: the prefill AS THE ENGINE BUILDS THE HEAD, chunks of 1,024 tokens
through the suffix route, every chunk handed the state of the one before,
keys and key sums into pages; then decode WINDOWS of 8 slots through the
state pool's kernel and the gathering attention: ``[the token, 7 proposals
that are wrong]`` with uneven ``q_lens``, of which every row keeps ONE token.

**The state's precision is held apart from the logits** (``STATE_COARSE``),
as ``models/nemotron_h.py``'s and for its reason.
"""

from __future__ import annotations

import math

kernel_paths = {"decode": 1, "prefill": 1, "ssm": 1, "gather": 1}

# How far (as a share of the reference's ``topk``-th largest block score of
# that (query, KV head)) a block the step's query READ may lie under that
# score, or one it did NOT read over it; a forced block left out reads
# infinity. Read on the chip (TPU v5 lite, PR 51, the cell's configuration at
# the timed sizes: 8 rows prefilled to 3,172-12,878 tokens in chunks of 1,024,
# three decode windows; ``benchmarks/chip/tests/test_sala_readings.py``; PERF.md section 6):
# SELECTION_READ below: the largest sound distance of each of six seeds
# (3000005111-116) was 0.0023-0.0032, and the two sides chose another set of 64
# in 4.8-7.8% of the 208,000-293,000 (sparse layer, position, KV head) triples
# a seed. The limit lies between the two readings the contract asks for: 2.5
# times the largest sound distance, and 2.5 times under what a step in the next
# precision below reads (the int8-weights control, seed ...111: 0.0200, another
# set in 23.9%; the comparison judges it not correct by its logits, rms 0.137
# against 0.02 and max 0.49 against 0.12). The controls of the selection itself:
# only a query's own block forced reads infinity (a forced block left out;
# another set in 43.8%), the state moved by the window 0.0772.
SELECTION_MARGIN = 0.008
SELECTION_READ = {"largest_distance": 0.0032, "flip_share": (0.048, 0.078), "int8_control_distance": 0.0200,
                  "wrong_blocks_control": float("inf"), "state_moves_by_the_window_control": 0.0772}

# The share of a row's recurrent-state values whose lowest 8 mantissa bits are
# all 0 (``models/nemotron_h.py::STATE_COARSE``: a float32 state reads near
# 2^-8, a state that went through bfloat16 reads 1.0). Read on the chip (PR 51,
# the same six seeds x 8 rows): a sound step 0.00775-0.00801 (twice 2^-8: the
# low bits of a sum of few products are not uniform; nemotron's reads 0.0065-
# 0.0070), the ``state_in_bfloat16`` control 1.0 on every row, not correct by
# this limit alone (its logits read rms 0.0126, as the sound step's). The
# limit lies 6 times over the one and 20 under the other.
STATE_COARSE = 0.05
STATE_READ = {"sound_largest": 0.00801, "bfloat16_control_smallest": 1.0}

# Switches of the negative controls (tests and the builder's chip script set
# them; a benchmark run never does).
CONTROLS = {
    # False: the reference keeps its own selection everywhere: a sound step fails.
    "follow_step_selection": True,
    # True: the step forces only a query's OWN block, not the 32 reaching back
    # 2,048 tokens: it reads the wrong blocks.
    "wrong_blocks": False,
    # True: after each decode window the step says the row kept the whole live
    # window, not the one token it kept: the state moves by the window.
    "state_moves_by_the_window": False,
    # True: the step rounds every state slot to bfloat16 after each forward.
    "state_in_bfloat16": False,
}

WINDOW = 8  # the decode window's slots, the engine's speculate_k
# The longest window the block's prefill takes in ONE dispatch: what
# ``GemmaConfig.max_seq_len`` says to the engine (``models/dsa.py``). A longer
# head is built in chunks of it, and so is the comparison's prefill.
PREFILL_WINDOW = 1024
CHUNK = PREFILL_WINDOW

_MIXERS = {"minicpm4": "S", "lightning-attn": "L"}
_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
           "init_blocks": 1, "window_size": 2048, "dense_len": 8192}

# Published key -> GemmaConfig field.
_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "scale_emb": "embed_scale",
    "dtype": "dtype",  # not the source's: stated under ``assumed``
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "minicpm_sala",
    "attention_bias": False,
    "attn_use_rope": False,
    "hidden_act": "silu",
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)",
    "lightning_use_rope": True,
    "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
    "rand_init": False,
    "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
    "max_position_embeddings": 524288,
}


class SelectionRecord:
    """What every position the step ran read, by row of the last comparison:
    {"ids" [n], "bits" [S layers, n, K, blocks / 8] uint8: a bit a block, most
    significant first, "state_coarse": STATE_COARSE's reading}."""

    def __init__(self) -> None:
        self.rows: list[dict] = []

    def read_for(self, tokens, shape: tuple, follow: bool = True):
        """-> (bits [S layers, T, K, blocks / 8] of the recorded row whose ids
        are these tokens' head, the positions recorded [scalar]: 0 for a
        sequence the step never saw or with ``follow`` off; that row's state
        reading). The records enter as constants."""
        import jax.numpy as jnp
        import numpy as np

        Ls, T, K, width = shape
        records = [r for r in self.rows if len(r["ids"]) <= T] if follow else []
        zero = jnp.asarray(0.0, jnp.float32)
        if not records:
            coarse = max((r.get("state_coarse", 0.0) for r in self.rows), default=0.0)
            return jnp.zeros(shape, jnp.uint8), jnp.asarray(0, jnp.int32), zero + coarse
        ids = np.full((len(records), T), -1, np.int32)
        bits = np.zeros((len(records),) + shape, np.uint8)
        for r, rec in enumerate(records):
            n = len(rec["ids"])
            ids[r, :n] = rec["ids"]
            w = min(width, rec["bits"].shape[-1])
            bits[r, :, :n, :, :w] = rec["bits"][..., :w]
        n = jnp.asarray([len(rec["ids"]) for rec in records], jnp.int32)
        same = jnp.all((tokens[None, :] == ids) | (jnp.arange(T)[None, :] >= n[:, None]), axis=1)
        score = jnp.where(same, n, -1)
        best = jnp.argmax(score)
        coarse = jnp.asarray([rec.get("state_coarse", 0.0) for rec in records], jnp.float32)[best]
        seen = score[best] > 0
        return jnp.asarray(bits)[best], jnp.where(seen, n[best], 0), jnp.where(seen, coarse, 0.0)


_SELECTION = SelectionRecord()


def sala_dims(config: dict, vocab_size: int) -> dict:
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
    known = set(_FIELDS) | set(_BLOCK_IS) | {
        "vocab_size", "mixer_types", "mixer_types_published", "sparse_config",
    }
    unknown = sorted(set(config) - known)
    if unknown:
        raise ValueError(f"architectural key(s) {unknown} are consumed by nothing in this block")
    if config["sparse_config"] != _SPARSE:
        raise ValueError(f"sparse_config {config['sparse_config']}: this block is {_SPARSE}")
    mixers = config["mixer_types"]
    if len(mixers) != int(config["num_hidden_layers"]) or set(mixers) - set(_MIXERS):
        raise ValueError("mixer_types: minicpm4 or lightning-attn for each of num_hidden_layers")
    if config["head_dim"] != config["lightning_head_dim"] or config["num_attention_heads"] != config["lightning_nh"]:
        raise ValueError("the two mixers share their heads and head size in this block")
    dims = {field: config[key] for key, field in _FIELDS.items()}
    for field in ("norm_eps", "rope_theta", "embed_scale"):
        dims[field] = float(dims[field])
    sp = config["sparse_config"]
    return dict(
        vocab_size=vocab_size, **dims, max_seq_len=PREFILL_WINDOW,
        layer_pattern="".join(_MIXERS[m] for m in mixers),
        block_size=sp["block_size"], block_topk=sp["topk"], block_init=sp["init_blocks"],
        block_window=sp["window_size"], pool_stride=sp["kernel_stride"],
        # the PUBLISHED depth under the root, whatever the file keeps
        residual_scale=float(config["scale_depth"]) / math.sqrt(config["mup_denominator"]),
        logit_divisor=float(config["hidden_size"]) / config["dim_model_base"],
        attn_gate=True, activation="silu", rope_full_layers=True,
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if not hasattr(GemmaConfig, "block_topk"):
        # A program from before this block: nothing to build it with.
        raise SystemExit("sala: this mcpx has no linear-attention or block-selecting layers (GemmaConfig)")
    return GemmaConfig(**sala_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (two periods of [S, L, L, L], 4 heads of 32
    on 2 KV heads, blocks of 64 of which a query keeps 4, one whole and the 2
    reaching back 128 tokens forced): rehearsals and tests only."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=512, norm_eps=1e-6, max_seq_len=256, layer_pattern="SLLLSLLL", qk_norm=True,
        attn_gate=True, block_size=64, block_topk=4, block_init=1, block_window=128, pool_stride=16,
        ssm_chunk_size=64, embed_scale=12.0, residual_scale=1.4 / math.sqrt(32), logit_divisor=1.0,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block (this file's header): the
    prefill in chunks through the suffix route, then decode windows, with the
    selection recorded by row for ``reference_logits``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import init_paged_kv, init_state_pool
    from mcpx.engine.paged_decode import decode_chunk_paged, keep_window

    cfg = model_cfg
    if CONTROLS["wrong_blocks"]:
        cfg = dataclasses.replace(cfg, block_window=cfg.block_size)
    chunk = min(CHUNK, T)
    rows = jnp.arange(B, dtype=jnp.int32)
    Ls, K = cfg.n_block_layers, cfg.n_kv_heads
    _SELECTION.rows.clear()
    calls = [0]

    def prefill_chunk(params, tokens, pos, table, pools, q_lens):
        return decode_chunk_paged(
            params, cfg, tokens, pos, table, pools, use_pallas=True, interpret=interpret,
            mesh=mesh, logits_at=jnp.maximum(q_lens - 1, 0), q_lens=q_lens, selection=True,
            state_slots=(rows, rows), commit=True,
        )

    prefill_j = jax.jit(prefill_chunk, donate_argnums=4)

    def decode(params, window, pos, table, pools, q_lens, kept):
        logits, pools, bits = decode_chunk_paged(
            params, cfg, window, pos, table, pools, use_pallas=True, interpret=interpret,
            mesh=mesh, logits_at=jnp.zeros((B,), jnp.int32), q_lens=q_lens, selection=True,
        )
        state = keep_window(pools["state"], rows, kept, q_lens > 0)
        if CONTROLS["state_in_bfloat16"]:
            low = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
            state = {**state, "ssm": low(state["ssm"])}
        held = jax.lax.bitcast_convert_type(state["ssm"][:, :B], jnp.uint32)  # [Ll, B, d, H d]
        n_held = jnp.sum(held != 0, axis=(0, 2, 3))
        coarse = jnp.sum((held != 0) & ((held & 0xFF) == 0), axis=(0, 2, 3)) / jnp.maximum(n_held, 1)
        return logits, {**pools, "state": state}, bits, coarse

    decode_j = jax.jit(decode, donate_argnums=4)

    def sys_prefill(params, tokens, lens, table):
        calls[0] = 0
        pools = jax.jit(lambda: {
            **init_paged_kv(model_cfg, n_pages, page_size),
            "state": init_state_pool(model_cfg, B, WINDOW, n_pages),
        })()
        tokens_h, lens_h = np.asarray(tokens), np.asarray(lens)
        last, read = None, []
        for start in range(0, int(lens_h.max()), chunk):
            live = np.clip(lens_h - start, 0, chunk).astype(np.int32)
            logits, pools, bits = prefill_j(
                params, tokens[:, start : start + chunk], jnp.full((B,), start, jnp.int32), table,
                pools, jnp.asarray(live),
            )
            read.append(bits)  # [Ls, B, S, K, blocks / 8]
            ends = jnp.asarray((live > 0) & (lens_h <= start + chunk))
            last = logits if last is None else jnp.where(ends[:, None], logits, last)
        read = np.concatenate(jax.device_get(read), axis=2)  # one fetch: every chunk's slots in order
        for b in range(B):
            _SELECTION.rows.append({"ids": tokens_h[b, : lens_h[b]], "bits": read[:, b, : lens_h[b]]})
        return last, pools

    def sys_decode(params, tok, pos, table, pools):
        i = calls[0]
        calls[0] += 1
        tok_h = np.asarray(tok)
        q_lens = np.asarray([1 + (3 * b + 5 * i + 2) % WINDOW for b in range(B)], np.int32)
        wrong = (tok_h[:, None] + 1 + 7 * np.arange(1, WINDOW)[None, :] + i) % model_cfg.vocab_size
        window = np.concatenate([tok_h[:, None], wrong], axis=1).astype(np.int32)
        kept = q_lens if CONTROLS["state_moves_by_the_window"] else np.ones((B,), np.int32)
        logits, pools, bits, coarse = decode_j(
            params, jnp.asarray(window), pos, table, pools, jnp.asarray(q_lens), jnp.asarray(kept)
        )
        bits, coarse = np.asarray(bits), np.asarray(coarse)
        for b, rec in enumerate(_SELECTION.rows):
            rec["ids"] = np.append(rec["ids"], tok_h[b])
            rec["bits"] = np.concatenate([rec["bits"], bits[:, b, :1]], axis=1)  # slot 0 is the token's
            rec["state_coarse"] = float(coarse[b])
        return logits, pools

    return sys_prefill, sys_decode


def state_readings() -> list[float]:
    """STATE_COARSE's reading on each recorded row of the last step."""
    return [rec.get("state_coarse", 0.0) for rec in _SELECTION.rows]


def selection_readings(params, dims: dict) -> list[dict]:
    """What the selection check reads on each recorded row (the positions the
    last step ran): the largest distance, the (S layer, position, KV head)
    triples where the reference's own selection is another set, and those
    checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = list(_SELECTION.rows)
    width = -(-max(len(rec["ids"]) for rec in rows) // _QUERY_BLOCK) * _QUERY_BLOCK
    read = jax.jit(lambda p, t: _reference(p, dims, t)[1:])
    out = []
    for rec in rows:
        ids = np.zeros((width,), np.int32)
        ids[: len(rec["ids"])] = rec["ids"]
        distance, flipped, checked = (float(v) for v in read(params, jnp.asarray(ids)))
        out.append({"selection_distance": distance, "selection_flipped": int(flipped),
                    "selection_checked": int(checked)})
    return out


# ------------------------------------------------------------- the reference
def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded selection breaks the selection check, or its
    recorded state the precision the configuration states."""
    import jax.numpy as jnp

    logits, distance, _flipped, _checked, coarse = _reference(params, dims, tokens, with_state=True)
    sound = (distance <= SELECTION_MARGIN) & (coarse <= STATE_COARSE)
    return jnp.where(sound, logits, jnp.nan)


_QUERY_BLOCK = 128  # queries a block of the reference's attention holds
_TOKEN_TILE = 2048  # tokens a tile of the reference's feed-forward holds


def _reference(params, dims: dict, tokens, with_state: bool = False):
    """-> (logits [T, V]; the selection check's largest distance, the (S
    layer, position, KV head) triples the step ran where the reference's own
    selection is another set, the triples the step ran[; the recorded row's
    state reading])."""
    import jax
    import jax.numpy as jnp

    D, H, Kv, d = dims["d_model"], dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    G = H // Kv
    eps, pattern = dims["norm_eps"], dims["layer_pattern"]
    stride, block, topk = dims["pool_stride"], dims["block_size"], dims["block_topk"]
    init, kept_blocks = dims["block_init"], dims["block_window"] // dims["block_size"]
    r = block // stride  # pooled keys a block
    c = dims["residual_scale"]
    f32 = jnp.float32
    T = tokens.shape[0]
    Tq = -(-T // _QUERY_BLOCK) * _QUERY_BLOCK
    N = -(-T // block)  # blocks
    J = max(T // stride - 1, 0)  # pooled keys: mean over pages j and j + 1, both whole
    n_bits = -(-N // 8)
    Ls = pattern.count("S")

    step_bits, n_ran, coarse = _SELECTION.read_for(
        tokens, (Ls, T, Kv, n_bits), CONTROLS["follow_step_selection"]
    )
    pos = jnp.arange(T)

    def row(stack, i, x):
        """Layer ``i`` of a stack in float32; the barrier ties the casts to the
        activations they meet (``models/nemotron_h.py``)."""
        lp = {name: w[i] for name, w in stack.items()}
        x, lp = jax.lax.optimization_barrier((x, lp))
        return x, {name: w.astype(f32) for name, w in lp.items()}

    def norm(x, gain):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain

    def rotate(x):  # [T, H, d]: the halves paired, value i with value i + d / 2
        half = d // 2
        freq = jnp.exp(-math.log(dims["rope_theta"]) * (2.0 * jnp.arange(half, dtype=f32) / d))
        ang = pos[:, None].astype(f32) * freq
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def feed_forward(x, lp):
        n = norm(x, lp["mlp_norm"])
        pad = -T % _TOKEN_TILE
        tiles = jnp.pad(n, ((0, pad), (0, 0))).reshape(-1, _TOKEN_TILE, D)
        one = lambda t: (jax.nn.silu(t @ lp["w_gate"]) * (t @ lp["w_up"])) @ lp["w_down"]
        return x + c * jax.lax.map(one, tiles).reshape(-1, D)[:T]

    def linear(x, lp):
        n = norm(x, lp["norm"])
        q, k = (n @ lp["wq"]).reshape(T, H, d), (n @ lp["wk"]).reshape(T, H, d)
        if dims["qk_norm"]:
            q, k = norm(q, lp["q_norm"]), norm(k, lp["k_norm"])
        q, k = rotate(q) / math.sqrt(d), rotate(k)
        v = (n @ lp["wv"]).reshape(T, H, d)
        h = jnp.arange(1, H + 1, dtype=f32)
        lam = jnp.exp(-(2.0 ** (-8.0 * h / H)))

        def one_token(S, t):  # S [H, d of k, d of v]
            q_t, k_t, v_t = t
            S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.einsum("hk,hkv->hv", q_t, S)

        _, o = jax.lax.scan(one_token, jnp.zeros((H, d, d), f32), (q, k, v))
        o = norm(o, 1.0).reshape(T, H * d) * lp["o_norm"]
        return x + c * ((jax.nn.sigmoid(n @ lp["w_attn_gate"]) * o) @ lp["wo"])

    def block_attention(x, lp, bits, carry):
        distance, flipped, checked = carry
        n = norm(x, lp["norm"])
        q = (n @ lp["wq"]).reshape(T, Kv, G, d)
        k, v = (n @ lp["wk"]).reshape(T, Kv, d), (n @ lp["wv"]).reshape(T, Kv, d)
        pages = k[: (J + 1) * stride].reshape(J + 1, stride, Kv, d) if J else k[:0].reshape(0, stride, Kv, d)
        kc = (pages[:-1].sum(1) + pages[1:].sum(1)) / (2.0 * stride) if J else jnp.zeros((0, Kv, d), f32)
        qp = jnp.pad(q, ((0, Tq - T), (0, 0), (0, 0), (0, 0))).reshape(-1, _QUERY_BLOCK, Kv, G, d)
        bp = jnp.pad(bits, ((0, Tq - T), (0, 0), (0, 0))).reshape(-1, _QUERY_BLOCK, Kv, n_bits)
        blocks = jnp.arange(N)

        def one_block(args):
            i, q_b, bits_b = args
            t = i * _QUERY_BLOCK + jnp.arange(_QUERY_BLOCK)  # [Q]
            own = t // block
            visible = blocks[None, :] <= own[:, None]  # [Q, N]
            forced = (blocks[None, :] < init) | (blocks[None, :] > own[:, None] - kept_blocks)
            if J:
                seen = (stride * jnp.arange(J) + 2 * stride - 1)[None, :] <= t[:, None]  # [Q, J]
                s = jnp.einsum("qkgd,jkd->qkgj", q_b, kc) / math.sqrt(d)
                s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
                top = jnp.max(s, axis=-1, keepdims=True)
                e = jnp.where(seen[:, None, None, :], jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
                p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
                pooled = jnp.where(seen[:, None, :], jnp.sum(p, axis=2), -jnp.inf)  # [Q, Kv, J]
            else:
                pooled = jnp.zeros((_QUERY_BLOCK, Kv, 0), f32)
            # block b: the max over pooled r b - 1 .. r b + r - 1
            padded = jnp.pad(pooled, ((0, 0), (0, 0), (1, r * N - J)), constant_values=-jnp.inf)
            score = jnp.max(
                jnp.stack([padded[..., w : w + r * N : r][..., :N] for w in range(r + 1)]), axis=0
            )  # [Q, Kv, N]
            score = jnp.where(forced[:, None, :], jnp.inf, score)
            score = jnp.where(visible[:, None, :], score, -jnp.inf)
            k_eff = min(topk, N)
            own_vals, own_ids = jax.lax.top_k(score, k_eff)  # ties: the lower block first
            own_sel = jnp.any(own_ids[..., None] == blocks, axis=-2) & visible[:, None, :]
            step_sel = jnp.unpackbits(bits_b, axis=-1)[..., :N].astype(bool)
            ran = (t < n_ran)[:, None]  # [Q, 1]
            sel = jnp.where(ran[..., None], step_sel, own_sel)
            # the check: what was read against the topk-th largest score
            kth = own_vals[..., k_eff - 1]  # [Q, Kv]
            under = kth - jnp.min(jnp.where(sel, score, jnp.inf), axis=-1)
            left = jnp.max(jnp.where(sel | ~visible[:, None, :], -jnp.inf, score), axis=-1)
            over = jnp.where(left > -jnp.inf, left - kth, -jnp.inf)  # (nothing left out: no distance)
            apart = jnp.maximum(under, over)
            apart = jnp.where(jnp.isfinite(kth) & (kth > 0), apart / jnp.maximum(kth, 1e-30), apart)
            apart = jnp.where(ran & (t < T)[:, None], jnp.maximum(apart, 0.0), 0.0)
            other = ran & (t < T)[:, None] & jnp.any(sel != own_sel, axis=-1)
            # attention over the tokens <= t of the selected blocks
            keep = jnp.repeat(sel, block, axis=-1)[..., :T] & (pos[None, None, :] <= t[:, None, None])
            a = jnp.einsum("qkgd,skd->qkgs", q_b, k) / math.sqrt(d)
            a = jnp.where(keep[:, :, None, :], a, -jnp.inf)
            a = jax.nn.softmax(a, axis=-1)
            a = jnp.where(jnp.isfinite(a), a, 0.0)
            out = jnp.einsum("qkgs,skd->qkgd", a, v)
            return out, jnp.max(apart), jnp.sum(other), jnp.sum(ran & (t < T)[:, None]) * Kv

        n_blocks = Tq // _QUERY_BLOCK
        out, apart, other, ran_n = jax.lax.map(one_block, (jnp.arange(n_blocks), qp, bp))
        attn = out.reshape(Tq, H * d)[:T]
        carry = (jnp.maximum(distance, jnp.max(apart)), flipped + jnp.sum(other), checked + jnp.sum(ran_n))
        return x + c * ((jax.nn.sigmoid(n @ lp["w_attn_gate"]) * attn) @ lp["wo"]), carry

    with jax.default_matmul_precision("highest"):
        x = dims["embed_scale"] * params["embed"].astype(f32)[tokens]
        zero = jnp.asarray(0, jnp.int32)
        carry = (jnp.asarray(0.0, f32), zero, zero)
        seen = {"L": 0, "S": 0}
        for kind in pattern:
            i = seen[kind]
            seen[kind] += 1
            if kind == "L":
                x, lp = row(params["linear_layers"], i, x)
                x = linear(x, lp)
            else:
                x, lp = row(params["block_layers"], i, x)
                x, carry = block_attention(x, lp, step_bits[i], carry)
            x = feed_forward(x, lp)
        x = norm(x, params["final_norm"].astype(f32)) / dims["logit_divisor"]
        logits = x @ params["head"].astype(f32)
    return (logits,) + carry + ((coarse,) if with_state else ())
