"""The block of deepseek-ai/DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``):
the latent-attention block of ``models/mla.py`` (A.X-K1 is the same family's)
with two mechanisms more, as a configuration's block module: the bridge from
the published keys to the program's model-config object, the block's plain
reference, and the program's step of the comparison.

Everything ``models/mla.py``'s docstring writes down holds here (the query's
and the key-value's bottlenecks with their norms, the cache row ``[c_kv |
k_rope]``, the expanded and the absorbed form, YaRN's ``m^2`` in the softmax
scale, half-split rope pairing, the shared expert, unscaled embeddings, an
untied head). What this block adds, with ``n`` a layer's normed input and
``c_q`` the normed query latent:

  the index (the source's ``inference/model.py::Indexer``; ``config.json``
  only names its three sizes):
    q^I[t]   = c_q[t] W_qI                    [index_n_heads, index_head_dim]
    k^I[s]   = LayerNorm(n[s] W_kI) g + b     ONE head of index_head_dim, eps 1e-6
               the first ``qk_rope_head_dim`` values of k^I and of every q^I
               head rotated by the layer's rope (half-split), the rest not
    w[t]     = n[t] W_w * index_n_heads^-0.5 * index_head_dim^-0.5
    I[t, s]  = sum_h w[t, h] relu(q^I[t, h] . k^I[s]),  s <= t,  float32
    S_t      = the min(index_topk, t + 1) keys of largest I[t, .], ties to
               the lower position
    the latent attention exactly as ``models/mla.py`` has it, its softmax
    over S_t alone. THE CACHE HOLDS k^I beside [c_kv | k_rope] (after the
    norm and the rotation): 512 + 64 + 128 values a token a layer.
  the router (``topk_method`` "noaux_tc"):
    s = sigmoid(n2 W_r) over all ``n_routed_experts_published``; s' = s + b;
    a group (``n_group`` runs of consecutive experts) scores the sum of its
    two largest s'; the best ``topk_group`` groups stay; the
    ``num_experts_per_tok`` largest s' inside them are chosen; w =
    s[chosen] / sum(s[chosen]) * routed_scaling_factor: the UNBIASED scores.

Departures (the configuration file lists them): the index in bfloat16 where
the source runs it in FP8 behind a Hadamard rotation of q^I and k^I (an
orthogonal map: it leaves q . k as it is and exists for the quantisation);
the multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
built: it follows the last layer, on a pipeline's last stage, and the main
model's logits do not read it.

The reference below is that, in plain ``jax.numpy`` float32 at ``highest``,
EXPANDED, no cache, a block of queries and a group of heads at a time so that
a row of 7,168 tokens fits beside the served weights. It runs UNDER THE
STEP'S ROUTING, as the other routed blocks' references do, and UNDER THE
STEP'S SELECTION, and checks both: a bfloat16 index score moves keys across
the ``index_topk``-th place, and a query that reads another 1-2% of its 2,048
keys moves a layer's attention output by ~10%, far more than rounding
(``SELECTION_MARGIN`` has the readings).
"""

from __future__ import annotations

import importlib.util
import math
import os

kernel_paths = {"decode": 1, "prefill": 1}


def _beside(name: str):
    """A block module beside this one, imported by path as the harness
    imports this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("chip_block_" + name + "_for_dsa", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_mla = _beside("mla")
yarn_score_factor = _mla.yarn_score_factor

# The routing limit: ``models/mla.py``'s, for the same scoring (the bias is
# added on both sides before the distance is taken). Read on the chip at this
# cell's sizes: the comment at SELECTION_MARGIN.
MARGIN = _mla.MARGIN

# A group whose score (a sum of two) lies this far over the best group the
# reference leaves out is kept by the step too, whatever its rounding: twice
# the routing limit.
GROUP_SLACK = 2 * MARGIN

# How far a key the step's query READ may lie under the reference's
# ``index_topk``-th index score of that query, or one it did NOT read over it,
# in units of the standard deviation of the query's visible index scores.
# Readings (TPU v5 lite, PR 44, the cell's configuration at the timed sizes: 8
# rows, contexts to 7,168 prefilled in chunks of 2,048, three decoded
# positions; ``tests/test_dsa_readings.py``): PERF.md section 6 (PR 44).
SELECTION_MARGIN = 0.25

# Switches of the negative controls (tests set them; a benchmark run never
# does): the reference under its OWN routing / selection, and the STEP with
# its selection left out (every key a query sees attended).
CONTROLS = {"follow_step_routing": True, "follow_step_selection": True, "step_selects": True}

# The longest window this block's prefill takes in ONE dispatch, which is what
# ``GemmaConfig.max_seq_len`` says to the engine (its one reader caps the
# prefill buckets by it; the rope serves ``max_position_embeddings``, which
# the file keeps as published and the slab cuts to 8,192). The engine warms
# every bucket at every cohort size, and at 2,048 x 8 rows the expanded
# prefill's scores (128 heads x 256 queries x 2,048 keys x 8 rows, float32:
# 2.1 GB a block) and the grouped experts' rows (131,072 x 7,168) do not fit
# beside 10.8 GB of weights; 1,024 is the bucket ``a.x-k1``'s cell warms and
# dispatches at 8 rows. A longer head is built in chunks of it
# (``engine._ensure_prefix``), and so is the comparison's prefill.
PREFILL_WINDOW = 1024
CHUNK = PREFILL_WINDOW

_ROUTING = _mla._harness_file("routing_record").RoutingRecord()


class SelectionRecord:
    """What every position past ``index_topk`` read, by row of the last
    comparison: {"ids" [n], "first": the first position recorded, "bits"
    [L, n - first, ceil(n / 8)] uint8: a bit a key, most significant first}."""

    def __init__(self) -> None:
        self.rows: list[dict] = []

    def read_for(self, tokens, n_layers: int, first: int, follow: bool = True):
        """-> (bits [L, T - first, ceil(T / 8)] of the recorded row whose ids
        are these tokens' head, positions recorded [scalar]: 0 for a sequence
        the step never saw or with ``follow`` off). The records enter as
        constants."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        T = tokens.shape[0]
        span, width = T - first, -(-T // 8)
        records = [r for r in self.rows if len(r["ids"]) <= T] if follow else []
        if not records:
            return jnp.zeros((n_layers, span, width), jnp.uint8), jnp.asarray(0, jnp.int32)
        ids = np.full((len(records), T), -1, np.int32)
        starts, total = [], 0
        for r, rec in enumerate(records):
            ids[r, : len(rec["ids"])] = rec["ids"]
            starts.append(total)
            total += rec["bits"].shape[1]
        bits = np.zeros((n_layers, total + span, width), np.uint8)  # a slice never runs out
        for start, rec in zip(starts, records):
            b = rec["bits"]
            bits[:, start : start + b.shape[1], : b.shape[2]] = b[:, :, :width]
        n = jnp.asarray([len(rec["ids"]) for rec in records], jnp.int32)
        same = jnp.all((tokens[None, :] == ids) | (jnp.arange(T)[None, :] >= n[:, None]), axis=1)
        score = jnp.where(same, n, -1)
        best = jnp.argmax(score)
        row = jax.lax.dynamic_slice(
            jnp.asarray(bits), (0, jnp.asarray(starts, jnp.int32)[best], 0), (n_layers, span, width)
        )
        return row, jnp.where(score[best] > 0, n[best] - first, 0)


_SELECTION = SelectionRecord()

# Published key -> GemmaConfig field: the latent block's, and the index's.
_FIELDS = {
    **_mla._FIELDS,
    "index_n_heads": "index_n_heads",
    "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "n_group": "router_groups",
    "topk_group": "router_groups_kept",
}
# Published keys the block has no knob for: the file may state only this.
_BLOCK_IS = {
    "model_type": "deepseek_v32",
    "hidden_act": "silu",
    "tie_word_embeddings": False,
    "attention_bias": False,
    "scoring_func": "sigmoid",
    "norm_topk_prob": True,
    "topk_method": "noaux_tc",  # groups, and a bias in the choice
    "n_shared_experts": 1,
    "moe_layer_freq": 1,
    "ep_size": 1,  # the checkpoint's; the deployment is the file's
    "num_key_value_heads": 128,  # every head its own once expanded; the cache holds none
    "num_nextn_predict_layers": 1,  # not built (``departures``)
}
# The router's correction bias under random weights: ``trinity-mini``'s draw.
ROUTER_BIAS_SCALE = 0.1


def dsa_dims(config: dict, vocab_size: int) -> dict:
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
    if dims["max_seq_len"] < PREFILL_WINDOW:
        raise ValueError(f"max_position_embeddings {dims['max_seq_len']} is under the prefill window")
    dims["max_seq_len"] = PREFILL_WINDOW
    for field in ("norm_eps", "rope_theta", "router_scale"):
        dims[field] = float(dims[field])
    return dict(
        vocab_size=vocab_size, **dims, n_kv_heads=1, attention="latent",
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_pos=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]), yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=1.0, attn_score_factor=yarn_score_factor(yarn),
        d_shared_expert=int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
        router_scoring="sigmoid", router_bias_scale=ROUTER_BIAS_SCALE,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


def model_config(config: dict, vocab_size: int):
    from mcpx.models.gemma.config import GemmaConfig

    if "index_topk" not in GemmaConfig.__dataclass_fields__:
        # A program from before this block: nothing to build it with.
        raise SystemExit("dsa: this mcpx has no learned index (GemmaConfig.index_topk)")
    return GemmaConfig(**dsa_dims(config, vocab_size))


def rehearsal_config(vocab_size: int):
    """The same block at CPU size (the dense lead layer and ONE sparse layer,
    16 experts in 4 groups of which 2 stay, experts 4..7 held, top-2, a
    shared expert; an index of 4 heads x 32 over the 32 best keys, so that a
    rehearsal's prompts lie past it; prefill buckets to 256, so that a
    catalogue head of a few hundred tokens is built in chunks): rehearsals
    and tests only. Two layers, for ``models/mla.py``'s reason."""
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(
        vocab_size=vocab_size, d_model=256, n_layers=2, n_heads=4, n_kv_heads=1, head_dim=32,
        d_ff=512, rope_theta=10000.0, norm_eps=1e-6, max_seq_len=256,
        attention="latent", q_lora_rank=96, kv_lora_rank=64, qk_rope_head_dim=16, v_head_dim=32,
        yarn_factor=40.0, yarn_original_max_pos=32, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        attn_score_factor=yarn_score_factor({"mscale": 1, "mscale_all_dim": 1, "factor": 40}),
        index_n_heads=4, index_head_dim=32, index_topk=32,
        n_experts=16, n_experts_per_tok=2, d_expert=128, expert_first=4, experts_held=4,
        n_dense_layers=1, d_shared_expert=128, router_scoring="sigmoid", router_scale=2.5,
        router_bias_scale=ROUTER_BIAS_SCALE, router_groups=4, router_groups_kept=2,
        activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )


# ------------------------------------------------------------------ the step
def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """``reference.step_functions`` for this block. Both steps are the
    program's paged forward through the two kernels (the index, then the
    latent attention over what it chose), with the routing AND the selection
    recorded by row. The prefill is what the engine does with a head longer
    than its buckets: a row at a time, chunks of ``CHUNK`` tokens, each a
    suffix prefill over the pages of those before it. ``CONTROLS
    ["step_selects"]`` off runs the same step with its selection left out:
    every key a query sees is read (and recorded as read)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mcpx.engine.kv_cache import init_paged_kv
    from mcpx.engine.paged_decode import decode_chunk_paged

    cfg = model_cfg
    topk = cfg.index_topk
    table_keys = (n_pages - 1) // B * page_size
    if not CONTROLS["step_selects"]:
        cfg = dataclasses.replace(cfg, index_topk=table_keys - 1)  # past every context
    chunk = min(CHUNK, T)
    first = min(topk, T)  # positions before it read every key they see
    # A rehearsal (the kernels interpreted on the CPU) at the cell's own
    # lengths takes the gathered jnp route: the interpreter needs tens of
    # milliseconds a program, 7,168 tokens x 8 rows are ~20,000 programs, and
    # the comparison has 120 s. Tier-1 runs the kernels interpreted at small
    # sizes (tests/test_dsa_block.py); the chip runs them compiled.
    kernels = not (interpret and T > PREFILL_WINDOW)
    _ROUTING.rows.clear()
    _SELECTION.rows.clear()

    def forward(params, tokens, pos, table, pools, q_lens, logits_at):
        return decode_chunk_paged(
            params, cfg, tokens, pos, table, pools, use_pallas=kernels, interpret=interpret,
            mesh=mesh, logits_at=logits_at, q_lens=q_lens, routing=True, selection=True,
        )

    forward_j = jax.jit(forward, donate_argnums=4)

    def note(rec_r, rec_s, ids, chosen, bits, start):
        """A row's window [start, start + len(ids)) into its two records."""
        n = len(ids)
        rec_r["ids"] = np.append(rec_r["ids"], ids)
        rec_r["chosen"] = np.concatenate([rec_r["chosen"], chosen[:, :n]], axis=1)
        rec_s["ids"] = rec_r["ids"]
        lo = max(first - start, 0)
        if lo < n:
            rec_s["bits"] = np.concatenate([rec_s["bits"], bits[:, lo:n]], axis=1)

    def sys_prefill(params, tokens, lens, table):
        pools = jax.jit(lambda: init_paged_kv(model_cfg, n_pages, page_size))()
        tokens_h, lens_h = np.asarray(tokens), np.asarray(lens)
        Ls, k, width = model_cfg.n_sparse_layers, model_cfg.n_experts_per_tok, table_keys // 8
        lasts = []
        for b in range(B):
            rec_r = {"ids": np.zeros((0,), np.int32), "chosen": np.zeros((Ls, 0, k), np.int32)}
            rec_s = {"ids": rec_r["ids"], "first": first,
                     "bits": np.zeros((model_cfg.n_layers, 0, width), np.uint8)}
            n = int(lens_h[b])
            for start in range(0, n, chunk):
                live = min(chunk, n - start)
                last, pools, chosen, bits = forward_j(
                    params, tokens[b : b + 1, start : start + chunk],
                    jnp.asarray([start], jnp.int32), table[b : b + 1], pools,
                    jnp.asarray([live], jnp.int32), jnp.asarray([live - 1], jnp.int32),
                )
                chosen, bits = jax.device_get((chosen, bits))  # [Ls, 1, S, k], [L, 1, S, keys / 8]
                note(rec_r, rec_s, tokens_h[b, start : start + live], chosen[:, 0], bits[:, 0], start)
            lasts.append(last)
            _ROUTING.rows.append(rec_r)
            _SELECTION.rows.append(rec_s)
        return jnp.concatenate(lasts), pools

    def sys_decode(params, tok, pos, table, pools):
        logits, pools, chosen, bits = forward_j(
            params, tok[:, None], pos, table, pools,
            jnp.ones((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        )
        chosen, bits, tok_h, pos_h = jax.device_get((chosen, bits, tok, pos))
        for b in range(B):
            note(_ROUTING.rows[b], _SELECTION.rows[b], tok_h[b : b + 1], chosen[:, b], bits[:, b],
                 int(pos_h[b]))
        return logits, pools

    return sys_prefill, sys_decode


def routing_readings(params, dims: dict) -> list[dict]:
    """What the two checks read on each recorded row (the positions the last
    step ran): the routing's largest distance, its flipped and checked (sparse
    layer, position) pairs, and the selection's: largest distance (in standard
    deviations of a query's visible index scores), the (layer, position, key)
    triples where the reference's own choice differs, and those checked."""
    import jax
    import jax.numpy as jnp

    import numpy as np

    out = []
    # Every row padded to one length (whole query blocks), so that the
    # reference compiles once: a position past a row's record is not checked,
    # and no checked position sees it.
    rows = list(_ROUTING.rows)
    width = -(-max(len(rec["ids"]) for rec in rows) // _QUERY_BLOCK) * _QUERY_BLOCK
    read = jax.jit(lambda p, t: _reference(p, dims, t)[1:])
    for rec in rows:
        ids = np.zeros((width,), np.int32)
        ids[: len(rec["ids"])] = rec["ids"]
        values = read(params, jnp.asarray(ids))
        distance, flipped, checked, sel_distance, sel_flipped, sel_checked = (float(v) for v in values)
        out.append({"distance": distance, "flipped": int(flipped), "checked": int(checked),
                    "selection_distance": sel_distance, "selection_flipped": int(sel_flipped),
                    "selection_checked": int(sel_checked)})
    return out


# ------------------------------------------------------------- the reference
def reference_logits(params, dims: dict, tokens):
    """Logits [T, V] (float32) of one unpadded token sequence [T]; all NaN
    where the step's recorded routing or selection breaks its check."""
    import jax.numpy as jnp

    logits, distance, _f, _c, sel_distance, _sf, _sc = _reference(params, dims, tokens)
    sound = (distance <= MARGIN) & (sel_distance <= SELECTION_MARGIN)
    return jnp.where(sound, logits, jnp.nan)


# Queries a block of the reference's attention holds, and heads a group.
_QUERY_BLOCK = 128
_HEAD_GROUP = 32
# Rows a tile of an expert's choosers holds.
_EXPERT_TILE = 512


def _reference(params, dims: dict, tokens):
    """-> (logits [T, V]; the routing check's largest distance, flipped and
    checked pairs; the selection check's largest distance, flipped and checked
    triples)."""
    import jax
    import jax.numpy as jnp

    lax = jax.lax
    D, H, L, Ld = dims["d_model"], dims["n_heads"], dims["n_layers"], dims["n_dense_layers"]
    nope, rope, rkv, eps = dims["head_dim"], dims["qk_rope_head_dim"], dims["kv_lora_rank"], dims["norm_eps"]
    dv = dims["v_head_dim"]
    Hi, di, topk = dims["index_n_heads"], dims["index_head_dim"], dims["index_topk"]
    E, k = dims["n_experts"], dims["n_experts_per_tok"]
    G, G_kept = dims["router_groups"], dims["router_groups_kept"]
    first_e = dims["expert_first"]
    held = dims["experts_held"] or E
    f32 = jnp.float32
    T = tokens.shape[0]
    half = rope // 2
    scale = dims["attn_score_factor"] / math.sqrt(nope + rope)
    QB = math.gcd(T, _QUERY_BLOCK)
    HG = math.gcd(H, _HEAD_GROUP)
    selecting = T > topk
    first = min(topk, T)

    step_choice = _ROUTING.chosen_for(tokens, L - Ld, k, CONTROLS["follow_step_routing"])
    step_bits, n_read = _SELECTION.read_for(tokens, L, first, CONTROLS["follow_step_selection"])
    ang = jnp.arange(T, dtype=f32)[:, None] * jnp.asarray(_mla._yarn_inv_freq(dims), f32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)  # [T, rope / 2]
    pos = jnp.arange(T)

    def norm(x, gain):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + eps) * gain

    def rotate(t):  # [T, ..., rope], half-split pairs
        c, s = (a.reshape((T,) + (1,) * (t.ndim - 2) + (half,)) for a in (cos, sin))
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)

    def rotate_first(t):  # the first ``rope`` values of the last axis
        return jnp.concatenate([rotate(t[..., :rope]), t[..., rope:]], axis=-1)

    def swiglu(n, w_gate, w_up, w_down):
        return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down

    def selection(n1, c_q, lp, bits):
        """-> (the keys every query reads [T, T] bool, the check's largest
        distance, flipped triples, checked triples) of one layer."""
        q_i = rotate_first(jnp.einsum("tr,rhe->the", c_q, lp["w_qi"].astype(f32)))
        k_i = n1 @ lp["w_ki"].astype(f32)
        mean = jnp.mean(k_i, axis=-1, keepdims=True)
        var = jnp.mean((k_i - mean) ** 2, axis=-1, keepdims=True)
        k_i = (k_i - mean) * lax.rsqrt(var + eps) * lp["ki_norm"].astype(f32) + lp["ki_norm_bias"].astype(f32)
        k_i = rotate_first(k_i)
        w_i = (n1 @ lp["w_wi"].astype(f32)) * (Hi**-0.5 * di**-0.5)

        def block(carry, xs):
            distance, flipped, checked = carry
            q_b, w_b, pos_b, bits_b = xs  # [QB, Hi, di], [QB, Hi], [QB], [QB, T / 8]
            s = jnp.sum(jax.nn.relu(jnp.einsum("qhe,se->qhs", q_b, k_i)) * w_b[:, :, None], axis=1)
            visible = pos[None, :] <= pos_b[:, None]
            s = jnp.where(visible, s, -jnp.inf)
            kth = lax.top_k(s, topk)[0][:, -1:]
            over = s > kth
            tie = s == kth
            need = topk - jnp.sum(over, axis=-1, keepdims=True)
            own = visible & (over | (tie & (jnp.cumsum(tie, axis=-1) <= need)))
            read = jnp.unpackbits(bits_b, axis=-1, count=T).astype(bool) & visible
            ran = (pos_b >= first) & (pos_b - first < n_read)
            # The check: no key read far under the query's topk-th score, none
            # left out far over it, against the spread of what it sees.
            n_vis = jnp.sum(visible, axis=-1)
            mean_s = jnp.sum(jnp.where(visible, s, 0.0), axis=-1) / n_vis
            spread = jnp.sqrt(jnp.sum(jnp.where(visible, (s - mean_s[:, None]) ** 2, 0.0), axis=-1) / n_vis)
            under = kth[:, 0] - jnp.min(jnp.where(read, s, jnp.inf), axis=-1)
            above = jnp.max(jnp.where(visible & ~read, s, -jnp.inf), axis=-1) - kth[:, 0]
            far = jnp.maximum(under, above) / spread
            distance = jnp.maximum(distance, jnp.max(jnp.where(ran, far, 0.0)))
            flipped += jnp.sum(jnp.where(ran[:, None], read != own, False))
            checked += jnp.sum(jnp.where(ran, n_vis, 0))
            return (distance, flipped, checked), jnp.where(ran[:, None], read, own)

        blocks = lambda a: a.reshape((T // QB, QB) + a.shape[1:])
        bits_all = jnp.concatenate([jnp.zeros((first, bits.shape[1]), jnp.uint8), bits])
        zero = jnp.asarray(0, jnp.int32)
        (distance, flipped, checked), mask = lax.scan(
            block, (jnp.asarray(0.0, f32), zero, zero),
            (blocks(q_i), blocks(w_i), blocks(pos), blocks(bits_all)),
        )
        return mask.reshape(T, T), distance, flipped, checked

    def attention(x, lp, bits):
        n1 = norm(x, lp["pre_attn_norm"].astype(f32))
        c_q = norm(n1 @ lp["w_dq"].astype(f32), lp["q_lora_norm"].astype(f32))
        down = n1 @ lp["w_dkv"].astype(f32)
        c_kv = norm(down[:, :rkv], lp["kv_lora_norm"].astype(f32))
        k_rope = rotate(down[:, rkv:])
        mask = pos[None, :] <= pos[:, None]
        checks = (jnp.asarray(0.0, f32), jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
        if selecting:
            mask, *checks = selection(n1, c_q, lp, bits)

        def heads(out, ws):  # a group of heads, expanded: their own keys and values
            w_uq, w_ukv, wo = (w.astype(f32) for w in ws)  # [rq, HG, .], [rkv, HG, .], [HG, dv, D]
            q = jnp.einsum("tr,rhe->the", c_q, w_uq)
            q_nope, q_rope = q[..., :nope], rotate(q[..., nope:])
            kv = jnp.einsum("tr,rhe->the", c_kv, w_ukv)
            k_nope, v = kv[..., :nope], kv[..., nope:]

            def block(_, xs):
                qn, qr, m = xs  # [QB, HG, nope], [QB, HG, rope], [QB, T]
                s = jnp.einsum("qhe,she->hqs", qn, k_nope) + jnp.einsum("qhe,se->hqs", qr, k_rope)
                s = jnp.where(m[None], s * scale, -jnp.inf)
                return None, jnp.einsum("hqs,she->qhe", jax.nn.softmax(s, axis=-1), v)

            blocks = lambda a: a.reshape((T // QB, QB) + a.shape[1:])
            _, o = lax.scan(block, None, (blocks(q_nope), blocks(q_rope), blocks(mask)))
            return out + jnp.einsum("the,hed->td", o.reshape(T, HG, dv), wo), None

        grouped = lambda w, axis: jnp.moveaxis(
            w.reshape(w.shape[:axis] + (H // HG, HG) + w.shape[axis + 1 :]), axis, 0
        )
        out, _ = lax.scan(
            heads, jnp.zeros((T, D), f32),
            (grouped(lp["w_uq"], 1), grouped(lp["w_ukv"], 1), grouped(lp["wo"], 0)),
        )
        return x + out, checks

    def wide_ff(n2, w_gate, w_up, w_down):
        """A feed-forward a quarter of its width at a time: its float32 copy
        whole would be 1.6 GB beside the served weights."""
        F = w_gate.shape[1]
        parts = math.gcd(F, 4)
        cols = lambda w: w.reshape(D, parts, F // parts).transpose(1, 0, 2)

        def part(acc, ws):
            return acc + swiglu(n2, *(w.astype(f32) for w in ws)), None

        ff, _ = lax.scan(
            part, jnp.zeros((T, D), f32),
            (cols(w_gate), cols(w_up), w_down.reshape(parts, F // parts, D)),
        )
        return ff

    def add_checks(carry, checks):
        distance, flipped, checked = carry
        return jnp.maximum(distance, checks[0]), flipped + checks[1], checked + checks[2]

    def dense_layer(carry, xs):
        x, sel = carry
        lp, bits = xs
        a, checks = attention(x, lp, bits)
        n2 = norm(a, lp["pre_mlp_norm"].astype(f32))
        return (a + wide_ff(n2, lp["w_gate"], lp["w_up"], lp["w_down"]), add_checks(sel, checks)), None

    def sparse_layer(carry, xs):
        x, distance, flipped, checked, sel = carry
        lp, choice, bits = xs
        a, checks = attention(x, lp, bits)
        n2 = norm(a, lp["pre_mlp_norm"].astype(f32))
        s = jax.nn.sigmoid(n2 @ lp["router"].astype(f32))  # [T, E]: what weighs
        biased = s + lp["router_bias"].astype(f32)  # what is compared, and chooses
        grouped = biased.reshape(T, G, E // G)
        group_s = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)  # [T, G]
        own_group_s, kept = lax.top_k(group_s, G_kept)
        in_kept = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
        own_s, own = lax.top_k(jnp.where(in_kept[:, :, None], grouped, -jnp.inf).reshape(T, E), k)
        ran = choice[:, 0] >= 0  # the positions the step ran
        idx = jnp.where(ran[:, None], choice, own)
        sel_e = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)  # [T, E]
        # The check, in two steps as the choice is made. Groups: none that
        # holds a chosen expert scores far under the reference's
        # ``topk_group``-th group (a sum of two scores: half of it counts).
        # Experts: which groups the step kept is not recorded, and two groups
        # a rounding apart can hold quite different experts, so the experts
        # are judged inside the groups the step kept FOR CERTAIN: those of
        # its chosen experts, and those no rounding drops (``GROUP_SLACK``
        # over the best group left out). Inside them no unchosen expert lies
        # far over the least chosen one, and fewer than k lie over it at all.
        chosen_group = jnp.any(sel_e.reshape(T, G, E // G), axis=-1)
        group_under = jnp.max(jnp.where(chosen_group, own_group_s[:, -1:] - group_s, 0.0), axis=-1) / 2
        best_out = jnp.max(jnp.where(in_kept, -jnp.inf, group_s), axis=-1, keepdims=True)  # -inf: all kept
        certain = chosen_group | (group_s >= best_out + GROUP_SLACK)
        inside = jnp.where(certain[:, :, None], grouped, -jnp.inf).reshape(T, E)
        least = jnp.min(jnp.where(sel_e, biased, jnp.inf), axis=-1)
        over = jnp.max(jnp.where(sel_e, -jnp.inf, inside), axis=-1) - least
        under = lax.top_k(inside, k)[0][:, k - 1] - least
        far = jnp.maximum(jnp.maximum(under, over), group_under)
        distance = jnp.maximum(distance, jnp.max(jnp.where(ran, far, 0.0)))
        own_sel = jnp.any(own[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
        flipped += jnp.sum(ran & jnp.any(sel_e != own_sel, axis=-1))
        checked += jnp.sum(ran)
        w = jnp.where(sel_e, s, 0.0)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)  # norm_topk_prob, over all chosen
        w = (w * dims["router_scale"])[:, first_e : first_e + held]  # this chip's experts
        here = sel_e[:, first_e : first_e + held]

        C = _EXPERT_TILE
        rows_pad = jnp.arange(T + C) % T  # a tile past the last chooser reads rows it weighs 0

        def expert(acc, ws):  # one held expert over the tokens that chose it, a tile at a time
            w_gate, w_up, w_down, w_e, here_e = ws
            w_gate, w_up, w_down = (m.astype(f32) for m in (w_gate, w_up, w_down))
            order = jnp.argsort(~here_e, stable=True)[rows_pad]  # its choosers first
            n_here = jnp.sum(here_e)

            def tile(i, acc):
                rows = lax.dynamic_slice(order, (i * C,), (C,))
                live = i * C + jnp.arange(C) < n_here
                y = swiglu(n2[rows], w_gate, w_up, w_down) * jnp.where(live, w_e[rows], 0.0)[:, None]
                return acc.at[rows].add(y)

            return lax.fori_loop(0, (n_here + C - 1) // C, tile, acc), None

        routed, _ = lax.scan(
            expert, jnp.zeros((T, D), f32), (lp["w_gate"], lp["w_up"], lp["w_down"], w.T, here.T)
        )
        shared = swiglu(n2, *(lp[m].astype(f32) for m in ("shared_gate", "shared_up", "shared_down")))
        return (a + shared + routed, distance, flipped, checked, add_checks(sel, checks)), None

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f32)[tokens]  # unscaled
        zero = jnp.asarray(0, jnp.int32)
        sel0 = (jnp.asarray(0.0, f32), zero, zero)
        # scans only to take one layer's weights at a time
        (x, sel), _ = lax.scan(dense_layer, (x, sel0), (params["dense_layers"], step_bits[:Ld]))
        (x, distance, flipped, checked, sel), _ = lax.scan(
            sparse_layer, (x, jnp.asarray(0.0, f32), zero, zero, sel),
            (params["layers"], step_choice, step_bits[Ld:]),
        )
        logits = norm(x, params["final_norm"].astype(f32)) @ params["head"].astype(f32)
    return (logits, distance, flipped, checked) + tuple(sel)
