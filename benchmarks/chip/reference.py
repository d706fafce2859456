"""The configurations' plain reference: the decoder's forward pass in
straightforward ``jax.numpy``, float32 with ``highest`` matmul precision, no
KV cache, no kernel, no batching tricks — and the comparison of the
program's model step against it.

Both configurations run through mcpx's one decoder block, so there is one
reference: pre-norm decoder, RMSNorm with a (1 + scale) gain, RoPE over
half-split head dims, grouped-query attention with a causal mask, gated
tanh-GELU MLP (GeGLU), embeddings tied and scaled by sqrt(hidden). The
configuration files name where this departs from each source model.

Independent of ``mcpx/models`` and ``mcpx/engine``: it reads only the
parameter arrays (names and layouts of ``init_params``).
"""

from __future__ import annotations

import math


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


def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """The three jitted programs of the comparison: the program's dense
    prefill committed to a paged pool, its one-token paged decode through
    the ragged kernel, and the reference's full forward."""
    import jax
    import jax.numpy as jnp

    from mcpx.engine.kv_cache import commit_prefill_to_pages, init_paged_kv
    from mcpx.engine.paged_decode import decode_chunk_paged
    from mcpx.models.gemma.model import init_kv_cache, prefill

    @jax.jit
    def sys_prefill(params, tokens, lens, table):
        dense = init_kv_cache(model_cfg, B, T)
        last, dense = prefill(params, model_cfg, tokens, lens, dense, last_only=True)
        pools = init_paged_kv(model_cfg, n_pages, page_size)
        pools = commit_prefill_to_pages(pools, dense, table, lens, page_size)
        return last, pools

    @jax.jit
    def sys_decode(params, tok, pos, table, pools):
        logits, pools = decode_chunk_paged(
            params, model_cfg, tok[:, None], pos, table, pools,
            use_pallas=True, interpret=interpret, mesh=mesh,
            logits_at=jnp.zeros((B,), jnp.int32), q_lens=jnp.ones((B,), jnp.int32),
        )
        return logits, pools

    ref = jax.jit(lambda p, t: reference_logits(p, dims, t))
    return sys_prefill, sys_decode, ref


# Tolerances of the comparison, and why. The served path holds weights AND
# activations in bfloat16 (unit roundoff 2^-9) with float32 accumulation;
# the reference reads the same bfloat16 weights and rounds nothing after.
# Independent roundings through L layers of ~6 rounded tensors each grow like
# sqrt(6 L) * 2^-9 of the activations' scale: 1.9% of a logit's scale at 16
# layers. Two numbers are taken at every compared position, both against the
# reference row's own spread (its standard deviation over the vocabulary),
# and the worst position counts:
#   rms  the root-mean-square error over the row's 3,072 entries: the
#        per-entry error above. Read on the chip (PR 23, TPU v5 lite, the
#        slab's shape, 24 seeds a configuration): 0.0149-0.0162 at olmo2-1b,
#        0.0141-0.0158 at mistral-7b-1chip. TOL_RMS is 0.02: the roundoff
#        model's 1.9%, a quarter above the worst reading.
#   max  the worst single entry: an extreme of ~10^5 entries, four to five
#        of those standard deviations and heavy-tailed from seed to seed
#        (0.058-0.077 and 0.055-0.070 over the same 24 seeds), so it cannot
#        carry a tight tolerance without failing one seed in ten. TOL_MAX
#        0.12 is there for a fault in a few entries that the mean hides.
# The negative control (``int8_rounded``: the program's step on weights of
# 256 levels) read rms 0.0637-0.0644 and max 0.25-0.29 at olmo2-1b: over
# three times TOL_RMS and twice TOL_MAX, so a step in a lower precision
# than stated fails both; a dropped layer, mask or rope term reads near 1.
TOL_RMS = 0.02
TOL_MAX = 0.12


def int8_rounded(params):
    """Negative control: every matrix rounded to 256 levels (symmetric, one
    scale a tensor) and stored back in its own type. A step that computed
    on such weights is a lower precision than the configuration states and
    has to FAIL the comparison; ``run.py --control int8-weights`` shows it."""
    import jax
    import jax.numpy as jnp

    def q(w):
        if w.ndim < 2:
            return w
        x = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0  # norm gains start at 0
        return (jnp.round(x / scale) * scale).astype(w.dtype)

    return jax.jit(lambda p: jax.tree.map(q, p))(params)


def compare_with_engine_step(params, model_cfg, dims, mesh, *, seed, interpret, page_size,
                             rows, pages_per_row, prefill_len, n_decode=3, control=""):
    """Run seeded prompts through the PROGRAM's model step at the slab's
    shape — ``rows`` rows, a page table ``pages_per_row`` wide over a pool of
    ``rows * pages_per_row + 1`` pages, as the engine holds them: dense
    ``prefill`` at the ``prefill_len`` bucket committed to pages, then
    ``decode_chunk_paged`` one token at a time through the ragged kernel
    against the paged pool — and through the reference's full forward;
    compare the logits of the last prompt position and of every decoded
    position of every row. Prompt lengths and tokens come from ``seed``.

    Returns ``{"rms_rel_err": ..., "max_rel_err": ..., "positions": n, "ok":
    bool}``: the root-mean-square and the largest |system - reference| over
    the vocabulary, divided by the reference logits' standard deviation at
    that position, each at its worst position."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed % (2**32))
    B, T, V = rows, prefill_len, dims["vocab_size"]
    # Seeded lengths between a fifth of the bucket and all of it, short of
    # the decoded tail; all rows padded to one length for the reference
    # (causal: positions before the pad do not see it), so one program each.
    prompt_lens = [int(n) for n in rng.integers(T // 5, T - n_decode, size=B)]
    seqs = [rng.integers(0, V, size=T, dtype=np.int32) for _ in range(B)]
    assert pages_per_row * page_size > T and pages_per_row >= T // page_size
    n_pages = 1 + B * pages_per_row  # page 0 is the null page
    table = 1 + np.arange(B * pages_per_row, dtype=np.int32).reshape(B, pages_per_row)
    tokens = np.zeros((B, T), np.int32)
    for b, n in enumerate(prompt_lens):
        tokens[b, :n] = seqs[b][:n]
    lens = jnp.asarray(prompt_lens, jnp.int32)
    table_d = jnp.asarray(table)

    sys_prefill, sys_decode, ref = step_functions(
        model_cfg, dims, mesh, B=B, T=T, n_pages=n_pages, page_size=page_size,
        interpret=interpret,
    )
    sys_params = int8_rounded(params) if control == "int8-weights" else params

    with mesh:
        last, pools = sys_prefill(sys_params, jnp.asarray(tokens), lens, table_d)
        got = [[np.asarray(last[b], np.float32)] for b in range(B)]
        for i in range(n_decode):
            tok = jnp.asarray([seqs[b][prompt_lens[b] + i] for b in range(B)], jnp.int32)
            pos = jnp.asarray([prompt_lens[b] + i for b in range(B)], jnp.int32)
            logits, pools = sys_decode(sys_params, tok, pos, table_d, pools)
            for b in range(B):
                got[b].append(np.asarray(logits[b], np.float32))
        worst = worst_rms = 0.0
        n_pos = 0
        for b in range(B):
            want = np.asarray(ref(params, jnp.asarray(seqs[b])), np.float32)
            for i, g in enumerate(got[b]):
                w = want[prompt_lens[b] - 1 + i]
                err = float(np.max(np.abs(g - w)) / np.std(w))
                rms = float(np.sqrt(np.mean((g - w) ** 2)) / np.std(w))
                # a NaN never passes
                worst = max(worst, err if math.isfinite(err) else math.inf)
                worst_rms = max(worst_rms, rms if math.isfinite(rms) else math.inf)
                n_pos += 1
    worst, worst_rms = min(worst, 1e30), min(worst_rms, 1e30)  # JSON has no infinity
    return {"max_rel_err": worst, "rms_rel_err": worst_rms, "positions": n_pos, "rows": B, "prompt_lens": prompt_lens,
            "control": control, "tol_rms": TOL_RMS, "tol_max": TOL_MAX,
            "ok": worst_rms <= TOL_RMS and worst <= TOL_MAX}
