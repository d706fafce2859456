"""The comparison of the program's model step against a configuration's
plain reference, and its tolerance.

The reference itself (the block's forward pass in straightforward
``jax.numpy``, float32 with ``highest`` matmul precision, no KV cache, no
kernel, no batching tricks) belongs to the configuration's block module
(``models/<module>.py``: ``reference_logits``). This file knows no block: it
takes the module, drives the program's step and the module's reference over
the same seeded prompts, and compares logits. A block whose step is not
prefill + commit-to-pages + paged decode brings its own ``step_functions``.
"""

from __future__ import annotations

import math


def step_functions(model_cfg, dims, mesh, *, B, T, n_pages, page_size, interpret):
    """The program's two jitted steps of the comparison: ``sys_prefill(params,
    tokens, lens, table) -> (last logits, state)``, the dense prefill committed
    to a paged pool, and ``sys_decode(params, tok, pos, table, state) ->
    (logits, state)``, one token of paged decode through the ragged kernel.
    A block module's own ``step_functions`` has this signature."""
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

    return sys_prefill, sys_decode


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
#        0.0141-0.0158 at mistral-7b-1chip, both 16 layers. 0.02 at 16
#        layers: the roundoff model's 1.9%, a quarter above the worst reading.
#   max  the worst single entry: an extreme of ~10^5 entries, four to five
#        of those standard deviations and heavy-tailed from seed to seed
#        (0.058-0.077 and 0.055-0.070 over the same 24 seeds), so it cannot
#        carry a tight tolerance without failing one seed in ten. 0.12 at 16
#        layers is there for a fault in a few entries that the mean hides.
# Both follow the depth by the same model, tol(L) = tol(16) * sqrt(L / 16):
# 0.02 / 0.12 at 16 layers exactly, 0.0283 / 0.170 at 32, 0.0346 / 0.208 at
# 48. Measured worst rms (and max), with the int8 control's smallest beside:
#   CPU (ISSUE 26: the dense prefill in bf16 against the reference, d_model
#   512, 4 rows x 36 positions): 0.0139 at 8 layers, 0.0192 at 16, 0.0252
#   at 32, 0.0290 at 64: x1.31 from 16 to 32, under sqrt(2).
#   Chip (PR 26, TPU v5 lite, olmo2-1b widths at the slab's shape, 12 seeds a
#   depth, control on 3):  8 layers 0.0108-0.0115 (0.044-0.051), control
#   0.0467;  16 layers 0.0148-0.0164 (0.059-0.077), control 0.0618;  32 layers
#   0.0195-0.0210 (0.077-0.093), control 0.0773 (max 0.320);  48 layers
#   0.0223-0.0241 (0.088-0.111). The worst sound reading is 0.82 / 0.74 / 0.69
#   of tol at 16 / 32 / 48 layers: growth is x1.28 from 16 to 32 and x1.15
#   from 32 to 48, slower than the square root, so the law grows looser with
#   depth and never tighter; the control's smallest stays 3.7 times the sound
#   runs' largest and 2.7 times tol at 32 layers.
# Under 16 layers the law does not hold the other way (a depth-independent
# part, the embedding scale and the unembedding, does not shrink: 2 layers at
# model=test read 0.0075 on the CPU against a square-root 0.0071), so the
# tolerance never goes under tol(16): at 8 layers 0.02 lies 1.7 times over the
# sound runs' largest and 2.3 times under the control's smallest.
# The negative control (``int8_rounded``: the program's step on weights of
# 256 levels) fails both numbers at every depth read: a step in a lower
# precision than stated does not pass; a dropped layer, mask or rope term
# reads near 1.
TOL_AT_16 = (0.02, 0.12)  # (rms, max) at 16 layers


def tol(n_layers: int) -> tuple[float, float]:
    """(rms, max) tolerance of the comparison at a depth."""
    scale = math.sqrt(max(n_layers, 16) / 16)
    return TOL_AT_16[0] * scale, TOL_AT_16[1] * scale


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


def compare_with_engine_step(block, params, model_cfg, dims, mesh, *, seed, interpret, page_size,
                             rows, pages_per_row, prefill_len, n_decode=3, control=""):
    """``block`` is the configuration's block module: its ``reference_logits``
    is the reference, its ``step_functions`` (where it has one) the program's
    step; ``dims`` carries at least ``vocab_size`` and ``n_layers``.

    Run seeded prompts through the PROGRAM's model step at the slab's
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
    import jax
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

    sys_prefill, sys_decode = getattr(block, "step_functions", step_functions)(
        model_cfg, dims, mesh, B=B, T=T, n_pages=n_pages, page_size=page_size,
        interpret=interpret,
    )
    ref = jax.jit(lambda p, t: block.reference_logits(p, dims, t))
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
    tol_rms, tol_max = tol(dims["n_layers"])
    return {"max_rel_err": worst, "rms_rel_err": worst_rms, "positions": n_pos, "rows": B, "prompt_lens": prompt_lens,
            "control": control, "n_layers": dims["n_layers"], "tol_rms": tol_rms, "tol_max": tol_max,
            "ok": worst_rms <= tol_rms and worst <= tol_max}
