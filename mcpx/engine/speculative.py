"""Grammar-aware speculative decoding: the single-model recurrent drafter.

Decode is the fattest serving phase because every emitted token costs one
full model forward per slab step. Speculative decoding breaks that coupling:
a cheap DRAFTER proposes K tokens per row, and the slab verifies the whole
window in ONE batched ``[rows, K+1]`` forward — accepted drafts ride along
for free, the first rejection's verification sample is the correction token
(so every forward still nets at least one token), and the window shape is
STATIC, so the compile count is independent of how much each row accepts
(the accelerator-safe verification layout of EAGLE-Pangu, PAPERS.md).

The drafter follows the single-model recurrent-drafter design (Recurrent
Drafter, PAPERS.md), radically lightened so it adds no trained parameters
and almost no per-step work on the decode hot path:

  - a per-row hidden state ``h`` evolves as an embedding EWMA
    ``h ← decay·h + embed(token)`` over the row's emitted tokens;
  - each of the K draft steps scores ``h`` against the model's tied
    unembedding (``h @ embed.T``), takes the highest-scoring
    grammar-admissible non-EOS token from the row's CURRENT draft state,
    advances the automaton, and chains ``h`` over its own proposal — the
    recurrent chain rule, without which a free row (whose proposal nothing
    else varies) would draft one token K times. Per step that is one
    unembed-sized matmul: the unembedding is a single layer of the full
    forward each accepted draft saves, so the drafter stays far cheaper
    than the compute it replaces;
  - after verification, ``h`` advances over the accepted tokens in closed
    form (a decay-weighted cumulative sum over the window — no scan; the
    walk's within-window chaining was a throwaway copy).

**The twist that makes it ours — the grammar pre-filter.** Draft proposals
are filtered through the per-row stacked grammar DFAs (PR 3,
``planner/grammar.stacked_tables``): a constrained row can only ever draft
a token that is grammar-admissible from its current draft state, so

  - single-successor states (JSON scaffolding, trie'd service-name and
    schema-key interiors — the bulk of plan text) force the draft, which
    verification then accepts with certainty: acceptance stays high exactly
    where decode is slowest, independent of drafter quality;
  - a constrained row can never EMIT an inadmissible token either way —
    accepted drafts are admissible by construction, and the correction is
    sampled under the budget-masked admissibility window
    (``grammar.stacked_window_admissibility``; property-tested).

Drafting applies the SAME budget-finishability mask (with the verify
mask's degrade-to-legal fallback) the verification positions will sample
under: the ``[B, C]`` successor-distance gather it costs per step is
cheap next to the window position a legal-but-certainly-rejected draft
would burn — near the budget horizon the masks bind on most states, and
mis-aligned draft support collapses constrained acceptance to the forced
chains. Free rows (``dfa_id == 0``) draft unmasked from the drafter
scores. EOS is never drafted (a stop must come
from the verified sample, where the engine's done/state bookkeeping handles
it); the drafter stops proposing when only EOS is admissible.

Everything here is pure jnp traced inside the engine's
``_hetero_segment_spec_impl`` executable — no host round-trips per token,
no per-acceptance recompiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mcpx.engine.sampling import NEG_INF
from mcpx.models.gemma.quant import embed_lookup, unembed

# Embedding-EWMA decay of the recurrent drafter state. A constant, not a
# knob: the drafter is untrained by design (no added parameters), and the
# grammar pre-filter — not this mixing weight — carries the acceptance rate
# on constrained rows.
DRAFT_DECAY = 0.5


def advance_drafter_state(hstate, embed, window, n_absorb):
    """Advance the recurrent drafter state over the first ``n_absorb``
    tokens of ``window`` ([B, W] — current token + accepted drafts) in
    CLOSED FORM:

        h' = decay^n · h + Σ_{i<n} decay^(n-1-i) · embed(window[i])

    computed with one embedding gather and a decay-weighted cumulative sum
    — no scan, no per-step ops on the hot path. ``n_absorb`` [B] is the
    per-row accepted count + 1 (the current token is always absorbed; the
    correction becomes the next current token and is absorbed next round).
    """
    B, W = window.shape
    emb = embed_lookup(embed, window, hstate.dtype)  # [B, W, H]
    i_ar = jnp.arange(W, dtype=hstate.dtype)
    # S[m] = Σ_{i<=m} decay^-i · emb[i]; prefix sums give every candidate
    # endpoint at once, then decay^(n-1) renormalises the selected one.
    scaled = emb * (DRAFT_DECAY ** (-i_ar))[None, :, None]
    prefix = jnp.cumsum(scaled, axis=1)  # [B, W, H]
    m = jnp.clip(n_absorb - 1, 0, W - 1)
    sel = jnp.take_along_axis(
        prefix, jnp.broadcast_to(m[:, None, None], (B, 1, emb.shape[2])), axis=1
    )[:, 0]
    n_f = n_absorb.astype(hstate.dtype)
    return (DRAFT_DECAY**n_f)[:, None] * hstate + (
        DRAFT_DECAY ** (m.astype(hstate.dtype))
    )[:, None] * sel


def draft_window(
    embed,  # model embedding table (tied unembedding; quantized ok)
    sdfa: tuple,  # stacked (trans, mask, dist_succ, active_ids, eos_cols)
    dfa_id: jax.Array,  # [B] grammar slot per row
    st: jax.Array,  # [B] DFA state after the current token
    cur: jax.Array,  # [B] current token (last emitted)
    hstate: jax.Array,  # [B, H] recurrent drafter state (pre-cur)
    emitted: jax.Array,  # [B] tokens emitted so far
    budgets: jax.Array,  # [B] per-row decode budgets
    done: jax.Array,  # [B] finished rows
    cons_v: jax.Array,  # [B] constrained flag per row
    free_mask: jax.Array,  # [V] draftable-vocab mask for free rows (no EOS)
    pad_id: int,
    *,
    k: int,
    mode: str,  # "recurrent" | "grammar"
) -> tuple:
    """Propose up to ``k`` draft tokens per row, walking the row's stacked
    grammar DFA as it goes. Returns

      - ``p_toks``  [B, K] proposed token ids (pad where not proposed),
      - ``p_use``   [B, K] proposal validity,
      - ``s_before`` [B, K] DFA state before consuming each proposal
        (``s_before[:, 0] == st``),
      - ``s_fin``   [B] DFA state after the whole proposed chain,
      - ``masks``   [B, K+1, C] the verify window's per-position
        admissibility (budget-finishability with degrade-to-legal,
        ``stacked_window_admissibility`` semantics). Emitted from the walk
        itself: step j already gathered the legal/finishable sets at
        exactly the state position j verifies from, so the verify pass
        pays ZERO extra table gathers for its masks (position K — the
        all-accepted correction slot — is one extra [B, C] lookup at
        ``s_fin``). ``sdfa`` carries ``dist_succ`` (stacked_spec_tables)
        instead of raw ``dist`` so finishability is one gather, not a
        chained transition-then-distance pair.

    Proposals stop permanently at the first position a row cannot draft:
    budget exhausted, no admissible non-EOS column (constrained), or — in
    ``mode="grammar"`` — a branch point (more than one legal column; that
    mode drafts only DFA-forced chains and free rows never draft). A
    stopped row's later mask slots repeat its frozen state's mask with the
    frozen budget index — harmless, because verification can only consume
    mask positions up to the row's accepted count, which the stop bounds.
    The walk chains a THROWAWAY copy of the drafter state over its own
    proposals (see module docstring); the authoritative state is advanced
    over the VERIFIED tokens via :func:`advance_drafter_state` once
    verification has picked them.
    """
    strans, smask, sdist_succ, sactive, seos = sdfa
    B = cur.shape[0]
    b_idx = jnp.arange(B)
    act_rows = sactive[dfa_id]  # [B, C]
    eos_rows = seos[dfa_id]  # [B, C]
    recurrent = mode == "recurrent"

    if recurrent:
        # Drafter state after absorbing the current token — the walk below
        # chains a THROWAWAY copy of it through its own proposals (h must
        # advance per draft step, or a free row — whose proposal nothing
        # else varies — would draft the same argmax token K times and
        # acceptance past position 1 would require the model to repeat
        # itself). The authoritative state is still advanced by the engine
        # over the VERIFIED tokens via :func:`advance_drafter_state`.
        h1 = DRAFT_DECAY * hstate + embed_lookup(embed, cur, hstate.dtype)
        free_ok = ~done
    else:
        h1 = hstate  # carried untouched: grammar mode never scores
        free_ok = jnp.zeros((B,), bool)

    def admissible(s, rem):
        """Legal + budget-finishable (degrade-to-legal) at state ``s``:
        drafting proposes from this support and verification samples under
        it — a draft that is legal but cannot finish within the row's
        remaining budget would be rejected with certainty, so proposing it
        would burn a window position for nothing. Near the budget horizon
        this is what keeps constrained acceptance high rather than
        collapsing to the forced chains."""
        legal = smask[dfa_id, s]  # [B, C] — the grammar pre-filter
        finishable = legal & (
            eos_rows | (sdist_succ[dfa_id, s] <= rem[:, None])
        )
        support = jnp.where(
            jnp.any(finishable, axis=-1, keepdims=True), finishable, legal
        )
        return support, legal

    def step(carry, _):
        s, alive, ej, h = carry
        support, legal = admissible(s, budgets - ej - 1)
        m_prop = support & ~eos_rows  # EOS is sampled at verify, never drafted
        has_prop = jnp.any(m_prop, axis=-1)
        if recurrent:
            # Per-step rescoring against the tied unembedding: one [B, H]
            # @ [H, V] matmul per draft position — the recurrent-drafter
            # chain rule, and well under the full forward each accepted
            # draft saves (the unembedding is one layer of that forward).
            scores = unembed(h, embed)  # [B, V] float32
            c_scores = jnp.take_along_axis(scores, act_rows, axis=-1)
            col = jnp.argmax(
                jnp.where(m_prop, c_scores, NEG_INF), axis=-1
            ).astype(jnp.int32)
            free_tok = jnp.argmax(
                jnp.where(free_mask, scores, NEG_INF), axis=-1
            ).astype(jnp.int32)
        else:
            # Forced-successor drafting: propose only where the legal set
            # is a singleton (the fast-forward forcing rule).
            col = jnp.argmax(m_prop, axis=-1).astype(jnp.int32)
            has_prop = has_prop & (jnp.sum(legal, axis=-1) == 1)
            free_tok = jnp.full((B,), pad_id, jnp.int32)
        c_tok = act_rows[b_idx, col]
        p_tok = jnp.where(cons_v, c_tok, free_tok)
        use = alive & (ej < budgets) & jnp.where(cons_v, has_prop, free_ok)
        s_next = jnp.where(use & cons_v, strans[dfa_id, s, col], s)
        if recurrent:
            h_next = jnp.where(
                use[:, None],
                DRAFT_DECAY * h + embed_lookup(embed, p_tok, h.dtype),
                h,
            )
        else:
            h_next = h
        return (s_next, use, ej + use, h_next), (
            jnp.where(use, p_tok, pad_id),
            use,
            s,
            support,
        )

    # Fully unrolled: K is small and static, and on overhead-bound backends
    # the scan's per-iteration loop machinery would cost more than the walk
    # it wraps — unrolling lets XLA fuse across draft steps.
    (s_fin, _, _, _), (p_toks, p_use, s_before, vmasks) = lax.scan(
        step, (st, ~done, emitted, h1), None, length=k, unroll=max(1, k)
    )
    # Position K (correction slot when all K drafts are accepted): one
    # extra lookup at the chain-end state, budget index emitted + K.
    m_fin, _ = admissible(s_fin, budgets - emitted - k - 1)
    masks = jnp.concatenate(
        [vmasks.transpose(1, 0, 2), m_fin[:, None, :]], axis=1
    )
    return p_toks.T, p_use.T, s_before.T, s_fin, masks
