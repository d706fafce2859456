"""Grammar-constrained DAG-plan decoding: byte DFA × tokenizer product.

The reference ``json.loads``'s raw LLM text and crashes on anything else
(bug B7, reference ``control_plane.py:74``). Here structural validity is
enforced *during* decoding: the plan grammar is a deterministic finite
automaton over BYTES, and for any tokenizer whose tokens denote byte
strings (``token_bytes()``) the byte DFA lifts to a token-level DFA.

**Compact (column-compressed) device tables.** Only a small "active"
subset of the vocabulary is legal in *any* grammar state (JSON structure
bytes, the trie'd service-name alphabet, string characters) — so the
decode-time tables are stored per active COLUMN, not per vocab id:

  - ``ctrans``:     int32 ``[n_states, C]``  (next state per active column)
  - ``cmask``:      bool  ``[n_states, C]``  (allowed columns per state)
  - ``active_ids``: int32 ``[C]``            (token id per column)
  - ``eos_cols``:   bool  ``[C]``            (column is EOS)

and the **entire constrained decode loop runs on-device in compact space**
(state gather → gather the active columns of the logits → mask → sample a
COLUMN → state transition; the sampled column maps back to a token id via
``active_ids``), with zero host round-trips per token. This is the TPU-native
answer to SGLang-style constrained decoding (PAPERS.md): the automaton is
data, not control flow — and column compaction is what lets a 256k-entry
SentencePiece vocab carry a 1k-service registry trie in a few MB of HBM
instead of the ~100 GB a dense ``[S, V]`` table would need (VERDICT r2 #4).

Construction has two paths, chosen by table size:

  - **dense** (small ``S×V``, e.g. the in-tree byte tokenizer or the
    shape-only grammar): the classic vectorised product over the full
    ``[S, V]`` matrix, then active columns are extracted. The full-vocab
    ``transitions``/``mask`` host tables are kept on the object (tests and
    debugging read them).
  - **sparse** (huge ``S×V``, i.e. a registry trie on a subword vocab): a
    BFS product of the byte DFA against a TRIE OVER TOKEN BYTE STRINGS —
    only reachable (state, token) pairs are ever touched, so cost scales
    with the true automaton size, not ``S×V``. Free-string positions make
    most of the vocab active, so this path requires the string positions to
    be trie-constrained (service names always; ``input_keys`` for the
    ``"in"`` lists) and raises ``ValueError`` past a visit budget — callers
    fall back to the shape-only grammar.

The grammar accepted is the planner wire shape (compact keys to cut decode
length; normalised by ``Plan.from_wire``):

    {"steps":[{"s":"<service>","in":["<key>",...],"next":["<service>",...]},...]}

Strings accept any non-control byte except ``"`` and ``\\`` (no escapes —
service names and keys are identifier-like). Nesting is fixed-depth, so a
DFA suffices (no pushdown needed). EOS is legal exactly in the accept state.

**Registry-constrained names** (VERDICT r1 #2): when ``service_names`` is
given, the ``"s"`` and ``"next"`` string positions compile to a byte TRIE
over exactly those names — the model *cannot* emit a service the control
plane doesn't know, turning the reference's prompt-listing convention
(``control_plane.py:65-66``) into a decode-time guarantee. ``input_keys``
optionally does the same for the ``"in"`` lists (payload/output keys from
the registry's schemas). A welcome side effect: deep trie states are
single-successor, so grammar fast-forward speculation swallows most of each
name without sampling.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from mcpx.models.tokenizer import ByteTokenizer

# Bytes permitted inside strings: printable ASCII minus quote and backslash.
# ASCII-only keeps decode(encode(x)) byte-faithful regardless of what the
# model samples (arbitrary high bytes could form invalid UTF-8, which the
# tokenizer's replacement-char decoding would silently rewrite); service
# names and payload keys are identifier-like, so ASCII loses nothing.
_STRING_BYTES = [b for b in range(0x20, 0x7F) if b not in (0x22, 0x5C)]
_QUOTE = 0x22

# Above this many S×V entries the dense product would not fit; build sparsely.
_DENSE_ENTRIES_MAX = 64_000_000
# Multi-byte vocabs pay per-byte-column passes over the whole [S, V] matrix
# in the dense lift; past this size the sparse BFS product is faster.
_DENSE_SUBWORD_MAX = 2_000_000
# Trie-node visit budget for the sparse BFS product — exceeding it means the
# grammar has effectively-free string positions on a huge vocab; callers fall
# back to the shape-only grammar.
_SPARSE_VISIT_BUDGET = 30_000_000


class _Builder:
    def __init__(self) -> None:
        self.transitions: list[dict[int, int]] = []
        self.eos_ok: set[int] = set()

    def state(self) -> int:
        self.transitions.append({})
        return len(self.transitions) - 1

    def link(self, src: int, byte: int, dst: int) -> None:
        existing = self.transitions[src].get(byte)
        if existing is not None and existing != dst:
            raise ValueError(f"nondeterministic byte {byte:#x} at state {src}")
        self.transitions[src][byte] = dst

    def literal(self, src: int, text: str) -> int:
        cur = src
        for b in text.encode("utf-8"):
            nxt = self.state()
            self.link(cur, b, nxt)
            cur = nxt
        return cur

    def string_content(self, entry: int) -> int:
        """``entry`` is the state right after an opening quote. Strings must
        be non-empty (an empty service/key name is grammar-valid JSON that
        ``Plan.from_wire`` would still reject — so the DFA forbids it): the
        first content byte moves to a loop state, and only the loop state
        may close the string. Returns the post-quote state."""
        loop = self.state()
        exit_state = self.state()
        for b in _STRING_BYTES:
            self.link(entry, b, loop)
            self.link(loop, b, loop)
        self.link(loop, _QUOTE, exit_state)
        return exit_state

    def trie(self, entry: int, names: list[bytes]) -> int:
        """``entry`` is the state right after an opening quote. Accepts
        exactly the given names (shared prefixes merge; a name that is a
        strict prefix of another branches on quote-vs-continuation). Returns
        the post-quote state."""
        exit_state = self.state()
        for nm in names:
            cur = entry
            for b in nm:
                nxt = self.transitions[cur].get(b)
                if nxt is None:
                    nxt = self.state()
                    self.link(cur, b, nxt)
                cur = nxt
            self.link(cur, _QUOTE, exit_state)
        return exit_state

    def string_list(self, entry: int, names: list[bytes] | None = None) -> int:
        """``entry`` is the state right after ``[``. Accepts ``]`` (empty) or
        ``"s"(,"s")*]`` where each item is a free string (``names=None``) or
        one of ``names``. Returns the post-``]`` state."""
        exit_state = self.state()
        content = self.state()
        if names:
            after_item = self.trie(content, names)
        else:
            after_item = self.string_content(content)
        # wire: entry --"--> content ; entry --]--> exit
        self.link(entry, _QUOTE, content)
        self.link(entry, ord("]"), exit_state)
        # after_item --,--> quote expected --"--> content ; after_item --]--> exit
        want_quote = self.state()
        self.link(after_item, ord(","), want_quote)
        self.link(want_quote, _QUOTE, content)
        self.link(after_item, ord("]"), exit_state)
        return exit_state

    def empty_list(self, entry: int) -> int:
        """``entry`` is the state right after ``[``. Accepts ONLY ``]`` —
        the typed grammar's list shape when no item is schema-legal (a
        service with no successors, or none of the trie'd keys)."""
        exit_state = self.state()
        self.link(entry, ord("]"), exit_state)
        return exit_state


def _col_bucket(c: int) -> int:
    """Column-pad bucket: next power of two, min 512 — one decode executable
    per bucket, so the generic byte-vocab grammar and realistic registry
    tries (both ~100 active columns) share the warmup-compiled shape."""
    n = 512
    while n < c:
        n *= 2
    return n


@dataclass
class PlanGrammar:
    # Compact token-level tables — THE decode-time representation:
    ctrans: np.ndarray  # [n_states, C] int32
    cmask: np.ndarray  # [n_states, C] bool
    dist: np.ndarray  # [n_states] int32 — min samples (incl. EOS) to finish
    active_ids: np.ndarray  # [C] int32 — token id per column
    eos_cols: np.ndarray  # [C] bool
    cdead: int  # compact-table dead/absorbing state index
    start_state: int  # always 0 (engine invariant)
    # Byte-level DFA (host-side validation: walk()/is_accept()):
    byte_transitions: np.ndarray  # [n_byte_states, 256] int32
    dead_state: int  # byte-DFA dead state (walk() sentinel)
    accept_states: frozenset[int]  # byte-DFA accept states
    tokenizer: "ByteTokenizer"
    # Names the "s"/"next" positions are trie-constrained to (None = free
    # strings). Informational; the constraint lives in the tables.
    service_names: "tuple[str, ...] | None" = None
    # Full-vocab dense host tables — populated by the DENSE construction
    # path only (small vocabs); None when built sparsely.
    transitions: Optional[np.ndarray] = None  # [n_states, V] int32
    mask: Optional[np.ndarray] = None  # [n_states, V] bool

    def __post_init__(self) -> None:
        # Device-resident, padded copies of the compact tables, built lazily
        # by device_tables(). Cached (keyed by the state-pad quantum) so
        # every batch using this grammar shares one HBM copy.
        self._device: "tuple | None" = None
        self._device_pad: int = 0

    @property
    def n_states(self) -> int:
        return self.ctrans.shape[0]

    @property
    def n_active(self) -> int:
        return self.active_ids.shape[0]

    def device_tables(self, pad_multiple: int = 512):
        """(ctrans, cmask, dist_succ, active_ids, eos_cols, inv_cols) as
        device arrays, state dim padded to a multiple of ``pad_multiple``
        and columns padded to ``_col_bucket``. The decode loop takes these
        as ARGUMENTS (not closure constants), so grammars with the same
        padded shape share one compiled executable — a registry update swaps
        tables without recompiling, and recompiles happen only when a pad
        bucket changes. Padding rows/columns are inert: mask False,
        transitions to the dead state, active id PAD (whose logit is masked
        anyway). ``dist_succ`` [S, C] int16 is ``successor_distance``: the
        device's only use of ``dist`` is the budget mask's "can the
        successor still finish", and read through ``trans`` that is one
        scalar gather per (row, position, column) in every forward, while a
        table indexed like ``cmask`` is one row per visited state. The host
        keeps ``self.dist`` (``min_len``). ``inv_cols`` [V] maps token id →
        compact column (or -1 when the token is active in no state) — how
        prompt-lookup draft tokens enter compact column space (engine draft
        speculation)."""
        if self._device is None or self._device_pad != pad_multiple:
            import jax.numpy as jnp

            n, c = self.ctrans.shape
            S = ((n + pad_multiple - 1) // pad_multiple) * pad_multiple
            C = _col_bucket(c)
            trans = np.full((S, C), self.cdead, np.int32)
            trans[:n, :c] = self.ctrans
            mask = np.zeros((S, C), bool)
            mask[:n, :c] = self.cmask
            ids = np.full((C,), self.tokenizer.pad_id, np.int32)
            ids[:c] = self.active_ids
            eos = np.zeros((C,), bool)
            eos[:c] = self.eos_cols
            inv = np.full((self.tokenizer.vocab_size,), -1, np.int32)
            inv[self.active_ids] = np.arange(c, dtype=np.int32)
            self._device = (
                jnp.asarray(trans),
                jnp.asarray(mask),
                jnp.asarray(successor_distance(self, S, C)),
                jnp.asarray(ids),
                jnp.asarray(eos),
                jnp.asarray(inv),
            )
            self._device_pad = pad_multiple
        return self._device

    @property
    def min_len(self) -> int:
        """Fewest sampled tokens (including EOS) of any accepted output."""
        return int(self.dist[self.start_state])

    def is_accept(self, state: int) -> bool:
        return state in self.accept_states

    def walk(self, text: str) -> int:
        """Host-side check: run the BYTE DFA over ``text``; returns final
        state (``dead_state`` on rejection). Tokenizer-independent — a
        decoded output is valid iff its bytes are, however it was split."""
        s = self.start_state
        for b in text.encode("utf-8"):
            s = int(self.byte_transitions[s, b])
        return s


def successor_distance(g: PlanGrammar, S: int, C: int) -> np.ndarray:
    """``dist_succ [S, C]`` int16: the fewest samples (EOS included) to
    finish AFTER taking column c from state s, i.e. ``dist[trans[s, c]]``
    over ``g``'s tables padded to ``[S, C]``, saturated at
    ``DIST_SUCC_MAX``. Pad rows and pad columns lead to the dead state, so
    they read its distance (``_DIST_INF``, saturated). Its one use is the
    budget mask's ``dist_succ <= rem`` with ``rem < max_decode_len``, which
    the saturation leaves exact while ``max_decode_len <= DIST_SUCC_MAX``
    (``MCPXConfig.validate``); half the bytes of an int32 table and read no
    slower on a v5e (PERF.md, PR 34). The one builder of both
    ``device_tables`` and ``stacked_spec_tables``: the homogeneous and the
    heterogeneous budget masks cannot drift."""
    n, c = g.ctrans.shape
    out = np.full((S, C), min(int(g.dist[g.cdead]), DIST_SUCC_MAX), np.int16)
    out[:n, :c] = np.minimum(g.dist[g.ctrans], DIST_SUCC_MAX)
    return out


def build_trivial_grammar(tokenizer=None) -> PlanGrammar:
    """The all-accept DFA occupying stacked-DFA slot 0 in the heterogeneous
    engine: every UNCONSTRAINED slab row carries ``dfa_id == 0`` so the
    fused per-row table gathers stay in range. Its compact tables are shaped
    like any grammar's but deliberately inert:

      - two legal columns in the live state, so grammar fast-forward (which
        forces a token only when exactly ONE column is legal) never forces
        anything for unconstrained rows;
      - self-looping transitions, so a row's state stays pinned at 0;
      - the sampled column is never consulted — unconstrained rows sample
        the full vocabulary and ``jnp.where(cons, ...)`` discards the
        compact-space draw.

    ``walk``/``is_accept`` accept every byte string (state 0 is accepting),
    matching the "no constraint" contract for host-side checks."""
    tok = tokenizer or ByteTokenizer()
    ctrans = np.asarray([[0, 0], [1, 1]], np.int32)  # state 1 = dead
    cmask = np.asarray([[True, True], [False, False]], bool)
    dist = np.asarray([1, _DIST_INF], np.int32)
    byte_trans = np.zeros((2, 256), np.int32)
    byte_trans[1, :] = 1
    return PlanGrammar(
        ctrans=ctrans,
        cmask=cmask,
        dist=dist,
        active_ids=np.asarray([tok.eos_id, tok.bos_id], np.int32),
        eos_cols=np.asarray([True, False], bool),
        cdead=1,
        start_state=0,
        byte_transitions=byte_trans,
        dead_state=1,
        accept_states=frozenset({0}),
        tokenizer=tok,
    )


def stacked_tables(
    grammars: "list[PlanGrammar]", pad_multiple: int = 512
) -> tuple[np.ndarray, ...]:
    """Stack several grammars' compact tables along a new leading axis so a
    per-row ``dfa_id`` can index them inside one fused decode segment
    (heterogeneous batching). Every grammar pads to the COMMON shape — the
    max state pad bucket and the max column bucket over the stack — with the
    same inert padding semantics as ``device_tables`` (mask False,
    transitions to that grammar's dead state, active id PAD, dist inf).
    Returns host arrays ``(trans [G,S,C], mask [G,S,C], dist [G,S],
    active_ids [G,C], eos_cols [G,C])``; the stack's shape depends only on
    the pad buckets, never on G's occupants, so swapping one resident
    grammar for another re-uploads data without changing any executable."""
    if not grammars:
        raise ValueError("stacked_tables needs at least one grammar")
    S = max(
        ((g.n_states + pad_multiple - 1) // pad_multiple) * pad_multiple
        for g in grammars
    )
    C = max(_col_bucket(g.n_active) for g in grammars)
    G = len(grammars)
    pad_id = grammars[0].tokenizer.pad_id
    trans = np.empty((G, S, C), np.int32)
    mask = np.zeros((G, S, C), bool)
    dist = np.full((G, S), _DIST_INF, np.int32)
    ids = np.full((G, C), pad_id, np.int32)
    eos = np.zeros((G, C), bool)
    for gi, g in enumerate(grammars):
        n, c = g.ctrans.shape
        trans[gi, :, :] = g.cdead
        trans[gi, :n, :c] = g.ctrans
        mask[gi, :n, :c] = g.cmask
        dist[gi, :n] = g.dist
        ids[gi, :c] = g.active_ids
        eos[gi, :c] = g.eos_cols
    return trans, mask, dist, ids, eos


def stacked_spec_tables(
    grammars: "list[PlanGrammar]", pad_multiple: int = 512
) -> tuple[np.ndarray, np.ndarray]:
    """Speculative-decoding companions to :func:`stacked_tables`, same
    stack order and pad geometry (state/column buckets MUST match — the
    engine builds both from one slot snapshot):

      - ``dist_succ [G, S, C]`` int16 — ``successor_distance`` of each
        slot (the table ``device_tables`` returns for one grammar), so the
        hot path's budget-finishability check costs ONE gather instead of
        the chained transition-then-distance pair — per draft step AND per
        verify window position;
      - ``inv_cols [G, V]`` int32 — token id → compact column, ``-1``
        where the token is not active in that grammar (the stacked
        counterpart of ``device_tables``'s ``inv_cols``). Lets the verify
        sampling run ONCE in vocab space (admissibility gathered out to
        [B, W, V], one fused draw for constrained and free rows alike) and
        map the winning token back to its column for the DFA advance.
        ``active_ids`` are strictly increasing per grammar, so a vocab-
        space argmax tie-breaks exactly like the compact-space argmax —
        the greedy-parity invariant survives the change of basis.
    """
    if not grammars:
        raise ValueError("stacked_spec_tables needs at least one grammar")
    S = max(
        ((g.n_states + pad_multiple - 1) // pad_multiple) * pad_multiple
        for g in grammars
    )
    C = max(_col_bucket(g.n_active) for g in grammars)
    G = len(grammars)
    V = grammars[0].tokenizer.vocab_size
    dist_succ = np.empty((G, S, C), np.int16)
    inv = np.full((G, V), -1, np.int32)
    for gi, g in enumerate(grammars):
        dist_succ[gi] = successor_distance(g, S, C)
        inv[gi, g.active_ids] = np.arange(g.n_active, dtype=np.int32)
    return dist_succ, inv


def stacked_window_admissibility(sdfa_tables, dfa_id, states, rem):
    """Batched multi-step admissibility masks for a K-token speculation
    window over STACKED grammar tables (jnp arrays; called inside the
    engine's speculative verify executable, ``_hetero_segment_spec_impl``).

    ``states`` [B, W] is the per-position DFA state after consuming the
    window prefix up to that position; ``rem`` [B, W] the remaining sample
    budget at each position (budget minus tokens already emitted minus one
    for the sample itself). Returns [B, W, C] boolean masks in the stack's
    common compact column space: column c is admissible at position (b, w)
    iff it is grammar-legal from ``states[b, w]`` under grammar slot
    ``dfa_id[b]`` AND (it is EOS or its successor can still finish within
    ``rem[b, w]`` samples). When no column is budget-finishable the mask
    degrades to the plain legal set — same semantics as the engine's
    single-step ``_stacked_budget_mask``, vectorised over the window, so a
    speculative verify at position w masks exactly as sequential decode
    would at emission index w (the greedy-parity invariant rests on this).

    REFERENCE implementation: the serving path gets these masks for free
    from the drafter's DFA walk (``speculative.draft_window`` emits the
    mask it computed at each visited state instead of re-gathering the
    whole window here — three [B, W, C] table gathers saved per verify).
    Kept as the spelled-out semantics the scan-emitted masks are
    property-tested against (tests/test_speculative.py).
    """
    import jax.numpy as jnp

    strans, smask, sdist, _sactive, seos = sdfa_tables
    legal = smask[dfa_id[:, None], states]  # [B, W, C]
    succ = strans[dfa_id[:, None], states]  # [B, W, C]
    finishable = legal & (
        seos[dfa_id][:, None, :]
        | (sdist[dfa_id[:, None, None], succ] <= rem[..., None])
    )
    feasible = jnp.any(finishable, axis=-1, keepdims=True)
    return jnp.where(feasible, finishable, legal)


def _validate_trie_names(names, what: str) -> list[bytes]:
    seen = set()
    out: list[bytes] = []
    for nm in names:
        b = nm.encode("utf-8")
        if not b:
            raise ValueError(f"empty {what} cannot be trie-compiled")
        bad = [x for x in b if x not in _STRING_BYTES]
        if bad:
            raise ValueError(
                f"{what} {nm!r} has bytes outside the grammar's "
                f"string alphabet: {bad[:4]}"
            )
        if b not in seen:
            seen.add(b)
            out.append(b)
    return out


def build_plan_grammar(
    tokenizer=None, service_names=None, input_keys=None, services=None
) -> PlanGrammar:
    """Compile the plan grammar. With ``service_names``, the ``"s"`` and
    ``"next"`` string positions accept exactly those names (byte trie);
    with ``input_keys``, the ``"in"`` list items likewise accept exactly
    those keys — without, each accepts any non-empty identifier-like string.
    Raises ``ValueError`` when the requested grammar cannot be compiled
    within budget for this tokenizer (huge subword vocab with free-string
    positions) — callers fall back to a less-constrained grammar.

    **Typed dataflow** (``services``): pass the candidate records (objects
    with ``name``/``input_schema``/``output_schema``) and each step's body
    is conditioned on the service its ``"s"`` named — its ``"in"`` list
    accepts only THAT service's own input keys, and its ``"next"`` list
    only services one of its outputs feeds (shared key, excluding self).
    Incoherent edges stop being representable: the registry-name guarantee
    (VERDICT r1 #2) extended to dataflow validity. State cost is one step
    body per service, so this is for SHORTLIST-tier grammars (the planner
    gates on ``len(services)``; a registry-wide typed grammar at 1k+
    services would multiply states by fan-out and trip the table budget)."""
    tok = tokenizer or ByteTokenizer()
    if services:
        service_names = tuple(s.name for s in services)
    service_names = tuple(service_names) if service_names else None
    names = _validate_trie_names(service_names, "service name") if service_names else None
    keys = _validate_trie_names(input_keys, "input key") if input_keys else None
    g = _Builder()

    start = g.state()
    # The engine's decode loop hard-codes start state 0 (one fewer scalar to
    # plumb through the jit boundary); the builder creates it first.
    assert start == 0
    after_open = g.literal(start, '{"steps":[')

    # --- one item: {"s":"<svc>","in":[...],"next":[...]}
    item_body = g.state()  # the state just after an item's '{'
    g.link(after_open, ord("{"), item_body)
    svc_content_pre = g.literal(item_body, '"s":"')
    want_brace = g.state()  # after ',' in the steps list: expects '{'
    steps_closed = g.state()

    def wire_item_close(item_close: int) -> None:
        # repetition: item_close --,--> '{' --> item_body ; --]--> close
        g.link(item_close, ord(","), want_brace)
        g.link(item_close, ord("]"), steps_closed)

    if services:
        by_name = {s.name: s for s in services}
        # De-duplicated, validated name order (mirrors _validate_trie_names).
        uniq = list(dict.fromkeys(s.name for s in services))
        for name in uniq:
            rec = by_name[name]
            # Extend the shared name trie by hand so each name keeps its
            # OWN terminal: the byte after the closing quote flows into a
            # body specialised to this service.
            cur = svc_content_pre
            for b in name.encode("utf-8"):
                nxt = g.transitions[cur].get(b)
                if nxt is None:
                    nxt = g.state()
                    g.link(cur, b, nxt)
                cur = nxt
            after_svc = g.state()
            g.link(cur, _QUOTE, after_svc)
            in_entry = g.literal(after_svc, ',"in":[')
            own_keys = _validate_trie_names(sorted(rec.input_schema), "input key")
            after_in = (
                g.string_list(in_entry, own_keys)
                if own_keys
                else g.empty_list(in_entry)
            )
            next_entry = g.literal(after_in, ',"next":[')
            outs = set(rec.output_schema)
            allowed = _validate_trie_names(
                [
                    n
                    for n in uniq
                    if n != name and outs & set(by_name[n].input_schema)
                ],
                "service name",
            )
            after_next = (
                g.string_list(next_entry, allowed)
                if allowed
                else g.empty_list(next_entry)
            )
            wire_item_close(g.literal(after_next, "}"))
    else:
        if names:
            after_svc = g.trie(svc_content_pre, names)
        else:
            after_svc = g.string_content(svc_content_pre)
        in_entry = g.literal(after_svc, ',"in":[')
        after_in = g.string_list(in_entry, keys)
        next_entry = g.literal(after_in, ',"next":[')
        after_next = g.string_list(next_entry, names)
        wire_item_close(g.literal(after_next, "}"))

    g.link(want_brace, ord("{"), item_body)
    accept = g.literal(steps_closed, "}")
    g.eos_ok.add(accept)

    # --- dense byte tables (dead state is absorbing: all 256 entries dead)
    n = len(g.transitions) + 1  # + dead state
    dead = n - 1
    byte_trans = np.full((n, 256), dead, np.int32)
    for s, edges in enumerate(g.transitions):
        for b, t in edges.items():
            byte_trans[s, b] = t

    V = tok.vocab_size
    # The dense [S, V] lift walks EVERY (state, token) pair one byte column
    # at a time — the byte tokenizer (all surfaces length 1, identity lift)
    # gets it cheaply at any size, and tiny vocabs keep it as the host-side
    # validation surface (tests cross-check it against the byte walk).
    # Serving-size multi-byte vocabs take the trie-BFS sparse product,
    # which touches only reachable pairs: measured 1.3s vs 21s for the
    # in-tree BPE vocab against a 1k-name registry trie, same automaton.
    token_bytes = tok.token_bytes()
    single_byte = all(b is None or len(b) <= 1 for b in token_bytes)
    dense_budget = _DENSE_ENTRIES_MAX if single_byte else _DENSE_SUBWORD_MAX
    if n * V <= dense_budget:
        trans, mask = _compile_token_tables(byte_trans, dead, g.eos_ok, tok)
        active = np.flatnonzero(mask.any(axis=0)).astype(np.int32)
        ctrans = trans[:, active]
        cmask = mask[:, active]
        eos_cols = active == tok.eos_id
        cdead = dead
        accept_rows = sorted(g.eos_ok)
        dense_trans, dense_mask = trans, mask
    else:
        ctrans, cmask, active, eos_cols, accept_rows, cdead = _sparse_token_tables(
            byte_trans, dead, g.eos_ok, tok
        )
        dense_trans = dense_mask = None

    dist = _distance_to_accept_compact(ctrans, cmask, eos_cols, accept_rows)
    return PlanGrammar(
        ctrans=ctrans,
        cmask=cmask,
        dist=dist,
        active_ids=np.asarray(active, np.int32),
        eos_cols=np.asarray(eos_cols, bool),
        cdead=cdead,
        start_state=start,
        byte_transitions=byte_trans,
        dead_state=dead,
        accept_states=frozenset(g.eos_ok),
        tokenizer=tok,
        service_names=tuple(sorted(service_names)) if service_names else None,
        transitions=dense_trans,
        mask=dense_mask,
    )


def _compile_token_tables(
    byte_trans: np.ndarray,  # [n_states, 256], dead-absorbing
    dead: int,
    eos_ok: set[int],
    tok,
) -> tuple[np.ndarray, np.ndarray]:
    """Lift the byte DFA to the tokenizer's vocabulary: token t from state s
    lands where walking t's bytes lands (product construction, vectorised
    over the whole [n_states, vocab] matrix one byte column at a time). A
    token is legal iff its entire byte string stays inside the grammar —
    for the byte tokenizer this is the identity lift; for subword vocabs
    any tokenization of a valid plan is accepted."""
    n = byte_trans.shape[0]
    V = tok.vocab_size
    token_bytes = tok.token_bytes()
    if len(token_bytes) != V:
        raise ValueError(f"token_bytes() returned {len(token_bytes)} entries for vocab {V}")
    nonempty = np.array([b is not None and len(b) > 0 for b in token_bytes])
    longest = max((len(b) for b in token_bytes if b), default=1)
    bmat = np.full((V, longest), -1, np.int32)
    for t, b in enumerate(token_bytes):
        if b:
            bmat[t, : len(b)] = list(b)

    state = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, V))
    for col in range(longest):
        bc = bmat[:, col]
        act = bc >= 0
        if not act.any():
            break
        state[:, act] = byte_trans[state[:, act], bc[act]]
    trans = state
    trans[:, ~nonempty] = dead  # special/padding tokens never advance
    mask = (trans != dead) & nonempty[None, :]
    for s in eos_ok:
        mask[s, tok.eos_id] = True
        trans[s, tok.eos_id] = dead  # post-EOS state is never consulted
    # PAD self-loops everywhere in the DENSE tables (kept for host-side
    # inspection/tests; the engine freezes finished rows' states explicitly,
    # and PAD is never an active column in the compact tables).
    trans[:, tok.pad_id] = np.arange(n)
    return trans, mask


def _token_trie(tok) -> tuple[list[dict[int, int]], list[list[int]]]:
    """Trie over the vocabulary's token byte strings: ``children[node]`` maps
    byte → node, ``tokens_at[node]`` lists token ids whose bytes end there.
    Cached on the tokenizer object (one vocab = one trie)."""
    cached = getattr(tok, "_mcpx_token_trie", None)
    if cached is not None:
        return cached
    children: list[dict[int, int]] = [{}]
    tokens_at: list[list[int]] = [[]]
    for t, b in enumerate(tok.token_bytes()):
        if not b:
            continue
        node = 0
        for byte in b:
            nxt = children[node].get(byte)
            if nxt is None:
                nxt = len(children)
                children[node][byte] = nxt
                children.append({})
                tokens_at.append([])
            node = nxt
        tokens_at[node].append(t)
    trie = (children, tokens_at)
    try:
        tok._mcpx_token_trie = trie
    except AttributeError:
        pass  # exotic tokenizer without attribute assignment; rebuild next time
    return trie


def _sparse_token_tables(byte_trans, byte_dead, eos_ok, tok):
    """BFS product of the byte DFA with the token trie, touching only
    reachable (state, token) pairs — the construction path for huge vocabs
    where a dense [S, V] matrix cannot exist. Returns compact tables with
    token-reachable states renumbered (start stays 0, dead appended last)."""
    children, tokens_at = _token_trie(tok)
    state_ids: dict[int, int] = {0: 0}
    order: list[int] = [0]
    rows: list[dict[int, int]] = []  # token id -> successor BYTE state
    visits = 0
    qi = 0
    while qi < len(order):
        s = order[qi]
        qi += 1
        row: dict[int, int] = {}
        stack = [(0, s)]
        while stack:
            node, ds = stack.pop()
            visits += 1
            if visits > _SPARSE_VISIT_BUDGET:
                raise ValueError(
                    "grammar×vocab product exceeds the sparse build budget — "
                    "free-string positions on a large subword vocab; "
                    "trie-constrain service names AND input keys, or fall "
                    "back to the shape-only grammar"
                )
            for t in tokens_at[node]:
                row[t] = ds
            for byte, child in children[node].items():
                ns = int(byte_trans[ds, byte])
                if ns != byte_dead:
                    stack.append((child, ns))
        rows.append(row)
        for succ in row.values():
            if succ not in state_ids:
                state_ids[succ] = len(order)
                order.append(succ)

    active = sorted({t for row in rows for t in row} | {tok.eos_id})
    col = {t: c for c, t in enumerate(active)}
    S = len(order) + 1
    cdead = S - 1
    C = len(active)
    ctrans = np.full((S, C), cdead, np.int32)
    cmask = np.zeros((S, C), bool)
    for si, row in enumerate(rows):
        for t, succ in row.items():
            ctrans[si, col[t]] = state_ids[succ]
            cmask[si, col[t]] = True
    eos_cols = np.zeros((C,), bool)
    eos_cols[col[tok.eos_id]] = True
    accept_rows = [state_ids[s] for s in eos_ok if s in state_ids]
    for r in accept_rows:
        cmask[r, col[tok.eos_id]] = True  # ctrans stays dead: post-EOS unused
    return ctrans, cmask, np.asarray(active, np.int32), eos_cols, accept_rows, cdead


_DIST_INF = np.iinfo(np.int32).max // 2
# Where the device's int16 successor-distance table saturates; also the
# largest ``engine.max_decode_len`` the budget mask stays exact for.
DIST_SUCC_MAX = int(np.iinfo(np.int16).max)


def _distance_to_accept_compact(
    ctrans: np.ndarray,  # [S, C]
    cmask: np.ndarray,  # [S, C]
    eos_cols: np.ndarray,  # [C]
    accept_rows,
) -> np.ndarray:
    """``dist[s]`` = fewest sampled tokens to *finish* from state ``s``
    (counting the final EOS sample). Value iteration to fixpoint over the
    compact token graph (tokens may span several bytes, so this is shortest
    path in SAMPLES, which is what the decode budget counts). The decode
    loop uses this to force the JSON closed before the token budget runs
    out — a budget-bounded constrained decode is never truncated mid-plan."""
    S = ctrans.shape[0]
    gen = cmask & ~eos_cols[None, :]
    dist = np.full((S,), _DIST_INF, np.int32)
    for s in accept_rows:
        dist[s] = 1
    # Converges in (longest min-completion length) sweeps, not S.
    for _ in range(S + 1):
        succ = np.where(gen, dist[ctrans], _DIST_INF)  # [S, C]
        nd = np.minimum(dist, succ.min(axis=1, initial=_DIST_INF) + 1)
        if np.array_equal(nd, dist):
            break
        dist = nd
    return np.minimum(dist, _DIST_INF).astype(np.int32)
