"""Grammar DFA tests: acceptance, rejection, mask/transition table
consistency, and device-side constrained sampling (SURVEY.md §4.1)."""

import numpy as np
import pytest

from mcpx.core.dag import Plan
from mcpx.models.tokenizer import ByteTokenizer
from mcpx.planner.grammar import DIST_SUCC_MAX, build_plan_grammar


def test_accepts_valid_plans():
    g = build_plan_grammar()
    for text in [
        '{"steps":[{"s":"search","in":["query"],"next":["sum"]},{"s":"sum","in":[],"next":[]}]}',
        '{"steps":[{"s":"a","in":[],"next":[]}]}',
        '{"steps":[{"s":"a","in":["x","y"],"next":[]},{"s":"b","in":[],"next":[]}]}',
    ]:
        final = g.walk(text)
        assert g.is_accept(final), text
        # And what the grammar accepts, the Plan parser accepts.
        plan = Plan.from_json(text)
        assert plan.nodes


def test_rejects_invalid():
    g = build_plan_grammar()
    for text in [
        '{"steps":[]}',  # empty steps not allowed
        '{"steps":[{"s":"a"}]}',  # missing keys
        '{"nodes":[]}',  # wrong envelope
        '{"steps":[{"s":"a","in":[],"next":[]}]',  # unterminated
        'plain text',
        '{"steps":[{"s":"a\\"","in":[],"next":[]}]}',  # escape rejected
        '{"steps":[{"s":"","in":[],"next":[]}]}',  # empty service name
        '{"steps":[{"s":"a","in":[""],"next":[]}]}',  # empty key
    ]:
        assert not g.is_accept(g.walk(text)), text


def test_mask_matches_transitions():
    g = build_plan_grammar()
    tok = ByteTokenizer()
    # Wherever mask is True (except EOS in accept), transition is not dead.
    live = g.mask.copy()
    live[:, tok.eos_id] = False
    assert np.all(g.transitions[live] != g.dead_state)
    # Dead state allows nothing.
    assert not g.mask[g.dead_state].any()
    # PAD never allowed, self-loops everywhere.
    assert not g.mask[:, tok.pad_id].any()
    assert np.array_equal(g.transitions[:, tok.pad_id], np.arange(g.n_states))


def test_greedy_walk_emits_valid_json():
    """Following any allowed token from start must eventually be able to
    reach accept: simulate a random-but-legal walk and parse the result."""
    rng = np.random.default_rng(0)
    g = build_plan_grammar()
    tok = ByteTokenizer()
    state = g.start_state
    out = []
    closers = [tok.eos_id, ord('"'), ord("]"), ord("}")]
    for _ in range(600):
        allowed = set(np.flatnonzero(g.mask[state]).tolist())
        assert allowed, f"stuck at state {state} after {len(out)} bytes"
        out_tok = None
        # After a while, prefer closing constructs so the walk terminates.
        if len(out) > 60:
            for c in closers:
                if c in allowed:
                    out_tok = c
                    break
        if out_tok is None:
            out_tok = int(rng.choice(sorted(allowed)))
        if out_tok == tok.eos_id:
            break
        out.append(out_tok)
        state = int(g.transitions[state, out_tok])
    text = tok.decode(out)
    assert g.is_accept(g.walk(text)), text
    # The grammar guarantees *structure*: always-parseable JSON in the steps
    # shape. Referential integrity (next-steps naming real steps) is the LLM
    # planner's bounded-retry responsibility, not the DFA's.
    import json

    obj = json.loads(text)
    assert isinstance(obj["steps"], list) and obj["steps"]
    assert all(set(s) == {"s", "in", "next"} for s in obj["steps"])


def test_compact_keys_parse_to_plan():
    text = '{"steps":[{"s":"fetch","in":["query"],"next":["rank"]},{"s":"rank","in":["doc"],"next":[]}]}'
    plan = Plan.from_json(text)
    assert [n.name for n in plan.nodes] == ["fetch", "rank"]
    assert plan.topological_generations() == [["fetch"], ["rank"]]


def test_distance_to_accept():
    """dist[s] must be the exact shortest completion length: simulate the
    greedy 'always move closer' walk from every reachable state and check it
    finishes in exactly dist[s] samples."""
    g = build_plan_grammar()
    tok = ByteTokenizer()
    inf = np.iinfo(np.int32).max // 2
    # Accept states are one EOS sample away.
    for s in g.accept_states:
        assert g.dist[s] == 1
    assert g.dist[g.dead_state] >= inf
    assert g.min_len == g.dist[g.start_state]
    # The shortest valid plan really is min_len bytes + EOS.
    shortest = '{"steps":[{"s":"?","in":[],"next":[]}]}'
    assert g.is_accept(g.walk(shortest))
    assert g.min_len == len(shortest) + 1
    # Greedy-descent from every live reachable state terminates in dist[s].
    reachable = {g.start_state}
    frontier = [g.start_state]
    while frontier:
        nxt = []
        for s in frontier:
            for b in np.flatnonzero(g.mask[s]):
                t = int(g.transitions[s, b])
                if t != g.dead_state and t not in reachable:
                    reachable.add(t)
                    nxt.append(t)
        frontier = nxt
    for s in sorted(reachable):
        d = int(g.dist[s])
        assert d < inf, f"reachable state {s} cannot finish"
        state, taken = s, 0
        while state not in g.accept_states:
            allowed = np.flatnonzero(g.mask[state])
            succ = [
                (int(g.dist[int(g.transitions[state, b])]), int(b))
                for b in allowed
                if b != tok.eos_id
            ]
            db, b = min(succ)
            assert db == int(g.dist[state]) - 1  # BFS consistency
            state = int(g.transitions[state, b])
            taken += 1
        assert taken + 1 == d, f"state {s}: took {taken}+EOS, dist={d}"


def test_budget_mask_never_strands():
    """Emulate the engine's budget mask host-side: any walk that only takes
    tokens allowed by (grammar AND budget) finishes within the budget."""
    rng = np.random.default_rng(1)
    g = build_plan_grammar()
    tok = ByteTokenizer()
    for budget in [g.min_len, g.min_len + 1, g.min_len + 7, 96]:
        for trial in range(20):
            state, emitted, text = g.start_state, 0, []
            while True:
                rem = budget - emitted - 1  # samples left after this one
                allowed = [
                    int(b)
                    for b in np.flatnonzero(g.mask[state])
                    if b == tok.eos_id or int(g.dist[int(g.transitions[state, b])]) <= rem
                ]
                assert allowed, f"stranded at {state} budget={budget} emitted={emitted}"
                b = int(rng.choice(allowed))
                emitted += 1
                if b == tok.eos_id:
                    break
                text.append(b)
                state = int(g.transitions[state, b])
                assert emitted < budget, "budget exceeded without EOS"
            decoded = tok.decode(text)
            assert g.is_accept(g.walk(decoded)), decoded


class ToySubwordTokenizer:
    """Synthetic multi-byte tokenizer (SentencePiece stand-in): all single
    bytes plus merged JSON-structure fragments and service-name pieces —
    exercises the grammar's token-DFA product without external model files."""

    MERGES = [b'{"steps":[{"s":"', b'","in":[', b'"],"next":[', b'"]}',
              b'auth', b'fetch', b'-00', b'"]},{"s":"', b'{"s":"', b'": "', b'xyz']

    def __init__(self):
        self._pieces = [bytes([i]) for i in range(256)] + list(self.MERGES)
        self.pad_id = len(self._pieces)
        self.bos_id = self.pad_id + 1
        self.eos_id = self.pad_id + 2
        raw = self.eos_id + 1
        self.vocab_size = ((raw + 127) // 128) * 128

    def token_bytes(self):
        out = list(self._pieces)
        out += [None] * (self.vocab_size - len(out))
        return out

    def encode(self, text, *, bos=True, eos=False):
        data = text.encode("utf-8")
        ids, i = ([self.bos_id] if bos else []), 0
        by_len = sorted(range(256, len(self._pieces)), key=lambda t: -len(self._pieces[t]))
        while i < len(data):
            for t in by_len:
                p = self._pieces[t]
                if data.startswith(p, i):
                    ids.append(t)
                    i += len(p)
                    break
            else:
                ids.append(data[i])
                i += 1
        return ids + ([self.eos_id] if eos else [])

    def decode(self, ids):
        return b"".join(self._pieces[i] for i in ids if 0 <= i < len(self._pieces)).decode(
            "utf-8", errors="replace"
        )


def test_subword_product_matches_byte_walk():
    """Token-level transitions == walking each token's bytes through the
    byte DFA, for every (state, token)."""
    tok = ToySubwordTokenizer()
    g = build_plan_grammar(tok)
    tb = tok.token_bytes()
    rng = np.random.default_rng(0)
    states = rng.integers(0, g.n_states, size=40)
    tokens = list(rng.integers(0, tok.vocab_size, size=60)) + [256, 257, 258, 259, 263]
    for s in states:
        for t in tokens:
            b = tb[t]
            if t in (tok.eos_id, tok.pad_id) or b is None or not b:
                continue
            expect = int(s)
            for byte in b:
                expect = int(g.byte_transitions[expect, byte])
            assert int(g.transitions[s, t]) == expect, (s, t, b)
            assert bool(g.mask[s, t]) == (expect != g.dead_state)


def test_subword_constrained_walk_emits_valid_json():
    """A constrained greedy walk over the SUBWORD vocab must emit bytes the
    grammar accepts — multi-byte fragments included — and round-trip
    through Plan.from_json."""
    import json as _json
    import random

    tok = ToySubwordTokenizer()
    g = build_plan_grammar(tok)
    rng = random.Random(5)
    for trial in range(10):
        state, ids, emitted = g.start_state, [], 0
        budget = 96
        while True:
            rem = budget - emitted - 1
            allowed = [
                int(t)
                for t in np.flatnonzero(g.mask[state])
                if t == tok.eos_id or int(g.dist[int(g.transitions[state, t])]) <= rem
            ]
            assert allowed, f"stranded at {state}"
            t = rng.choice(allowed)
            emitted += 1
            if t == tok.eos_id:
                break
            ids.append(t)
            state = int(g.transitions[state, t])
        decoded = tok.decode(ids)
        assert g.is_accept(g.walk(decoded)), decoded
        _json.loads(decoded)


def test_subword_dist_counts_samples_not_bytes():
    """min_len over a subword vocab must be <= the byte vocab's min_len:
    merged fragments cover several bytes per sample."""
    byte_g = build_plan_grammar(ByteTokenizer())
    sub_g = build_plan_grammar(ToySubwordTokenizer())
    assert sub_g.min_len <= byte_g.min_len
    assert sub_g.min_len >= 4  # still needs items + closes + EOS


def test_byte_tokenizer_product_is_identity_lift():
    """For the byte tokenizer the token DFA must equal the byte DFA on byte
    ids (the product is the identity lift)."""
    tok = ByteTokenizer()
    g = build_plan_grammar(tok)
    np.testing.assert_array_equal(g.transitions[:, :256], g.byte_transitions)


# --- registry-constrained name tries (VERDICT r1 #2) -----------------------


def test_trie_accepts_only_listed_names():
    tok = ByteTokenizer()
    names = ["auth-fetch", "auth-verify", "billing", "notify"]
    g = build_plan_grammar(tok, names)
    assert g.service_names == tuple(sorted(names))
    ok = '{"steps":[{"s":"auth-fetch","in":["q"],"next":["notify"]}]}'
    assert g.is_accept(g.walk(ok))
    # unknown service name in "s" or "next" dies mid-string
    assert g.walk('{"steps":[{"s":"auth-zzz","in":[],"next":[]}]}') == g.dead_state
    assert g.walk('{"steps":[{"s":"billing","in":[],"next":["ghost"]}]}') == g.dead_state
    # truncated legal prefix cannot close the string
    assert g.walk('{"steps":[{"s":"auth","in":[],"next":[]}]}') == g.dead_state
    # "in" keys stay free-form
    assert g.is_accept(g.walk('{"steps":[{"s":"billing","in":["anything at all"],"next":[]}]}'))


def test_typed_grammar_only_admits_schema_valid_bodies():
    """Typed-dataflow construction: each step's body is conditioned on the
    service its "s" named — "in" admits only that service's own input keys,
    "next" only services one of its outputs feeds (no self). Incoherent
    edges are UNREPRESENTABLE, extending the registry-name guarantee to
    dataflow validity (the shortlist serving tier's grammar)."""
    from mcpx.registry.base import ServiceRecord

    recs = [
        ServiceRecord(
            name="fetch",
            endpoint="local://fetch",
            input_schema={"query": "str"},
            output_schema={"data": "str"},
        ),
        ServiceRecord(
            name="summarize",
            endpoint="local://sum",
            input_schema={"data": "str"},
            output_schema={"summary": "str"},
        ),
        ServiceRecord(
            name="audit",
            endpoint="local://audit",
            input_schema={"report": "str"},
            output_schema={},
        ),
    ]
    g = build_plan_grammar(ByteTokenizer(), services=recs)
    assert g.service_names == tuple(sorted(r.name for r in recs))
    # Schema-valid: fetch(data) -> summarize(data->summary); own keys only.
    ok = (
        '{"steps":[{"s":"fetch","in":["query"],"next":["summarize"]},'
        '{"s":"summarize","in":["data"],"next":[]}]}'
    )
    assert g.is_accept(g.walk(ok))
    # fetch's outputs feed NO input of audit: the edge is unrepresentable.
    assert g.walk('{"steps":[{"s":"fetch","in":[],"next":["audit"]}]}') == g.dead_state
    # "in" is typed per-service: fetch has no "data" input.
    assert g.walk('{"steps":[{"s":"fetch","in":["data"],"next":[]}]}') == g.dead_state
    # No self-edges, even when schemas would chain.
    assert g.walk('{"steps":[{"s":"fetch","in":[],"next":["fetch"]}]}') == g.dead_state
    # audit produces nothing -> its "next" can only be the empty list.
    assert g.is_accept(g.walk('{"steps":[{"s":"audit","in":["report"],"next":[]}]}'))
    assert (
        g.walk('{"steps":[{"s":"audit","in":["report"],"next":["fetch"]}]}')
        == g.dead_state
    )
    # Empty "in" stays legal everywhere (payload-only steps).
    assert g.is_accept(g.walk('{"steps":[{"s":"summarize","in":[],"next":[]}]}'))


def test_typed_grammar_greedy_walks_stay_schema_valid():
    """Every token-greedy path through the typed tables decodes to a plan
    whose edges ALL typecheck — the structural claim the shortlist tier's
    coherence rests on."""
    import json as _json
    import random

    from mcpx.registry.base import ServiceRecord
    from mcpx.utils.synth import synth_registry

    recs = synth_registry(6, seed=3)
    by_name = {r.name: r for r in recs}
    g = build_plan_grammar(ByteTokenizer(), services=recs)
    rng = random.Random(0)
    for _ in range(25):
        state, out = g.start_state, []
        for _step in range(220):
            legal = [c for c in range(g.cmask.shape[1]) if g.cmask[state, c]]
            col = rng.choice(legal)
            if g.eos_cols[col]:
                break
            out.append(int(g.active_ids[col]))
            state = int(g.ctrans[state, col])
        else:
            continue  # walk didn't terminate: skip (budget tests cover it)
        obj = _json.loads(ByteTokenizer().decode(out))
        for step in obj["steps"]:
            src = by_name[step["s"]]
            assert set(step["in"]) <= set(src.input_schema)
            for nxt in step["next"]:
                assert set(src.output_schema) & set(by_name[nxt].input_schema)
                assert nxt != step["s"]


def test_trie_prefix_name_branches_on_quote():
    g = build_plan_grammar(ByteTokenizer(), ["auth", "auth-fetch"])
    assert g.is_accept(g.walk('{"steps":[{"s":"auth","in":[],"next":["auth-fetch"]}]}'))
    assert g.is_accept(g.walk('{"steps":[{"s":"auth-fetch","in":[],"next":["auth"]}]}'))
    assert g.walk('{"steps":[{"s":"auth-","in":[],"next":[]}]}') == g.dead_state


def test_trie_random_legal_walk_names_only_registry_services():
    """Any mask-legal walk through a trie grammar must terminate in plans
    whose every service name is a listed one — the decode-time guarantee
    the planner's accept path relies on."""
    import json as _json

    rng = np.random.default_rng(3)
    tok = ByteTokenizer()
    names = ["svc-alpha", "svc-beta", "other-gamma"]
    g = build_plan_grammar(tok, names)
    for trial in range(5):
        state = g.start_state
        ids = []
        emitted = 0
        while emitted < 300:
            rem = 300 - emitted
            allowed = [
                int(t)
                for t in np.flatnonzero(g.mask[state])
                if t == tok.eos_id or int(g.dist[int(g.transitions[state, t])]) <= rem
            ]
            assert allowed, f"stranded at {state}"
            t = int(rng.choice(allowed))
            emitted += 1
            if t == tok.eos_id:
                break
            ids.append(t)
            state = int(g.transitions[state, t])
        text = tok.decode(ids)
        assert g.is_accept(g.walk(text)), text
        obj = _json.loads(text)
        for step in obj["steps"]:
            assert step["s"] in names
            assert all(nx in names for nx in step["next"])


def test_trie_rejects_unencodable_names():
    with pytest.raises(ValueError):
        build_plan_grammar(ByteTokenizer(), ['has"quote'])
    with pytest.raises(ValueError):
        build_plan_grammar(ByteTokenizer(), [""])


def test_device_tables_pad_and_share():
    tok = ByteTokenizer()
    g = build_plan_grammar(tok, ["a-svc", "b-svc"])
    trans, mask, dist_succ, active_ids, eos_cols, inv_cols = g.device_tables()
    n, c = g.ctrans.shape
    assert trans.shape[0] % 512 == 0 and trans.shape[0] >= n
    assert trans.shape[1] >= c and trans.shape == mask.shape
    assert dist_succ.shape == trans.shape
    assert active_ids.shape == eos_cols.shape == (trans.shape[1],)
    # same objects on second call (one HBM copy per grammar)
    t2 = g.device_tables()
    assert t2[0] is trans and t2[1] is mask and t2[2] is dist_succ
    # padded rows/cols: unreachable, all-False mask, dead transitions
    assert not bool(np.asarray(mask)[n:].any())
    assert not bool(np.asarray(mask)[:, c:].any())
    assert np.all(np.asarray(trans)[n:] == g.cdead)
    # real rows match compact host tables, which match the dense tables'
    # active columns (dense path keeps both forms coherent)
    np.testing.assert_array_equal(np.asarray(trans)[:n, :c], g.ctrans)
    np.testing.assert_array_equal(np.asarray(mask)[:n, :c], g.cmask)
    np.testing.assert_array_equal(
        np.asarray(dist_succ)[:n, :c], np.minimum(g.dist[g.ctrans], DIST_SUCC_MAX)
    )
    np.testing.assert_array_equal(g.ctrans, g.transitions[:, g.active_ids])
    np.testing.assert_array_equal(g.cmask, g.mask[:, g.active_ids])
    # EOS is an active column; PAD never is
    assert tok.eos_id in g.active_ids
    assert tok.pad_id not in g.active_ids
    assert bool(g.eos_cols[np.flatnonzero(g.active_ids == tok.eos_id)[0]])
    # inv_cols is the exact inverse of active_ids; inactive ids map to -1
    inv_np = np.asarray(inv_cols)
    assert inv_np.shape == (tok.vocab_size,)
    np.testing.assert_array_equal(inv_np[g.active_ids], np.arange(c))
    assert inv_np[tok.pad_id] == -1


def _table_grammar(kind: str, vocab: str):
    """One grammar of each construction the planner serves, over either
    in-tree tokenizer."""
    from mcpx.models.tokenizer import make_tokenizer
    from mcpx.registry.base import ServiceRecord

    tok = make_tokenizer(vocab)
    if kind == "generic":
        return build_plan_grammar(tok)
    if kind == "trie":
        names = [f"svc-{k}-{i:02d}" for k in ("fetch", "rank") for i in range(6)]
        return build_plan_grammar(tok, names, input_keys=["query", "user_id"])
    recs = [
        ServiceRecord(name="fetch", endpoint="local://f",
                      input_schema={"query": "str"}, output_schema={"data": "str"}),
        ServiceRecord(name="summarize", endpoint="local://s",
                      input_schema={"data": "str"}, output_schema={"summary": "str"}),
        ServiceRecord(name="audit", endpoint="local://a",
                      input_schema={"summary": "str"}, output_schema={}),
    ]
    return build_plan_grammar(tok, services=recs)


def _reachable_states(g) -> np.ndarray:
    seen, frontier = {g.start_state}, [g.start_state]
    while frontier:
        s = frontier.pop()
        for t in np.unique(g.ctrans[s][g.cmask[s] & ~g.eos_cols]):
            if int(t) not in seen:
                seen.add(int(t))
                frontier.append(int(t))
    return np.asarray(sorted(seen), np.int32)


@pytest.mark.parametrize("vocab", ["byte", "bpe"])
@pytest.mark.parametrize("kind", ["generic", "trie", "typed"])
def test_dist_succ_table_is_the_chained_distance(kind, vocab):
    """``device_tables()``'s third member is ``dist[trans]`` on EVERY padded
    cell (saturated at int16's maximum, which no budget reaches), and the
    engine's budget mask read from it by row equals the chained
    transition-then-distance formula it replaced, at every reachable state
    and every remaining budget the engine can pass."""
    import jax
    import jax.numpy as jnp

    from mcpx.core.config import EngineConfig
    from mcpx.engine.engine import InferenceEngine
    from mcpx.planner.grammar import _DIST_INF

    g = _table_grammar(kind, vocab)
    dfa = g.device_tables(512)
    trans, mask, dist_succ, _ids, eos = (np.asarray(t) for t in dfa[:5])
    S, C = trans.shape
    n, c = g.ctrans.shape
    dist = np.full((S,), _DIST_INF, np.int32)
    dist[:n] = g.dist
    assert dist_succ.dtype == np.int16 and dist_succ.shape == (S, C)
    np.testing.assert_array_equal(dist_succ, np.minimum(dist[trans], DIST_SUCC_MAX))
    # pad rows and pad columns lead to the dead state
    assert (dist_succ[n:] == DIST_SUCC_MAX).all() and (dist_succ[:, c:] == DIST_SUCC_MAX).all()
    assert EngineConfig().max_decode_len <= DIST_SUCC_MAX  # validate() holds every config to it

    st = _reachable_states(g)
    assert len(st) > 10 and int(g.dist[st].max()) < _DIST_INF
    legal = mask[st]
    chained = dist[trans[st]]  # what the device gathered scalar by scalar
    new = jax.jit(
        lambda rem: InferenceEngine._budget_mask(
            None, dfa[:5], jnp.asarray(st), jnp.full(st.shape, rem, jnp.int32)
        )
    )
    # ... and the largest a valid config can: max_decode_len - 1 at its cap
    for rem in (*range(-1, EngineConfig().max_decode_len + 1), DIST_SUCC_MAX - 1):
        fin = legal & (eos[None, :] | (chained <= rem))
        old = np.where(fin.any(-1, keepdims=True), fin, legal)
        np.testing.assert_array_equal(np.asarray(new(rem)), old, err_msg=f"rem={rem}")


def test_stacked_spec_table_is_the_device_table():
    """One builder serves both paths: a grammar's slot of the heterogeneous
    speculative stack is the very table ``device_tables`` gives the
    homogeneous path."""
    from mcpx.planner.grammar import build_trivial_grammar, stacked_spec_tables

    g = _table_grammar("trie", "byte")
    triv = build_trivial_grammar(g.tokenizer)
    own = np.asarray(g.device_tables(512)[2])
    sdist_succ, _inv = stacked_spec_tables([triv, g], 512)
    np.testing.assert_array_equal(sdist_succ[1], own)
    # the trivial slot at the stack's common shape: its live state's two
    # self-loops finish in one sample, everything else leads to its dead state
    want = np.full(own.shape, DIST_SUCC_MAX, np.int16)
    want[0, :2] = 1
    np.testing.assert_array_equal(sdist_succ[0], want)


def test_engine_pad_makes_registry_grammar_share_warmup_shape():
    """The engine's pad quanta must give the generic grammar and a realistic
    registry trie identical padded table shapes — that equality is what lets
    the warmup-compiled decode executable serve real requests without an
    in-path XLA compile."""
    from mcpx.engine.engine import InferenceEngine

    eng = InferenceEngine()
    pad = eng._grammar_pad()
    generic = eng.grammar.device_tables(pad)
    names = [f"svc-{kind}-{i:04d}" for kind in ("fetch", "rank", "notify") for i in range(50)]
    trie = build_plan_grammar(ByteTokenizer(), names)
    dev = trie.device_tables(pad)
    for a, b in zip(generic, dev):
        assert a.shape == b.shape


def _subword_tok(pieces: list[str], vocab_pad: int = 0):
    """Minimal multi-byte-token tokenizer for exercising the grammar product
    on subword vocabs without external files: bytes 0..255 are always
    present (byte fallback), then the given pieces, then PAD/BOS/EOS."""

    class SubwordTok:
        def __init__(self) -> None:
            self.pieces = [bytes([i]) for i in range(256)] + [
                p.encode("utf-8") for p in pieces
            ]
            self.pad_id = len(self.pieces)
            self.bos_id = self.pad_id + 1
            self.eos_id = self.pad_id + 2
            self.vocab_size = self.pad_id + 3 + vocab_pad

        def token_bytes(self):
            out = list(self.pieces)
            out += [None] * (self.vocab_size - len(out))
            return out

        def decode(self, ids):
            data = b"".join(
                self.pieces[i] for i in ids if 0 <= i < len(self.pieces)
            )
            return data.decode("utf-8", errors="replace")

    return SubwordTok()


def test_sparse_product_matches_dense():
    """The sparse BFS product (huge-vocab path) must accept exactly the same
    strings as the dense product: equal min_len, equal legal-token sets
    along a greedy walk, and a full emitted plan that byte-walks to accept."""
    import mcpx.planner.grammar as G

    names = ["alpha-svc", "alpine-svc", "beta"]
    keys = ["user_id", "query"]
    pieces = ['{"steps":[{"s":"', 'alpha', '-svc', '","in":[', '"user_id"',
              '],"next":[', ']}', ']}'[0], 'alp', 'beta', '"query"', '",']
    tok = _subword_tok(pieces)
    dense = G.build_plan_grammar(tok, names, input_keys=keys)
    assert dense.transitions is not None  # small vocab -> dense path

    # Force the sparse path by shrinking the dense-entries budgets
    # (subword vocabs gate on _DENSE_SUBWORD_MAX since the BPE speedup).
    old = G._DENSE_ENTRIES_MAX, G._DENSE_SUBWORD_MAX
    G._DENSE_ENTRIES_MAX = G._DENSE_SUBWORD_MAX = 1
    try:
        sparse = G.build_plan_grammar(tok, names, input_keys=keys)
    finally:
        G._DENSE_ENTRIES_MAX, G._DENSE_SUBWORD_MAX = old
    assert sparse.transitions is None  # sparse path taken

    assert sparse.min_len == dense.min_len
    # Same active token set.
    np.testing.assert_array_equal(sparse.active_ids, dense.active_ids)

    # Greedy forced-completion walk through BOTH automata emits identical
    # token sequences and lands in accept.
    def emit(g):
        st, out = g.start_state, []
        for _ in range(200):
            legal = np.flatnonzero(g.cmask[st])
            assert legal.size, (st, out)
            # prefer EOS when legal, else smallest finishing column
            eos_legal = [c for c in legal if g.eos_cols[c]]
            if eos_legal:
                return out, True
            c = min(legal, key=lambda c: int(g.dist[int(g.ctrans[st, c])]))
            out.append(int(g.active_ids[c]))
            st = int(g.ctrans[st, c])
        return out, False

    toks_d, done_d = emit(dense)
    toks_s, done_s = emit(sparse)
    assert done_d and done_s
    assert toks_d == toks_s
    text = tok.decode(toks_d)
    assert dense.is_accept(dense.walk(text)), text
    assert sparse.is_accept(sparse.walk(text)), text


def test_sparse_free_strings_exceed_budget():
    """Free-string positions on a large vocab must raise (the planner then
    falls back through key tries to the shape-only grammar) rather than
    building an enormous table."""
    import mcpx.planner.grammar as G

    tok = _subword_tok([f"piece{i}" for i in range(50)])
    old_dense = G._DENSE_ENTRIES_MAX, G._DENSE_SUBWORD_MAX
    old_budget = G._SPARSE_VISIT_BUDGET
    G._DENSE_ENTRIES_MAX = G._DENSE_SUBWORD_MAX = 1
    G._SPARSE_VISIT_BUDGET = 300
    try:
        import pytest

        with pytest.raises(ValueError, match="budget"):
            # names trie'd but "in" keys free -> permissive states blow the
            # visit budget at this (artificially tiny) setting
            G.build_plan_grammar(tok, ["alpha-svc"])
    finally:
        G._DENSE_ENTRIES_MAX, G._DENSE_SUBWORD_MAX = old_dense
        G._SPARSE_VISIT_BUDGET = old_budget


def test_stacked_tables_step_identical_to_single():
    """Heterogeneous batching stacks several grammars' compact tables along
    a leading slot axis (engine per-row dfa_id indexing). Stepping through
    the stacked tables must be token-for-token identical to stepping the
    original per-grammar tables — legal sets, transitions, eos columns,
    active ids and distance-to-accept all agree at every state of random
    legal walks, per grammar, per slot."""
    import random

    from mcpx.planner.grammar import build_trivial_grammar, stacked_tables

    tok = ByteTokenizer()
    g_plain = build_plan_grammar(tok)
    g_trie = build_plan_grammar(tok, ["svc-a", "svc-b", "other-name"])
    triv = build_trivial_grammar(tok)
    strans, smask, sdist, sids, seos = stacked_tables([triv, g_plain, g_trie])
    assert strans.shape[0] == 3 and strans.shape == smask.shape
    for gi, g in ((1, g_plain), (2, g_trie)):
        C = g.n_active
        assert np.array_equal(sids[gi, :C], g.active_ids)
        assert np.array_equal(seos[gi, :C], g.eos_cols)
        assert not smask[gi, :, C:].any()  # padding columns inert
        assert np.array_equal(sdist[gi, : g.n_states], g.dist)
        rng = random.Random(gi)
        for _walk in range(10):
            s = g.start_state
            for _step in range(80):
                legal = np.flatnonzero(g.cmask[s])
                assert np.array_equal(legal, np.flatnonzero(smask[gi, s]))
                if len(legal) == 0:
                    break
                c = int(rng.choice(list(legal)))
                if g.eos_cols[c]:
                    break
                nxt = int(g.ctrans[s, c])
                assert nxt == int(strans[gi, s, c])
                s = nxt


def test_trivial_grammar_never_forces_and_accepts_everything():
    """The trivial slot-0 DFA (unconstrained rows): grammar fast-forward
    forces a token only when exactly ONE column is legal, so no trivial
    state may have a single-column mask; host-side walk accepts any text."""
    from mcpx.planner.grammar import build_trivial_grammar

    g = build_trivial_grammar()
    assert not (g.cmask.sum(axis=1) == 1).any()
    for text in ["", "anything at all", '{"not":"a plan"}', "\x00\xff"]:
        assert g.is_accept(g.walk(text)) or g.walk(text) == g.start_state
    assert g.is_accept(g.walk("free text"))
