"""Radix-tree prefix KV cache (ISSUE 8): tree semantics (insert / match /
split / evict over seeded streams), refcount pinning under eviction
pressure, EDF-safe locality ordering, warm-replan prompt byte-sharing, and
engine-level reuse (matched-token accounting, compile-count invariance,
the external pin API)."""

import asyncio
import random

import pytest

from tests.helpers import count_compiles, release_prefix_cache

from mcpx.core.config import MCPXConfig
from mcpx.engine.kv_cache import PageAllocator
from mcpx.engine.prefix_cache import RadixPrefixCache
from mcpx.scheduler.locality import locality_order

PAGE = 4


def make_cache(n_pages=64, max_nodes=64, max_tokens=0):
    alloc = PageAllocator(n_pages=n_pages, page_size=PAGE, max_pages_per_seq=32)
    return alloc, RadixPrefixCache(
        alloc, PAGE, max_nodes=max_nodes, max_tokens=max_tokens
    )


def blocks(*ids):
    """Token stream from 4-token blocks; block k starts with token k*100
    so divergence always lands on a page boundary (first tokens distinct)."""
    out = []
    for k in ids:
        out.extend([k * 100, k * 100 + 1, k * 100 + 2, k * 100 + 3])
    return out


def insert_all(cache, ids):
    """Match + insert the page-aligned remainder, like admission does."""
    n, _pages, node = cache.match(ids)
    want = ((len(ids)) // PAGE) * PAGE - n
    inode = None
    if want > 0:
        inode = cache.insert(ids, n, want)
        if inode is not None:
            inode.refs -= 1  # release the born-pin (the "row" retires)
    cache.seal()
    return n, node, inode


# ---------------------------------------------------------------- radix tree
def test_match_insert_split_basic():
    _alloc, cache = make_cache()
    a = blocks(1, 2, 3) + [7]  # 12 aligned tokens + 1 suffix token
    n, node, inode = insert_all(cache, a)
    assert n == 0 and inode is not None and len(inode.tokens) == 12
    # Full re-match caps at aligned(len-1): 12 of 13.
    n2, pages, _ = cache.match(a)
    assert n2 == 12 and len(pages) == 3
    # A prompt sharing one block splits the 3-block edge at the boundary.
    b = blocks(1, 9) + [7]
    n3, pages3, node3 = cache.match(b)
    assert n3 == 4 and len(pages3) == 1
    assert node3 is not None and len(node3.tokens) == 4
    cache.check_invariants()
    _alloc.check_invariants()
    # Insert b's remainder; both full paths now resident.
    insert_all(cache, b)
    assert cache.match(blocks(1, 9) + [7])[0] == 8
    assert cache.match(blocks(1, 2, 3) + [7])[0] == 12
    cache.check_invariants()


def test_evicted_and_split_runs_come_back_in_ascending_order():
    """The tree's own returns (an eviction, a split edge's two halves, a
    rollback) go through ``PageAllocator.free``: whatever order they come
    back in, the next allocation takes the lowest free ids, ascending."""
    alloc, cache = make_cache()
    insert_all(cache, blocks(1, 2, 3, 4) + [7])  # pages 1..4
    insert_all(cache, blocks(5, 6) + [7])  # 5, 6
    insert_all(cache, blocks(1, 2, 8) + [7])  # splits the first edge at 2 blocks; page 7
    held = alloc.allocate("row", 2 * PAGE)  # 8, 9
    assert held == [8, 9]
    assert cache.evict(need_tokens=(alloc.n_pages - 1) * PAGE) > 0  # everything unpinned goes
    assert len(cache) == 0
    assert alloc.allocate("next", 9 * PAGE) == [1, 2, 3, 4, 5, 6, 7, 10, 11]
    node = cache.insert(blocks(9, 9), 0, 2 * PAGE)
    assert node.pages == [12, 13]
    cache.rollback(node)
    alloc.free("row")
    assert alloc.allocate("last", 3 * PAGE) == [8, 9, 12]
    alloc.check_invariants()


def test_within_page_divergence_shares_nothing_but_both_cache():
    _alloc, cache = make_cache()
    a = [5, 6, 7, 8, 5, 5, 5, 5, 9]
    insert_all(cache, a)
    # Diverges at token 2 (inside the first page): no page to share, no
    # split — but children are keyed by first-PAGE content, so b still
    # caches as a sibling branch and its own repeats hit.
    b = [5, 6, 99, 8, 1, 2, 3, 4, 9]
    n, pages, node = cache.match(b)
    assert n == 0 and not pages and node is None
    assert cache.can_insert(b, 0) == 8
    insert_all(cache, b)
    assert cache.match(a, record=False)[0] == 8
    assert cache.match(b, record=False)[0] == 8
    cache.check_invariants()
    _alloc.check_invariants()


def test_property_seeded_streams_vs_reference():
    """Randomised block streams: tree matches equal the longest common
    page-aligned prefix against everything inserted, through arbitrary
    interleavings of insert/match/evict."""
    rng = random.Random(1234)
    _alloc, cache = make_cache(n_pages=256, max_nodes=256)
    inserted: list[list[int]] = []

    def expected(ids):
        cap = ((len(ids) - 1) // PAGE) * PAGE
        best = 0
        for s in inserted:
            cov = (len(s) // PAGE) * PAGE
            common = 0
            for x, y in zip(ids[:cov], s[:cov]):
                if x != y:
                    break
                common += 1
            best = max(best, (common // PAGE) * PAGE)
        return min(cap, best)

    for step in range(200):
        seq = blocks(*(rng.randrange(6) for _ in range(rng.randint(1, 5))))
        seq.append(7)  # a suffix token beyond the aligned coverage
        want = expected(seq)
        got, pages, _node = cache.match(seq)
        assert got == want, (step, got, want)
        assert len(pages) == got // PAGE
        if rng.random() < 0.7:
            n = got
            rem = (len(seq) // PAGE) * PAGE - n
            if rem > 0 and cache.can_insert(seq, n):
                node = cache.insert(seq, n, rem)
                if node is not None:
                    node.refs -= 1
                    inserted.append(seq)
            cache.seal()
        if rng.random() < 0.1:
            # Full-pressure eviction: everything is unpinned, so the tree
            # must empty completely and the reference resets with it.
            cache.max_nodes = 0
            cache.evict()
            cache.max_nodes = 256
            assert len(cache) == 0 and cache.resident_tokens == 0
            inserted = []
        cache.check_invariants()
    _alloc.check_invariants()


def test_pinned_run_survives_eviction_pressure():
    alloc, cache = make_cache()
    a = blocks(1, 2, 3) + [7]
    b = blocks(4, 5) + [7]
    insert_all(cache, a)
    insert_all(cache, b)
    held = alloc.stats().sequences
    assert held == 2
    # Pin a's run (like a resident row / plan_and_execute pin).
    _n, _pages, node_a = cache.match(a)
    node_a.refs += 1
    cache.max_nodes = 0
    cache.evict()
    cache.check_invariants()
    # Unpinned b reclaimed; pinned a survives with its pages.
    assert cache.match(b, record=False)[0] == 0
    assert cache.match(a, record=False)[0] == 12
    assert alloc.stats().sequences >= 1
    # Release the pin: pressure reclaims everything.
    node_a.refs -= 1
    cache.evict()
    assert len(cache) == 0
    assert alloc.stats().sequences == 0
    alloc.check_invariants()
    assert cache.evictions >= 2


def test_eviction_is_lru_and_cascades():
    _alloc, cache = make_cache()
    old = blocks(1, 2) + [7]
    new = blocks(3, 4) + [7]
    insert_all(cache, old)
    insert_all(cache, new)
    cache.match(new)  # refresh new's stamp; old becomes LRU
    cache.max_nodes = 1
    cache.evict()
    assert cache.match(new, record=False)[0] == 8
    assert cache.match(old, record=False)[0] == 0
    cache.check_invariants()


# ------------------------------------------------------------- locality sort
class _Req:
    def __init__(self, depth, enq, deadline=None):
        self.depth, self.enq, self.deadline = depth, enq, deadline


def _order(items, now=100.0, age_cap=0.5, slack=0.1):
    return locality_order(
        items,
        now=now,
        depth_of=lambda r: r.depth,
        enqueued_of=lambda r: r.enq,
        deadline_of=lambda r: r.deadline,
        age_cap_s=age_cap,
        deadline_slack_s=slack,
    )


def test_locality_sort_groups_by_depth_fifo_within():
    a, b, c, d = _Req(0, 99.7), _Req(8, 99.8), _Req(8, 99.9), _Req(4, 99.95)
    assert _order([a, b, c, d]) == [b, c, d, a]


def test_locality_sort_respects_edf():
    """The scheduler property (ISSUE 8 satellite): urgent requests — over
    the fairness age or with deadlines inside the slack — keep strict
    earliest-deadline-first order AHEAD of any deeper-prefix request."""
    now = 100.0
    urgent_late = _Req(0, 99.9, deadline=now + 0.05)   # deadline imminent
    urgent_old = _Req(0, 99.0)                          # over fairness age
    deep = _Req(64, 99.95, deadline=now + 10.0)         # deep but slack-rich
    deeper = _Req(128, 99.96)                           # no deadline at all
    out = _order([deep, urgent_late, deeper, urgent_old])
    # EDF head: the imminent deadline first, then the deadline-less
    # over-age request (FIFO among deadline-less), THEN locality order.
    assert out == [urgent_late, urgent_old, deeper, deep]
    # With everything slack-rich, pure locality order (stable FIFO ties).
    relaxed = _order([deep, deeper], now=now)
    assert relaxed == [deeper, deep]


def test_locality_sort_empty_tree_is_identity():
    reqs = [_Req(0, 99.9 + i * 0.001) for i in range(5)]
    assert _order(list(reqs)) == reqs


# ------------------------------------------------- warm-replan prompt bytes
def test_replan_prompt_extends_original_bytes():
    """The warm-replan splice: with the original service order re-rendered
    and exclusions as an Avoid suffix line, the replan prompt's ids are a
    byte-extension of the original through the whole services block."""
    from mcpx.models.tokenizer import ByteTokenizer
    from mcpx.planner.base import PlanContext
    from mcpx.planner.llm import build_prompt_ids
    from mcpx.registry.base import ServiceRecord

    tok = ByteTokenizer()
    services = [
        ServiceRecord(
            name=f"svc-{i}",
            endpoint=f"http://svc/{i}",
            input_schema={"a": "str"},
            output_schema={"b": "str"},
        )
        for i in range(4)
    ]
    ctx = PlanContext(registry=None)
    p1, s1, kept = build_prompt_ids(tok, "do the thing", services, ctx, 512)
    assert kept == [s.name for s in services]
    orig = p1 + s1
    p2, s2, _ = build_prompt_ids(
        tok, "do the thing", services, ctx, 512, avoid=["svc-1"]
    )
    replan = p2 + s2
    text1, text2 = tok.decode(orig), tok.decode(replan)
    assert "Avoid: svc-1\n" in text2 and "Avoid" not in text1
    # Token-level: identical through the end of the services block.
    block_end = text1.rindex("\nIntent:")
    shared = tok.encode(text1[:block_end])
    assert orig[: len(shared)] == shared == replan[: len(shared)]


# ------------------------------------------------------------ engine reuse
def make_engine(**overrides):
    from mcpx.engine.engine import InferenceEngine

    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 4,
                "max_decode_len": 48,
                "kv_page_size": 16,
                "max_pages_per_seq": 16,
                "temperature": 0.0,
                **overrides,
            },
        }
    )
    return InferenceEngine(cfg)


def test_engine_reuse_compile_invariance_and_pin_api():
    """One engine, three acceptance properties: (1) repeats are served
    from the tree (matched tokens grow, per-request prefill tokens
    collapse), (2) the compile count is independent of matched offsets —
    serving ragged offsets compiles NOTHING new (the suffix executable
    takes offsets as data), (3) the external pin API protects a run
    across eviction pressure and releases cleanly."""

    async def go():
        eng = make_engine()
        await eng.start()
        try:
            tok = eng.tokenizer
            header = "Compose a DAG.\nServices:\n"
            prompts = [
                tok.encode(
                    header + f"svc-{i} in:a out:b\nIntent: thing {i}\nJSON:"
                )
                for i in range(3)
            ]
            cold = []
            for p in prompts:  # sequential: deterministic A=1 cohorts
                cold.append(await eng.generate(p, max_new_tokens=16))
            pf_cold = eng.metrics.prefill_tokens._value.get()
            m0 = eng._prefix_cache.matched_tokens
            psz = eng.config.engine.kv_page_size
            with count_compiles("_impl") as compiles:
                warm = []
                for p in prompts:  # same prompts: deep match, tiny suffix
                    warm.append(await eng.generate(p, max_new_tokens=16))
                pf_repeats = (
                    eng.metrics.prefill_tokens._value.get() - pf_cold
                )
                # A novel tail at a DIFFERENT offset (shares the header):
                novel = tok.encode(
                    header + "svc-9 in:a out:b\nIntent: other\nJSON:"
                )
                await eng.generate(novel, max_new_tokens=16)
            # (2) no executable recompiled for any of the new offsets.
            assert compiles == [], compiles
            # Byte parity on the warm path.
            for c, w in zip(cold, warm):
                assert w.text == c.text
            # (1) reuse observable: matched tokens grew, and each repeat
            # prefilled at most its final partial page.
            assert eng._prefix_cache.matched_tokens > m0
            assert pf_repeats <= len(prompts) * psz, (pf_repeats, pf_cold)
            st = eng.prefix_cache_stats()
            assert st["enabled"] and st["hits"] >= len(prompts)
            assert eng.queue_stats()["prefix_token_hit_rate"] > 0.0

            # (3) the pin API: pinned runs survive eviction pressure.
            pin = await eng.pin_prefix(prompts[0])
            assert pin is not None and pin.refs >= 1
            eng.config.engine.prefix_cache_entries = 0
            eng._evict_prefixes()
            assert eng._prefix_cache.match(prompts[0], record=False)[0] > 0
            eng.unpin_prefix(pin)
            for _ in range(100):
                await asyncio.sleep(0.02)
                if pin.refs == 0:
                    break
            assert pin.refs == 0
            release_prefix_cache(eng)
            assert eng._allocator.stats().sequences == 0
            eng._allocator.check_invariants()

            # (4) prefix_cache=false is a true pass-through (live flip on
            # an idle slab): nothing matched, nothing inserted, nothing
            # resident — and the scoreboard stays flat.
            eng.config.engine.prefix_cache = False
            st0 = eng.prefix_cache_stats()
            off_p = tok.encode("off-mode prompt: compose the thing. JSON:")
            await eng.generate(off_p, max_new_tokens=12)
            await eng.generate(off_p, max_new_tokens=12)
            st1 = eng.prefix_cache_stats()
            assert not st1["enabled"]
            assert st1["nodes"] == 0
            assert st1["hits"] == st0["hits"]
            assert st1["misses"] == st0["misses"]
            assert eng._allocator.stats().sequences == 0
        finally:
            await eng.aclose()

    asyncio.run(go())


@pytest.mark.slow  # two LLM plan decodes + an engine boot: not tier-1 budget
def test_llm_planner_warm_replan_reuses_prefix():
    """Planner-level warm replan: the replan context carries the original
    render order + exclusions, the replan prompt byte-extends the original
    through the services block, and the engine serves that head from the
    radix tree (matched tokens grow by at least the shared block)."""

    async def go():
        from mcpx.planner.base import PlanContext
        from mcpx.planner.llm import LLMPlanner
        from mcpx.registry.base import ServiceRecord, stable_snapshot
        from mcpx.registry.memory import InMemoryRegistry

        eng = make_engine()
        await eng.start()
        try:
            reg = InMemoryRegistry()
            for i in range(4):
                await reg.put(
                    ServiceRecord(
                        name=f"svc-{i}",
                        endpoint=f"http://svc/{i}",
                        input_schema={"a": "str"},
                        output_schema={"b": "str"},
                    )
                )
            version, _ = await stable_snapshot(reg)
            planner = LLMPlanner(eng)
            ctx1 = PlanContext(registry=reg, registry_version=version)
            plan1 = await planner.plan("do the thing", ctx1)
            if plan1.origin != "llm":
                pytest.skip("random-weight decode fell back to heuristic")
            assert plan1.prompt_ids and plan1.prompt_services
            m0 = eng._prefix_cache.matched_tokens
            ctx2 = PlanContext(
                registry=reg,
                registry_version=version,
                exclude={plan1.nodes[0].service},
                replan_prior=tuple(plan1.prompt_services),
            )
            plan2 = await planner.plan("do the thing", ctx2)
            if plan2.origin != "llm":
                pytest.skip("replan decode fell back to heuristic")
            # Byte-sharing through the services block...
            tok = eng.tokenizer
            text1 = tok.decode(plan1.prompt_ids)
            block_end = text1.rindex("\nIntent:")
            shared = tok.encode(text1[:block_end])
            assert plan2.prompt_ids[: len(shared)] == shared
            assert "Avoid:" in tok.decode(plan2.prompt_ids)
            # ...and the engine served it from the tree.
            page = eng.config.engine.kv_page_size
            assert (
                eng._prefix_cache.matched_tokens - m0
                >= (len(shared) // page) * page - page
            )
        finally:
            await eng.aclose()

    asyncio.run(go())