"""InferenceEngine integration on CPU: batching, constrained decode,
allocator hygiene (SURVEY.md §4.5 model-in-the-loop)."""

import asyncio
import functools
import math

import pytest

from tests.helpers import release_prefix_cache

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import EngineError
from mcpx.engine.engine import InferenceEngine


def make_engine(**engine_overrides):
    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,  # jnp reference attention on CPU
                "max_batch_size": 4,
                "max_decode_len": 96,
                "kv_page_size": 16,
                "max_pages_per_seq": 16,
                "temperature": 0.0,
                **engine_overrides,
            },
        }
    )
    return InferenceEngine(cfg)


def test_generate_constrained_prefix_valid():
    async def go():
        eng = make_engine()
        await eng.start()
        assert eng.state == "ready"
        try:
            prompt = eng.tokenizer.encode("plan: compose the services. JSON:")
            res = await eng.generate(prompt, max_new_tokens=48)
            # Constrained decoding guarantees the output is a legal DFA
            # prefix even from a random-weight model.
            state = eng.grammar.walk(res.text)
            assert state != eng.grammar.dead_state, res.text
            assert res.text.startswith('{"steps":[{"s":"')
            assert res.generated_tokens > 0
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_concurrent_requests_batch_and_allocator_clean():
    async def go():
        eng = make_engine()
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("intent")
            results = await asyncio.gather(
                *(eng.generate(prompt, max_new_tokens=24) for _ in range(6))
            )
            assert len(results) == 6
            for r in results:
                assert eng.grammar.walk(r.text) != eng.grammar.dead_state
            # All pages returned after batches complete (the radix prefix
            # cache intentionally retains prompt-head KV; drop it so the
            # check sees only row leaks).
            release_prefix_cache(eng)
            stats = eng._allocator.stats()
            assert stats.sequences == 0
            assert stats.free_pages == stats.total_pages - 1
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_unconstrained_generation():
    async def go():
        eng = make_engine()
        await eng.start()
        try:
            res = await eng.generate(
                eng.tokenizer.encode("hello"), max_new_tokens=8, constrained=False
            )
            assert res.generated_tokens <= 8
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_generate_before_start_raises():
    eng = make_engine()

    async def go():
        with pytest.raises(EngineError, match="not ready"):
            await eng.generate([1, 2, 3])

    asyncio.run(go())


def test_pallas_interpret_path():
    """One batch through the actual Pallas kernel in interpret mode."""

    async def go():
        eng = make_engine(use_pallas=True, interpret=True, max_decode_len=16)
        await eng.start()
        try:
            res = await eng.generate(
                eng.tokenizer.encode("x"), max_new_tokens=8
            )
            assert eng.grammar.walk(res.text) != eng.grammar.dead_state
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_per_request_budget_and_mixed_sampling():
    """Review regressions: per-request max_new_tokens is honored inside a
    shared batch, and incompatible sampling configs never share a batch."""

    async def go():
        eng = make_engine()
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("q")
            small, large, unconstrained = await asyncio.gather(
                eng.generate(prompt, max_new_tokens=4),
                eng.generate(prompt, max_new_tokens=40),
                eng.generate(prompt, max_new_tokens=6, constrained=False),
            )
            assert small.generated_tokens <= 4
            assert unconstrained.generated_tokens <= 6
            # Constrained results are legal DFA prefixes regardless of what
            # was batched alongside.
            for r in (small, large):
                assert eng.grammar.walk(r.text) != eng.grammar.dead_state
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_prefill_bucket_clamped_to_page_capacity():
    # capacity = 6*16 = 96; a 70-token prompt must not round up to the
    # T=128 bucket (which would scatter 8 chunks into 6 page columns).
    async def go():
        eng = make_engine(max_pages_per_seq=6, max_decode_len=16)
        await eng.start()
        try:
            prompt = list(range(3, 73))  # 70 tokens
            res = await eng.generate(prompt, max_new_tokens=16)
            assert res.generated_tokens > 0
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_generate_after_close_and_shutdown_drain():
    async def go():
        eng = make_engine()
        await eng.start()
        await eng.aclose()
        assert eng.state == "closed"
        with pytest.raises(EngineError):
            await eng.generate([1, 2, 3], max_new_tokens=4)

    asyncio.run(go())


def test_warmup_compile_then_serve():
    """warmup_compile pre-executes every (B, T) bucket; the engine must come
    up ready and serve correctly afterward (null-page warmup traffic must
    not disturb real sequences)."""

    async def go():
        eng = make_engine(warmup_compile=True, warmup_max_len=64, max_decode_len=24)
        await eng.start()
        try:
            res = await eng.generate(
                eng.tokenizer.encode("plan:"), max_new_tokens=24
            )
            assert eng.grammar.walk(res.text) != eng.grammar.dead_state
            stats = eng._allocator.stats()
            assert stats.sequences == 0  # warmup holds no pages
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_speculative_matches_plain_greedy():
    """Grammar fast-forward speculation is exact: greedy constrained output
    must be byte-identical with speculation on vs off, across budgets
    (including the forced-completion edge at grammar.min_len), while doing
    strictly fewer model forwards than tokens emitted."""

    async def go():
        eng_plain = make_engine(speculate_k=0)
        eng_spec = make_engine(speculate_k=8)
        await eng_plain.start()
        await eng_spec.start()
        try:
            prompts = [
                eng_plain.tokenizer.encode("plan: compose the services. JSON:"),
                eng_plain.tokenizer.encode("q"),
            ]
            budgets = [eng_plain.grammar.min_len, 24, 96]
            for prompt in prompts:
                for budget in budgets:
                    plain = await eng_plain.generate(prompt, max_new_tokens=budget)
                    spec = await eng_spec.generate(prompt, max_new_tokens=budget)
                    assert spec.text == plain.text, (budget, spec.text, plain.text)
            fwd = eng_spec.metrics.decode_forwards._value.get()
            toks = eng_spec.metrics.decode_tokens._value.get()
            assert fwd < toks, f"speculation did not amortise: {fwd} forwards / {toks} tokens"
        finally:
            await eng_plain.aclose()
            await eng_spec.aclose()

    asyncio.run(go())


def test_budget_forced_completion():
    """With budget >= grammar.min_len, constrained decode must emit a
    COMPLETE grammar-accepted plan (budget-aware masking forces the JSON
    closed) — even from random weights, at several budgets, with sampling."""

    async def go():
        eng = make_engine(temperature=0.8)
        await eng.start()
        try:
            import json

            prompt = eng.tokenizer.encode("plan: compose. JSON:")
            for budget in [eng.grammar.min_len, eng.grammar.min_len + 5, 96]:
                res = await eng.generate(prompt, max_new_tokens=budget)
                # The forced EOS consumes one budget sample and is never
                # emitted, so output bytes are strictly below the budget.
                assert res.generated_tokens < budget
                state = eng.grammar.walk(res.text)
                assert eng.grammar.is_accept(state), (budget, res.text)
                obj = json.loads(res.text)
                assert obj["steps"], res.text
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_continuous_admission_mid_stream():
    """Continuous batching: a request that arrives while another is mid-
    decode is admitted into a free slab row at the next segment boundary —
    and both produce exactly the same greedy output they'd produce alone
    (emission-indexed buffers keep staggered rows independent)."""

    async def go():
        eng = make_engine(decode_steps_per_tick=1, speculate_k=0)
        await eng.start()
        try:
            p1 = eng.tokenizer.encode("first intent: compose. JSON:")
            p2 = eng.tokenizer.encode("second, different prompt! JSON:")
            solo1 = await eng.generate(p1, max_new_tokens=48)
            solo2 = await eng.generate(p2, max_new_tokens=32)

            # Stagger: launch p1, wait until it is mid-decode, launch p2.
            t1 = asyncio.create_task(eng.generate(p1, max_new_tokens=48))
            for _ in range(200):
                await asyncio.sleep(0.01)
                if eng._slab.n_active >= 1:
                    break
            assert eng._slab.n_active >= 1, "first request never entered the slab"
            t2 = asyncio.create_task(eng.generate(p2, max_new_tokens=32))
            r1, r2 = await asyncio.gather(t1, t2)
            assert r1.text == solo1.text
            assert r2.text == solo2.text
            release_prefix_cache(eng)
            stats = eng._allocator.stats()
            assert stats.sequences == 0
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_pipeline_depths_agree():
    """The pipelined worker (lagged flag fetch + on-device merge) is exact:
    staggered greedy generations produce byte-identical output at pipeline
    depth 1 (fetch-what-you-dispatched) and depth 3 (flags read three
    segments late, retirement via generation-guarded lagged out_buf), and
    no pages or prefixes leak at either depth."""

    async def run(depth: int):
        eng = make_engine(pipeline_depth=depth, decode_steps_per_tick=1)
        await eng.start()
        try:
            tok = eng.tokenizer
            prompts = [
                tok.encode(f"intent number {i}: compose services. JSON:")
                for i in range(5)
            ]
            # Staggered arrivals: re-admission into freed rows happens while
            # older segments are still in flight (the gen-guard path).
            tasks = []
            for i, p in enumerate(prompts):
                tasks.append(
                    asyncio.create_task(eng.generate(p, max_new_tokens=24 + 8 * (i % 3)))
                )
                await asyncio.sleep(0.03 * (i % 2))
            results = await asyncio.gather(*tasks)
            release_prefix_cache(eng)
            stats = eng._allocator.stats()
            assert stats.sequences == 0
            eng._allocator.check_invariants()
            return [r.text for r in results]
        finally:
            await eng.aclose()

    async def go():
        t1 = await run(1)
        t3 = await run(3)
        assert t1 == t3, (t1, t3)
        for t in t1:
            assert t  # every staggered request produced output

    asyncio.run(go())


def test_engine_multichip_matches_single_chip():
    """The engine's own serving path on an 8-device 2x4 mesh (GQA K=4 so the
    KV pools genuinely shard over `model`) produces the same greedy output
    as a 1-device engine with identical weights — the north star's KV-cache
    sharding as a property of InferenceEngine, not just the dryrun."""
    import jax

    from mcpx.core.config import MCPXConfig
    from mcpx.models.gemma.config import GemmaConfig
    from mcpx.parallel.mesh import make_mesh

    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 4,
                "max_decode_len": 48,
                "kv_page_size": 16,
                "max_pages_per_seq": 8,
                "temperature": 0.0,
            },
        }
    )
    # GQA with K=4: KV heads shard 4-way over `model`; float32 so TP psum
    # reassociation cannot wobble the greedy argmax.
    model_cfg = GemmaConfig(
        vocab_size=384,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        head_dim=32,
        d_ff=256,
        dtype="float32",
        max_seq_len=256,
    )

    async def run_one(mesh):
        eng = InferenceEngine(cfg, model_cfg=model_cfg, mesh=mesh)
        await eng.start()
        try:
            prompts = [
                eng.tokenizer.encode("alpha plan request. JSON:"),
                eng.tokenizer.encode("beta"),
            ]
            outs = []
            for p in prompts:
                r = await eng.generate(p, max_new_tokens=40)
                outs.append(r.token_ids)
            # KV pools actually sharded over `model` on the multi-dev mesh.
            kspec = eng._paged_kv["k"].sharding.spec
            return outs, kspec
        finally:
            await eng.aclose()

    async def go():
        outs1, _ = await run_one(make_mesh(data=1, model=1, devices=jax.devices()[:1]))
        outs8, kspec8 = await run_one(make_mesh(data=2, model=4))
        assert outs8 == outs1, (outs8, outs1)
        assert kspec8[0] == "model", f"KV pools not sharded over model: {kspec8}"

    asyncio.run(go())


def test_shared_prefix_matches_full_prefill():
    """Radix prefix serving is exact: with the declared prompt head (and
    every admitted prompt's page-aligned remainder) cached in read-only
    tree pages and only unmatched suffixes prefilled, greedy outputs are
    byte-identical to full per-request prefill — and the tree is
    refcounted/evictable, never leaked."""

    async def go():
        eng_full = make_engine(prefix_cache=False)
        eng_pfx = make_engine(prefix_cache=True)
        await eng_full.start()
        await eng_pfx.start()
        try:
            tok = eng_full.tokenizer
            header = "Compose a service DAG. JSON schema blah\nServices:\n"
            prefix_ids = tok.encode(header)
            prompts = [
                prefix_ids + tok.encode(f"svc-{i} in:a out:b\nIntent: do thing {i}\nJSON:", bos=False)
                for i in range(5)
            ]
            full = [
                await eng_full.generate(p, max_new_tokens=32) for p in prompts
            ]
            shared = await asyncio.gather(
                *(
                    eng_pfx.generate(
                        p, max_new_tokens=32, shared_prefix_len=len(prefix_ids)
                    )
                    for p in prompts
                )
            )
            for f, s in zip(full, shared):
                assert s.text == f.text, (s.text, f.text)
            # REPEATS now match their whole page-aligned prompt (not just
            # the declared header) and still decode identically.
            again = await asyncio.gather(
                *(
                    eng_pfx.generate(
                        p, max_new_tokens=32, shared_prefix_len=len(prefix_ids)
                    )
                    for p in prompts[:2]
                )
            )
            for f, s in zip(full[:2], again):
                assert s.text == f.text, (s.text, f.text)
            cache = eng_pfx._prefix_cache
            cache.check_invariants()
            st = cache.stats()
            # The shared header is one resident path plus a branch per
            # distinct prompt tail; everything unreferenced after retire.
            assert st["nodes"] >= 2
            assert st["resident_tokens"] % eng_pfx.config.engine.kv_page_size == 0
            assert cache.pinned_nodes() == 0
            # The repeat round hit the tree (token-level reuse observable).
            assert st["matched_tokens"] > 0 and st["hits"] >= 2
            assert eng_pfx.metrics.prefix_hits._value.get() >= 2
            # Allocator holds exactly the tree's pages beyond the rows.
            assert eng_pfx._allocator.stats().sequences == st["nodes"]
            eng_pfx._allocator.check_invariants()
            # Eviction drops everything once unreferenced and over budget.
            release_prefix_cache(eng_pfx)
            assert len(cache) == 0
            assert eng_pfx._allocator.stats().sequences == 0
        finally:
            await eng_full.aclose()
            await eng_pfx.aclose()

    asyncio.run(go())


def test_cancelled_request_reaps_row_and_pages():
    """A cancelled request (client disconnect / server timeout) frees its
    slab row and pages at the next tick instead of decoding the abandoned
    plan to budget exhaustion — and the engine keeps serving afterwards."""

    async def go():
        eng = make_engine(decode_steps_per_tick=1, speculate_k=0)
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("cancel me: compose. JSON:")
            t = asyncio.create_task(eng.generate(prompt, max_new_tokens=96))
            # Admission too can sit behind multi-second on-demand XLA CPU
            # compiles (prefill/admit/admit-merge executables).
            for _ in range(1200):
                await asyncio.sleep(0.05)
                if eng._slab.n_active >= 1:
                    break
            assert eng._slab.n_active >= 1
            t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass
            # The worker reaps the row at a tick boundary — but a tick can
            # be stretched by a multi-second on-demand XLA CPU compile
            # (warmup_compile is off in tests), so the window must outlast
            # a compile, not just a decode step.
            for _ in range(1200):
                await asyncio.sleep(0.05)
                # Cached prompt-head KV legitimately stays resident; only
                # the reaped ROW's pages must return.
                if eng._allocator.stats().sequences == len(eng._prefix_cache):
                    break
            release_prefix_cache(eng)
            assert eng._allocator.stats().sequences == 0
            assert eng.metrics.reaped_rows._value.get() == 1
            eng._allocator.check_invariants()
            # Service continues: a fresh request still completes.
            res = await eng.generate(prompt, max_new_tokens=24)
            assert res.generated_tokens > 0
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_mid_serving_failure_fails_rows_and_recovers():
    """A device/runtime failure inside a decode segment fails the in-flight
    requests with the ORIGINAL exception (callers can match the concrete
    type), resets the KV pools, clears the pipeline (in-flight handles,
    dirty rows, pending admissions) — and the very next request serves
    normally (SURVEY.md §5 failure detection: degrade loudly, recover
    without restart)."""

    async def go():
        eng = make_engine()
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("will fail mid-decode. JSON:")
            real_segment = eng._jit_segment
            calls = {"n": 0}

            def boom(*a, **kw):
                calls["n"] += 1
                raise RuntimeError("injected device failure")

            eng._jit_segment = boom
            resets0 = eng.metrics.engine_resets._value.get()
            # The caller sees the ORIGINAL device error, not a wrapper.
            with pytest.raises(RuntimeError, match="injected device failure"):
                await eng.generate(prompt, max_new_tokens=24)
            assert calls["n"] >= 1
            assert not eng._inflight and not eng._pending_admissions
            # The recovery is observable: mcpx_engine_resets_total counts
            # every _reset_pools a failed dispatch forced. Polled: the
            # request future resolves inside _fail_rows, BEFORE the worker
            # thread reaches _reset_pools.
            for _ in range(200):
                if eng.metrics.engine_resets._value.get() > resets0:
                    break
                await asyncio.sleep(0.01)
            assert eng.metrics.engine_resets._value.get() > resets0
            # Allocator state is checkable only AFTER the observed reset:
            # the radix tree's cached prompt head holds a sequence until
            # _reset_pools drops the tree, which the worker reaches after
            # resolving the failed futures (asserting earlier raced it).
            assert eng._allocator.stats().sequences == 0
            eng._allocator.check_invariants()

            # Restore the device path: service resumes with fresh pools.
            eng._jit_segment = real_segment
            res = await eng.generate(prompt, max_new_tokens=24)
            assert res.generated_tokens > 0
            assert eng.grammar.walk(res.text) != eng.grammar.dead_state
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_cancelled_queued_request_never_admitted():
    """A request cancelled while still QUEUED behind a full slab is skipped
    at admission (no prefill, no pages) instead of being admitted and then
    reaped; live requests around it complete normally."""

    async def go():
        eng = make_engine(max_batch_size=2, decode_steps_per_tick=1, speculate_k=0)
        await eng.start()
        try:
            tok = eng.tokenizer
            long_ = [
                asyncio.create_task(
                    eng.generate(tok.encode(f"occupy row {i}. JSON:"), max_new_tokens=96)
                )
                for i in range(2)
            ]
            for _ in range(1200):
                await asyncio.sleep(0.05)
                if eng._slab.n_active == 2:
                    break
            assert eng._slab.n_active == 2  # slab full; next request queues
            queued = asyncio.create_task(
                eng.generate(tok.encode("queued then abandoned"), max_new_tokens=96)
            )
            await asyncio.sleep(0.05)
            queued.cancel()
            try:
                await queued
            except asyncio.CancelledError:
                pass
            results = await asyncio.gather(*long_)
            for r in results:
                assert r.generated_tokens > 0
            # The abandoned request was never admitted: only the two
            # occupants were ever given rows, and nothing leaked.
            assert eng.metrics.admitted_rows._value.get() == 2
            release_prefix_cache(eng)
            assert eng._allocator.stats().sequences == 0
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_draft_speculation_matches_ff_only():
    """Prompt-lookup draft speculation (EngineConfig.draft_mode) is exact
    under greedy decode: with a registry-trie grammar whose names appear
    VERBATIM in the prompt, output must be byte-identical with drafts on vs
    off, and drafts can never cost extra forwards (a rejected draft chain
    truncates exactly where fast-forward would have stopped)."""
    from mcpx.planner.grammar import build_plan_grammar

    names = [f"svc-alpha-{i:02d}" for i in range(6)] + ["metric-rank-00"]

    async def go():
        eng_ff = make_engine(speculate_k=8, draft_mode="off")
        eng_dr = make_engine(speculate_k=8, draft_mode="prompt")
        await eng_ff.start()
        await eng_dr.start()
        try:
            g_ff = build_plan_grammar(eng_ff.tokenizer, names)
            g_dr = build_plan_grammar(eng_dr.tokenizer, names)
            # Prompt echoes the service names (as planner prompts do).
            prompt_text = (
                "services: " + " ".join(names) + "\nIntent: rank alpha\nJSON:"
            )
            for budget in (24, 64, 96):
                p_ff = eng_ff.tokenizer.encode(prompt_text)
                p_dr = eng_dr.tokenizer.encode(prompt_text)
                r_ff = await eng_ff.generate(
                    p_ff, max_new_tokens=budget, grammar=g_ff
                )
                r_dr = await eng_dr.generate(
                    p_dr, max_new_tokens=budget, grammar=g_dr
                )
                assert r_dr.text == r_ff.text, (budget, r_dr.text, r_ff.text)
            f_ff = eng_ff.metrics.decode_forwards._value.get()
            f_dr = eng_dr.metrics.decode_forwards._value.get()
            t_ff = eng_ff.metrics.decode_tokens._value.get()
            t_dr = eng_dr.metrics.decode_tokens._value.get()
            assert t_dr == t_ff
            assert f_dr <= f_ff, (
                f"drafts cost extra forwards: {f_dr} vs {f_ff} for {t_dr} tokens"
            )
        finally:
            await eng_ff.aclose()
            await eng_dr.aclose()

    asyncio.run(go())


def test_draft_speculation_accepts_through_branch_points():
    """Deterministic amortisation proof: a two-name trie branches where only
    the SHORT name can still finish within budget, so the budget-masked
    greedy argmax at the branch is forced — independent of (random) weights.
    Fast-forward cannot force that position (two grammar-legal columns);
    draft verification accepts it when the prompt's example fragment
    proposes it. Output stays identical; the draft engine must do strictly
    fewer forwards."""
    from mcpx.planner.grammar import build_plan_grammar

    names = ["aa", "a" + "b" * 40]

    async def go():
        eng_ff = make_engine(speculate_k=8, draft_mode="off")
        eng_dr = make_engine(speculate_k=8, draft_mode="prompt")
        await eng_ff.start()
        await eng_dr.start()
        try:
            g_ff = build_plan_grammar(eng_ff.tokenizer, names)
            g_dr = build_plan_grammar(eng_dr.tokenizer, names)
            # The example fragment after ':' is the draft source: the first
            # generated token is the forced '{' whose (prev=':', cur='{')
            # bigram matches 'Example:{', so the continuation walks the
            # fragment in lockstep with the forced JSON scaffolding and
            # proposes 'a' at the name branch.
            prompt_text = (
                'Example:{"steps":[{"s":"aa","in":["k"],"next":[]}]} JSON:'
            )
            # Budget fits a short-name plan but not the 41-char name, so the
            # branch's budget mask has exactly one feasible column.
            budget = g_ff.min_len + 6
            for _ in range(2):
                p_ff = eng_ff.tokenizer.encode(prompt_text)
                p_dr = eng_dr.tokenizer.encode(prompt_text)
                r_ff = await eng_ff.generate(
                    p_ff, max_new_tokens=budget, grammar=g_ff
                )
                r_dr = await eng_dr.generate(
                    p_dr, max_new_tokens=budget, grammar=g_dr
                )
                assert r_dr.text == r_ff.text, (r_dr.text, r_ff.text)
                assert '"s":"aa"' in r_dr.text
            f_ff = eng_ff.metrics.decode_forwards._value.get()
            f_dr = eng_dr.metrics.decode_forwards._value.get()
            t = eng_dr.metrics.decode_tokens._value.get()
            assert f_dr < f_ff, (
                f"drafts did not amortise: {f_dr} vs {f_ff} forwards "
                f"for {t} tokens"
            )
        finally:
            await eng_ff.aclose()
            await eng_dr.aclose()

    asyncio.run(go())


def test_draft_speculation_concurrent_rows_allocator_clean():
    """Drafted decode with several concurrent rows (staggered admissions →
    different emitted offsets, per-row prompt buffers) must stay exact and
    leak no pages."""

    async def go():
        eng = make_engine(speculate_k=8, draft_mode="prompt")
        await eng.start()
        try:
            prompts = [
                eng.tokenizer.encode(f"intent {i}: compose services. JSON:")
                for i in range(6)
            ]
            results = await asyncio.gather(
                *(eng.generate(p, max_new_tokens=32) for p in prompts)
            )
            for r in results:
                assert eng.grammar.walk(r.text) != eng.grammar.dead_state
            release_prefix_cache(eng)
            stats = eng._allocator.stats()
            assert stats.sequences == 0
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_hetero_mixed_slab_matches_homogeneous():
    """Heterogeneous batching tentpole: constrained greedy, a second
    grammar, free-form and temperature>0 requests share ONE slab
    (hetero_batch=on), and every deterministic row's output is
    byte-identical to its solo run — on the hetero engine AND on a
    hetero_batch=off engine (greedy parity across both modes). Stochastic
    rows stay legal DFA prefixes. Nothing leaks."""
    from mcpx.planner.grammar import build_plan_grammar

    async def go():
        eng = make_engine(hetero_batch=True, max_batch_size=6)
        eng_off = make_engine(max_batch_size=6)
        await eng.start()
        await eng_off.start()
        try:
            tok = eng.tokenizer
            p_plan = tok.encode("plan: compose the services. JSON:")
            p_free = tok.encode("free-form hello there")
            g2 = build_plan_grammar(tok, ["svc-a", "svc-b", "rank-c"])
            g2_off = build_plan_grammar(eng_off.tokenizer, ["svc-a", "svc-b", "rank-c"])

            solo_plan = await eng.generate(p_plan, max_new_tokens=48)
            solo_free = await eng.generate(p_free, max_new_tokens=12, constrained=False)
            solo_g2 = await eng.generate(p_plan, max_new_tokens=48, grammar=g2)
            # Greedy parity with the homogeneous engine (same deterministic
            # weights): per-row tables/sampling change nothing token-wise.
            off_plan = await eng_off.generate(p_plan, max_new_tokens=48)
            off_free = await eng_off.generate(p_free, max_new_tokens=12, constrained=False)
            off_g2 = await eng_off.generate(p_plan, max_new_tokens=48, grammar=g2_off)
            assert solo_plan.text == off_plan.text
            assert solo_free.token_ids == off_free.token_ids
            assert solo_g2.text == off_g2.text

            # The mixed slab: all five classes at once, strict queue order.
            mixed = await asyncio.gather(
                eng.generate(p_plan, max_new_tokens=48),
                eng.generate(p_free, max_new_tokens=12, constrained=False),
                eng.generate(p_plan, max_new_tokens=48, grammar=g2),
                eng.generate(p_plan, max_new_tokens=48, temperature=0.9),
                eng.generate(p_free, max_new_tokens=12, constrained=False, temperature=0.9),
            )
            assert mixed[0].text == solo_plan.text
            assert mixed[1].token_ids == solo_free.token_ids
            assert mixed[2].text == solo_g2.text
            assert '"s":"svc-' in mixed[2].text or '"s":"rank-' in mixed[2].text
            # Stochastic constrained row: still a legal plan prefix.
            assert eng.grammar.walk(mixed[3].text) != eng.grammar.dead_state
            assert mixed[4].generated_tokens <= 12
            release_prefix_cache(eng)
            stats = eng._allocator.stats()
            assert stats.sequences == 0
            eng._allocator.check_invariants()
            qs = eng.queue_stats()
            assert {"depth_constrained", "depth_free", "hol_wait_ms", "resident_grammars"} <= set(qs)
        finally:
            await eng.aclose()
            await eng_off.aclose()

    asyncio.run(go())


def test_hetero_segment_compiles_once_across_grammar_mix():
    """Executable-count acceptance: after the first heterogeneous segment
    compiles, introducing NEW grammars, an unconstrained row and a second
    temperature triggers ZERO further XLA compiles of the hetero segment —
    temperature/constrained are device values and grammars are stacked
    table DATA, not static args."""
    from mcpx.planner.grammar import build_plan_grammar
    from tests.helpers import count_compiles

    async def go(compiles):
        eng = make_engine(hetero_batch=True)
        await eng.start()
        try:
            p = eng.tokenizer.encode("plan: compose. JSON:")
            await eng.generate(p, max_new_tokens=24)
            n0 = len(compiles)
            assert n0 >= 1, "first hetero segment never compiled?"
            g1 = build_plan_grammar(eng.tokenizer, ["svc-a", "svc-b"])
            g2 = build_plan_grammar(eng.tokenizer, ["other-x", "other-y"])
            await asyncio.gather(
                eng.generate(p, max_new_tokens=24, grammar=g1),
                eng.generate(p, max_new_tokens=24, grammar=g2, temperature=0.7),
                eng.generate(eng.tokenizer.encode("free"), max_new_tokens=8, constrained=False),
            )
            assert len(compiles) == n0, (
                f"hetero segment recompiled for new grammars/configs: "
                f"{len(compiles) - n0} extra compiles"
            )
        finally:
            await eng.aclose()

    with count_compiles("_hetero_segment_impl") as compiles:
        asyncio.run(go(compiles))


def test_hetero_grammar_slots_recycle_and_defer():
    """More distinct grammars than stacked slots: the overflow grammar's
    request defers until a resident grammar drains, then admits and
    completes — strict queue order otherwise, and slot refcounts return to
    zero at the end."""
    from mcpx.planner.grammar import build_plan_grammar

    async def go():
        # 2 slots = trivial + ONE constrained grammar resident at a time.
        eng = make_engine(hetero_batch=True, hetero_grammar_slots=2)
        await eng.start()
        try:
            tok = eng.tokenizer
            p = tok.encode("plan: q. JSON:")
            g1 = build_plan_grammar(tok, ["aaa-svc"])
            g2 = build_plan_grammar(tok, ["bbb-svc"])
            r1, r2 = await asyncio.gather(
                eng.generate(p, max_new_tokens=32, grammar=g1),
                eng.generate(p, max_new_tokens=32, grammar=g2),
            )
            assert '"s":"aaa-svc"' in r1.text
            assert '"s":"bbb-svc"' in r2.text
            assert eng.queue_stats()["resident_grammars"] == 0
            release_prefix_cache(eng)
            assert eng._allocator.stats().sequences == 0
            eng._allocator.check_invariants()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_warm_grammar_compiles_every_cohort_bucket_before_traffic():
    """``warm_grammar`` is the engine's own walk over its cohort buckets: a
    grammar whose tables land in another pad bucket than the generic one
    gets one admit per bucket and one segment compiled up front, by direct
    dispatch, and then no cohort size — whatever way a burst is gathered —
    compiles anything while serving. A resident row decoding under another
    grammar while the warm runs is left alone."""
    from mcpx.planner.grammar import build_plan_grammar

    def compiles(eng):
        snap = eng.costs.snapshot(materialize=False)["executables"]
        return {n: e["compiles"] for n, e in snap.items()}

    async def go():
        eng = make_engine(
            max_decode_len=32, warmup_compile=True, warmup_max_len=64,
            grammar_state_budget=64,
        )
        await eng.start()
        try:
            tok = eng.tokenizer
            g = build_plan_grammar(tok, [f"service-number-{i:03d}" for i in range(40)])
            p = tok.encode("plan: JSON:")
            c0 = compiles(eng)
            solo = await eng.generate(p, max_new_tokens=24)
            resident = asyncio.ensure_future(eng.generate(p, max_new_tokens=24))
            await asyncio.sleep(0.02)  # admitted (or about to be) when the warm lands
            await eng.warm_grammar(g)
            assert (await resident).text == solo.text
            c1 = compiles(eng)
            grew = {n: c1[n] - c0[n] for n in c1 if c1[n] != c0[n]}
            n_buckets = len(eng._cohort_table(eng._prefill_buckets))
            assert grew == {"admit": n_buckets, "segment": 1}, grew
            for n in (1, 2, 4, 3):
                outs = await asyncio.gather(
                    *(
                        eng.generate(p + [65 + i], max_new_tokens=8, grammar=g)
                        for i in range(n)
                    )
                )
                assert all(o.text.startswith('{"steps"') for o in outs)
            assert compiles(eng) == c1
            assert eng.metrics.engine_resets._value.get() == 0
        finally:
            await eng.aclose()

    asyncio.run(go())


def _gathers(jaxpr):
    """Every ``gather`` equation of a jaxpr, those of its sub-jaxprs (while,
    scan, cond, pjit bodies) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _gathers(inner)


def _scalar_gathers_from_state_vector(jaxpr, n_states: int, at_least: int):
    """Gathers whose operand is a 1-D table over the grammar's states and
    whose result has ``at_least`` elements or more: the chained
    ``dist[trans[...]]`` shape, one scalar fetched per (row, column)."""
    return [
        e
        for e in _gathers(jaxpr)
        if e.invars[0].aval.shape == (n_states,)
        and math.prod(e.outvars[0].aval.shape) >= at_least
    ]


def test_segment_program_has_no_scalar_gather_over_states():
    """The served decode segment and the first-sample admit, traced as the
    engine dispatched them, hold no gather from a 1-D table over the DFA's
    states with B x C (a budget mask) or B x J x C (the draft verify)
    results: the successor's distance is read by row from ``dist_succ
    [S, C]``. The chain ``dist[trans[s]]`` it replaced was B x J x C =
    114,688 scalar gathers and 0.8 ms of every forward on a v5e (PERF.md,
    PR 34); the detector is shown to see that chain first."""
    import jax
    import jax.numpy as jnp

    S, C, B, J = 1024, 512, 4, 7
    chain = jax.make_jaxpr(lambda dist, trans, s: dist[trans[s]])(
        jax.ShapeDtypeStruct((S,), jnp.int32),
        jax.ShapeDtypeStruct((S, C), jnp.int32),
        jax.ShapeDtypeStruct((B, J), jnp.int32),
    )
    assert len(_scalar_gathers_from_state_vector(chain.jaxpr, S, B * C)) == 1

    async def go():
        eng = make_engine()
        await eng.start()
        try:
            p = eng.tokenizer.encode("plan: compose the services. JSON:")
            await eng.generate(p, max_new_tokens=24)
            tracked = {t.name: t for t in eng.costs._tracked}
            dfa = eng._dfa_for(eng.grammar)
            S, C = dfa[0].shape
            assert dfa[2].shape == (S, C)
            seen = {}
            for name, impl in (("segment", eng._segment_impl), ("admit", eng._admit_impl)):
                static = tracked[name]._static
                for entry in tracked[name]._entries.values():
                    args, kwargs = entry.lower_spec
                    statics = {k: v for k, v in kwargs.items() if k in static}
                    operands = {k: v for k, v in kwargs.items() if k not in static}
                    jaxpr = jax.make_jaxpr(functools.partial(impl, **statics))(
                        *args, **operands
                    ).jaxpr
                    rows = args[7].shape[0]  # cur [B] / budgets [A]
                    assert not _scalar_gathers_from_state_vector(jaxpr, S, rows * C)
                    seen[name] = statics
            # the path the cells run: the prompt draft's verify, chunk 8
            assert seen["segment"]["draft"] and seen["segment"]["chunk"] == 8
            assert "admit" in seen
        finally:
            await eng.aclose()

    asyncio.run(go())
