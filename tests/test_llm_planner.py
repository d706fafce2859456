"""LLMPlanner: prompt construction, endpoint resolution, retry/fallback
(SURVEY.md §7 step 6; fixes reference bugs B6/B7/B9)."""

import asyncio

import pytest

from mcpx.core.config import MCPXConfig, PlannerConfig
from mcpx.models.tokenizer import ByteTokenizer
from mcpx.planner.base import PlanContext
from mcpx.planner.llm import LLMPlanner
from mcpx.registry.base import ServiceRecord
from mcpx.registry.memory import InMemoryRegistry
from mcpx.telemetry.stats import ServiceStats


class FakeEngine:
    """Duck-typed engine returning scripted completions."""

    def __init__(self, outputs):
        self.outputs = list(outputs)
        self.tokenizer = ByteTokenizer()
        self.state = "ready"
        self.prompts = []
        self.warmed = []

    async def start(self):
        self.state = "ready"

    async def warm_grammar(self, grammar):
        self.warmed.append(grammar)

    async def generate(self, prompt_ids, **kw):
        import dataclasses

        self.prompts.append(self.tokenizer.decode(prompt_ids))

        @dataclasses.dataclass
        class R:
            text: str

        return R(text=self.outputs.pop(0) if self.outputs else "")


async def _registry():
    reg = InMemoryRegistry()
    await reg.put(
        ServiceRecord(
            name="fetch",
            endpoint="http://svc/fetch",
            description="fetch data",
            output_schema={"data": "str"},
            fallbacks=["http://backup/fetch"],
        )
    )
    await reg.put(
        ServiceRecord(
            name="summarize",
            endpoint="http://svc/sum",
            description="summarize text",
            input_schema={"data": "str"},
            cost_profile={"cost": 2.0},
        )
    )
    return reg


GOOD = '{"steps":[{"s":"fetch","in":[],"next":["summarize"]},{"s":"summarize","in":["data"],"next":[]}]}'


def test_valid_completion_resolves_endpoints_from_registry():
    async def go():
        reg = await _registry()
        eng = FakeEngine([GOOD])
        p = LLMPlanner(eng, PlannerConfig(kind="llm"))
        plan = await p.plan("fetch and summarize", PlanContext(registry=reg))
        assert [n.name for n in plan.nodes] == ["fetch", "summarize"]
        # Endpoints come from the registry, never from model output.
        assert plan.node("fetch").endpoint == "http://svc/fetch"
        assert plan.node("fetch").fallbacks == ["http://backup/fetch"]
        assert plan.node("summarize").endpoint == "http://svc/sum"
        assert len(plan.edges) == 1 and plan.edges[0].src == "fetch"
        assert "LLM-planned" in plan.explanation

    asyncio.run(go())


def test_unknown_service_retries_then_falls_back_to_heuristic():
    async def go():
        reg = await _registry()
        bad = '{"steps":[{"s":"nonexistent","in":[],"next":[]}]}'
        eng = FakeEngine([bad, bad, bad])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_plan_retries=2))
        plan = await p.plan("summarize the data", PlanContext(registry=reg))
        assert len(eng.prompts) == 3  # exhausted retry budget
        assert plan.nodes  # heuristic fallback produced something real
        assert all(n.service in ("fetch", "summarize") for n in plan.nodes)
        assert "heuristic fallback" in plan.explanation

    asyncio.run(go())


def test_second_attempt_can_succeed():
    async def go():
        reg = await _registry()
        eng = FakeEngine(['{"steps":[{"s":"ghost","in":[],"next":[]}]}', GOOD])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_plan_retries=2))
        plan = await p.plan("x", PlanContext(registry=reg))
        assert [n.name for n in plan.nodes] == ["fetch", "summarize"]
        assert "attempt 2" in plan.explanation

    asyncio.run(go())


def test_prompt_contains_telemetry_and_respects_shortlist_and_budget():
    async def go():
        reg = await _registry()
        for i in range(40):
            await reg.put(
                ServiceRecord(name=f"f{i}", endpoint=f"http://x/{i}", description="y" * 40)
            )
        eng = FakeEngine([GOOD])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_prompt_tokens=600))
        ctx = PlanContext(
            registry=reg,
            telemetry={"fetch": ServiceStats("fetch", ewma_latency_ms=12.5, ewma_error_rate=0.25)},
            shortlist=["summarize", "fetch"],
        )
        await p.plan("fetch and summarize", ctx)
        prompt = eng.prompts[0]
        assert len(prompt) <= 600
        assert "err=0.25" in prompt
        assert "p50=12" in prompt or "p50=13" in prompt
        assert "c=2" in prompt
        # Shortlisted services only, in retrieval order.
        assert "\nsummarize in:" in prompt and "\nf3 in:" not in prompt
        assert prompt.index("\nsummarize in:") < prompt.index("\nfetch in:")
        assert prompt.rstrip().endswith("JSON:")
        assert "fetch and summarize" in prompt

    asyncio.run(go())


def test_exclude_removes_candidates():
    async def go():
        reg = await _registry()
        eng = FakeEngine([GOOD, GOOD])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_plan_retries=0))
        ctx = PlanContext(registry=reg, exclude={"fetch"})
        # GOOD names "fetch", which is excluded -> unknown -> heuristic fallback.
        plan = await p.plan("summarize", ctx)
        assert all(n.service != "fetch" for n in plan.nodes)

    asyncio.run(go())


def test_model_in_the_loop_shape_only_grammar_falls_back_cleanly():
    """Real engine, random weights, constrain_names=off (round-1 behavior):
    constrained decode yields grammar-valid JSON whose service names are
    garbage -> planner must land on the heuristic fallback without ever
    raising a parse error (bug B7 fixed)."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        cfg = MCPXConfig.from_dict(
            {
                "model": {"size": "test", "max_seq_len": 256},
                "engine": {
                    "use_pallas": False,
                    "max_batch_size": 2,
                    "max_decode_len": 64,
                    "max_pages_per_seq": 16,
                    "temperature": 0.0,
                },
                "planner": {"kind": "llm", "max_plan_retries": 1, "constrain_names": "off"},
            }
        )
        eng = InferenceEngine(cfg)
        p = LLMPlanner(eng, cfg.planner)
        try:
            reg = await _registry()
            plan = await p.plan("fetch then summarize", PlanContext(registry=reg))
            assert plan.nodes
            plan.validate()
        finally:
            await eng.aclose()

    asyncio.run(go())


@pytest.mark.parametrize("mode", ["registry", "shortlist"])
def test_model_in_the_loop_trie_grammar_accepts_llm_plan(mode):
    """Real engine, random weights, trie-constrained names (VERDICT r1 #2):
    the model CANNOT emit an unknown service, so even noise-weight decodes
    produce accepted LLM plans — origin stays 'llm', no heuristic fallback,
    and every node resolves to a registry endpoint."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        cfg = MCPXConfig.from_dict(
            {
                "model": {"size": "test", "max_seq_len": 256},
                "engine": {
                    "use_pallas": False,
                    "max_batch_size": 2,
                    "max_decode_len": 96,
                    "max_pages_per_seq": 16,
                    "temperature": 0.0,
                },
                "planner": {
                    "kind": "llm",
                    "max_plan_retries": 0,
                    "constrain_names": mode,
                },
            }
        )
        eng = InferenceEngine(cfg)
        p = LLMPlanner(eng, cfg.planner)
        try:
            reg = await _registry()
            ctx = PlanContext(
                registry=reg,
                shortlist=["fetch", "summarize"] if mode == "shortlist" else None,
            )
            plan = await p.plan("fetch then summarize", ctx)
            assert plan.origin == "llm", plan.explanation
            assert plan.nodes
            for n in plan.nodes:
                assert n.service in ("fetch", "summarize")
                assert n.endpoint.startswith("http://svc/")
            plan.validate()
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_grammar_cache_identity_per_registry_version():
    """Concurrent plans against one registry version must share ONE grammar
    object (engine batches by grammar identity); a registry mutation bumps
    the version and yields a fresh grammar."""

    async def go():
        reg = await _registry()
        eng = FakeEngine([GOOD] * 4)
        p = LLMPlanner(eng, PlannerConfig(kind="llm"))
        v = await reg.version()
        ctx = PlanContext(registry=reg, registry_version=v)
        recs = await reg.list_services()
        g1, g2 = await asyncio.gather(p._grammar(ctx, v, recs), p._grammar(ctx, v, recs))
        assert g1 is g2
        assert g1 is not None and g1.service_names == ("fetch", "summarize")
        await reg.put(ServiceRecord(name="extra", endpoint="http://svc/extra"))
        v2 = await reg.version()
        assert v2 != v
        ctx2 = PlanContext(registry=reg, registry_version=v2)
        recs2 = await reg.list_services()
        g3 = await p._grammar(ctx2, v2, recs2)
        assert g3 is not g1
        assert g3.service_names is not None and "extra" in g3.service_names

    asyncio.run(go())


def test_grammar_ladder_keys_first_then_free_then_shape():
    """_build_grammar tries key tries first (constrain_input_keys default),
    falls back to free keys, then shape-only — each transition observable."""

    async def go():
        reg = await _registry()
        _, services = await __import__("mcpx.registry.base", fromlist=["stable_snapshot"]).stable_snapshot(reg)
        p = LLMPlanner(FakeEngine([]), PlannerConfig(kind="llm"))
        g = p._build_grammar(["fetch", "summarize"], services)
        assert g is not None
        # Key tries took effect: a plan using a schema key is accepted...
        ok = '{"steps":[{"s":"fetch","in":["data"],"next":[]}]}'
        assert g.is_accept(g.walk(ok))
        # ...while an out-of-schema key is UNREPRESENTABLE.
        bad = '{"steps":[{"s":"fetch","in":["nope"],"next":[]}]}'
        assert g.walk(bad) == g.dead_state

        # With constrain_input_keys=off, free-string keys are accepted.
        p2 = LLMPlanner(FakeEngine([]), PlannerConfig(kind="llm", constrain_input_keys="off"))
        g2 = p2._build_grammar(["fetch", "summarize"], services)
        assert g2.walk(bad) != g2.dead_state

    asyncio.run(go())


def test_exclude_builds_grammar_without_excluded_name():
    """Replan exclusions leave the trie (not just the resolution map):
    an excluded service's name becomes unrepresentable."""

    async def go():
        reg = await _registry()
        from mcpx.registry.base import stable_snapshot

        version, services = await stable_snapshot(reg)
        p = LLMPlanner(FakeEngine([]), PlannerConfig(kind="llm"))
        ctx = PlanContext(registry=reg, exclude={"fetch"}, registry_version=version)
        g = await p._grammar(ctx, version, services)
        assert g is not None
        assert g.walk('{"steps":[{"s":"summarize","in":[],"next":[]}]}') != g.dead_state
        assert g.walk('{"steps":[{"s":"fetch","in":[],"next":[]}]}') == g.dead_state
        # Cache key includes the exclude set: a no-exclude context gets a
        # different grammar that still accepts "fetch".
        ctx2 = PlanContext(registry=reg, registry_version=version)
        g2 = await p._grammar(ctx2, version, services)
        assert g2 is not g
        assert g2.walk('{"steps":[{"s":"fetch","in":[],"next":[]}]}') != g2.dead_state

    asyncio.run(go())


def test_warm_hands_the_registry_grammar_to_the_engine():
    async def go():
        reg = await _registry()
        eng = FakeEngine(["x"])
        p = LLMPlanner(eng, PlannerConfig(kind="llm"))
        await p.warm(reg)
        # The engine compiles for the registry grammar itself; the planner
        # serves no request to get there.
        assert len(eng.warmed) == 1 and eng.prompts == []
        assert eng.warmed[0].walk('{"steps":[{"s":"fetch"') != eng.warmed[0].dead_state
        # Empty registry: warm is a no-op, not an error.
        empty = InMemoryRegistry()
        await p.warm(empty)
        assert len(eng.warmed) == 1

    asyncio.run(go())


def test_repair_prunes_dangling_and_backward_next():
    """Grammar-valid decodes whose 'next' references name un-emitted or
    earlier steps are REPAIRED (forward edges to kept steps only) instead of
    discarded to the heuristic — the main fallback cause at 1k-service
    registries (trie guarantees registry membership, not step membership)."""

    async def go():
        reg = await _registry()
        # "ghost" exists in the registry? No — but repair drops the EDGE, not
        # the step; both steps exist in the registry here while "next" points
        # at an un-emitted service and backwards.
        wire = (
            '{"steps":['
            '{"s":"fetch","in":[],"next":["summarize","fetch"]},'
            '{"s":"summarize","in":["data"],"next":["fetch"]},'
            '{"s":"summarize","in":[],"next":[]}'
            "]}"
        )
        eng = FakeEngine([wire])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_plan_retries=0))
        plan = await p.plan("x", PlanContext(registry=reg))
        assert plan.origin == "llm"
        assert [n.name for n in plan.nodes] == ["fetch", "summarize"]  # dup dropped
        assert len(plan.edges) == 1  # forward fetch->summarize only
        assert plan.edges[0].src == "fetch" and plan.edges[0].dst == "summarize"
        assert "repaired" in plan.explanation

    asyncio.run(go())


def test_normalize_dataflow_rewires_and_prunes():
    """The planner turns an LLM plan's declared topology into real
    dataflow: step-wire inputs arrive as {key: key} (payload-only under the
    executor's name-keyed results), so overlapping keys along emitted edges
    are rewired to read the upstream node's result; an edge left carrying
    no data after rewiring is pruned (flag-disable restores it)."""

    async def go():
        reg = await _registry()
        await reg.put(
            ServiceRecord(
                name="audit",
                endpoint="http://svc/audit",
                description="audit the request",
                input_schema={"query": "str"},  # nothing produces "query"
            )
        )
        wire = (
            '{"steps":['
            '{"s":"fetch","in":[],"next":["summarize","audit"]},'
            '{"s":"summarize","in":["data"],"next":[]},'
            '{"s":"audit","in":["query"],"next":[]}'
            "]}"
        )
        p = LLMPlanner(
            FakeEngine([wire]), PlannerConfig(kind="llm", max_plan_retries=0)
        )
        plan = await p.plan("x", PlanContext(registry=reg))
        assert plan.origin == "llm"
        assert [(e.src, e.dst) for e in plan.edges] == [("fetch", "summarize")]
        assert "1 dataflow-free edge(s) pruned" in plan.explanation
        # The surviving edge now MOVES data: summarize reads fetch's result
        # (executor results are keyed by node name), not payload["data"].
        assert plan.node("summarize").inputs == {"data": "fetch"}
        # audit keeps its payload wiring and is a parallel root, not
        # serialized behind a service it shares nothing with.
        assert plan.node("audit").inputs == {"query": "query"}
        assert plan.topological_generations()[0] == sorted(["fetch", "audit"])

        p_off = LLMPlanner(
            FakeEngine([wire]),
            PlannerConfig(
                kind="llm", max_plan_retries=0, prune_dataflow_free_edges=False
            ),
        )
        plan_off = await p_off.plan("x", PlanContext(registry=reg))
        assert len(plan_off.edges) == 2
        # Rewiring happens regardless of the prune flag.
        assert plan_off.node("summarize").inputs == {"data": "fetch"}

    asyncio.run(go())


def test_token_exact_clamp_packs_subword_prompts():
    """With a subword vocab the clamp is token-exact: the prompt may exceed
    the budget in CHARS (impossible under the old 1-char=1-token clamp) while
    its encoding stays within the token budget, so shortlist lines that a
    char clamp would drop survive."""

    async def go():
        from mcpx.models.tokenizer import make_tokenizer

        reg = await _registry()
        for i in range(30):
            await reg.put(
                ServiceRecord(
                    name=f"catalog-fetch-{i:04d}",
                    endpoint=f"http://x/{i}",
                    input_schema={"query": "str", "user_id": "str"},
                    output_schema={"status": "str"},
                )
            )
        eng = FakeEngine([GOOD])
        eng.tokenizer = make_tokenizer("bpe")
        budget = 160
        p = LLMPlanner(eng, PlannerConfig(kind="llm", max_prompt_tokens=budget))
        ctx = PlanContext(
            registry=reg,
            shortlist=[f"catalog-fetch-{i:04d}" for i in range(30)],
        )
        await p.plan("fetch the catalog things", ctx)
        prompt = eng.prompts[0]
        n_tokens = len(eng.tokenizer.encode(prompt))
        assert n_tokens <= budget, n_tokens
        assert len(prompt) > budget  # chars exceed the token budget: packed
        assert prompt.count("\ncatalog-fetch-") >= 8  # far more than a char clamp keeps
        assert prompt.rstrip().endswith("JSON:")
        assert "fetch the catalog things" in prompt

    asyncio.run(go())


def test_typed_dataflow_size_gate_is_observable():
    """constrain_dataflow=True with a shortlist wider than the 24-service
    typed gate must NOT silently serve an untyped grammar: the typed_off
    fallback counter and a warning record that the dataflow guarantee is
    off (same observability contract as a failed typed build)."""

    async def go():
        from mcpx.telemetry.metrics import Metrics

        reg = InMemoryRegistry()
        for i in range(30):
            await reg.put(
                ServiceRecord(
                    name=f"svc-{i:04d}",
                    endpoint=f"http://x/{i}",
                    input_schema={"query": "str"},
                    output_schema={"status": "str"},
                )
            )
        from mcpx.registry.base import stable_snapshot

        version, services = await stable_snapshot(reg)
        eng = FakeEngine([])
        eng.metrics = Metrics()
        p = LLMPlanner(eng, PlannerConfig(kind="llm", constrain_names="shortlist"))

        def typed_off():
            return eng.metrics.grammar_fallbacks.labels(kind="typed_off")._value.get()

        before = typed_off()
        g = p._build_grammar(
            [s.name for s in services], services, version=version, typed=True
        )
        assert g is not None
        assert typed_off() == before + 1

        # Within the gate: no typed_off increment.
        g2 = p._build_grammar(
            [s.name for s in services[:8]], services[:8], version=version, typed=True
        )
        assert g2 is not None
        assert typed_off() == before + 1

    asyncio.run(go())


# ------------------------------------------------- the catalogue (a shortlist
# that covers the registry): one shared head, encoded once a registry version
class _CountingTokenizer(ByteTokenizer):
    def __init__(self):
        super().__init__()
        self.encoded = []

    def encode(self, text, **kw):
        self.encoded.append(text)
        return super().encode(text, **kw)


class _RecordingEngine(FakeEngine):
    def __init__(self, outputs):
        super().__init__(outputs)
        self.tokenizer = _CountingTokenizer()
        self.calls = []

    async def generate(self, prompt_ids, **kw):
        self.calls.append((list(prompt_ids), kw))
        return await super().generate(prompt_ids, **kw)


async def _catalogue_registry(n=12):
    reg = InMemoryRegistry()
    for i in reversed(range(n)):  # put in another order than the registry lists them
        await reg.put(ServiceRecord(name=f"svc{i:02d}", endpoint=f"http://svc/{i}",
                                    input_schema={"a": "str"}, output_schema={f"o{i}": "str"}))
    return reg


_ONE = '{"steps":[{"s":"svc03","in":[],"next":[]}]}'


@pytest.mark.parametrize("top_k, catalogue", [(12, True), (1000, True), (11, False)],
                         ids=["covers", "past", "one-short"])
def test_a_shortlist_that_covers_the_registry_is_one_shared_catalogue(top_k, catalogue):
    from mcpx.planner.llm import _PROMPT_HEADER, render_prompt

    async def go():
        reg = await _catalogue_registry()
        eng = _RecordingEngine([_ONE, _ONE, _ONE])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", shortlist_top_k=top_k, max_prompt_tokens=8192))
        ctx = PlanContext(registry=reg, registry_version=await reg.version())
        for intent in ("convert a and report", "an entirely different wording"):
            await p.plan(intent, ctx)
        services = await reg.list_services()
        header = eng.tokenizer.encode(_PROMPT_HEADER)
        blocks = [t for t in eng.tokenizer.encoded if t.startswith("svc00 ")]
        (ids_a, kw_a), (ids_b, kw_b) = eng.calls
        for intent, ids in (("convert a and report", ids_a), ("an entirely different wording", ids_b)):
            # the same bytes as the one-piece rendering, whatever way they were encoded
            assert eng.tokenizer.decode(ids) == render_prompt(intent, services, ctx)[0]
        if not catalogue:
            # rendered and declared as today: only the fixed header is shared
            assert kw_a["shared_prefix_len"] == kw_b["shared_prefix_len"] == len(header)
            assert not [t for t in blocks if t.endswith("\n")]
            return
        # registry (name) order, the same ids for two intents, encoded ONCE
        assert len(blocks) == 1 and blocks[0].endswith("\n") and "Intent" not in blocks[0]
        assert [line.split(" ")[0] for line in blocks[0].splitlines()] == [f"svc{i:02d}" for i in range(12)]
        head = len(header) + len(ByteTokenizer().encode(blocks[0], bos=False))
        assert kw_a["shared_prefix_len"] == kw_b["shared_prefix_len"] == head
        assert ids_a[:head] == ids_b[:head] and ids_a[head:] != ids_b[head:]
        if top_k < 13:
            return  # a thirteenth service makes this one a shortlist again
        # a new registry version (a line changes) encodes anew, once
        await reg.put(ServiceRecord(name="svc99", endpoint="http://svc/99", input_schema={"a": "str"}))
        await p.plan("a third", PlanContext(registry=reg, registry_version=await reg.version()))
        blocks = [t for t in eng.tokenizer.encoded if t.startswith("svc00 ")]
        assert len(blocks) == 2 and "svc99 " in blocks[1]
        assert eng.calls[2][1]["shared_prefix_len"] > head

    asyncio.run(go())


def test_a_ranked_shortlist_renders_as_it_always_did():
    """Below the registry's size the retriever's order and the header-only
    shared prefix stay, byte for byte."""
    from mcpx.planner.llm import _PROMPT_HEADER, render_prompt

    async def go():
        reg = await _catalogue_registry()
        eng = _RecordingEngine([_ONE])
        p = LLMPlanner(eng, PlannerConfig(kind="llm", shortlist_top_k=3))
        ctx = PlanContext(registry=reg, shortlist=["svc07", "svc03", "svc10"])
        await p.plan("rank these", ctx)
        by = {s.name: s for s in await reg.list_services()}
        want, _ = render_prompt("rank these", [by[n] for n in ("svc07", "svc03", "svc10")], ctx)
        ids, kw = eng.calls[0]
        assert eng.tokenizer.decode(ids) == want
        assert kw["shared_prefix_len"] == len(eng.tokenizer.encode(_PROMPT_HEADER))

    asyncio.run(go())


def test_the_control_plane_skips_retrieval_for_a_catalogue():
    from mcpx.server.control import ControlPlane

    class Retriever:
        size = 12

        def __init__(self):
            self.asked = []

        async def shortlist(self, intent, k):
            self.asked.append(k)
            return [f"svc{i:02d}" for i in range(min(k, 12))][::-1]

    async def go():
        reg = await _catalogue_registry()
        for top_k, asked, listed in ((12, [], None), (5, [5], 5)):
            cfg = MCPXConfig()
            cfg.planner.shortlist_top_k = top_k
            retriever = Retriever()
            cp = ControlPlane(config=cfg, registry=reg, planner=None, orchestrator=None, retriever=retriever)
            ctx = await cp._context("anything")
            assert retriever.asked == asked
            assert (ctx.shortlist is None) if listed is None else len(ctx.shortlist) == listed
            # a replan's exclusions are ranked around as before
            await cp._context("anything", exclude={"svc01"})
            assert retriever.asked == asked + [top_k + 1]

    asyncio.run(go())
