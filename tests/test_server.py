"""API-surface integration tests: aiohttp TestClient against the full app
with fake in-process microservices (SURVEY.md §4.4)."""

import asyncio

from aiohttp.test_utils import TestClient, TestServer

from mcpx.core.config import MCPXConfig
from mcpx.orchestrator.transport import RouterTransport
from mcpx.server.app import build_app
from mcpx.server.factory import build_control_plane

from tests.helpers import FakeService, make_transport


def make_app(*services: FakeService, config=None, planner=None):
    transport = RouterTransport(local=make_transport(*services))
    cp = build_control_plane(config or MCPXConfig(), transport=transport, planner=planner)
    return cp, build_app(cp)


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def seed_services(cp, *records):
    async def go():
        for r in records:
            await cp.registry.put(r)

    return go()


def test_full_flow_plan_execute():
    from mcpx.registry import ServiceRecord

    search = FakeService("search", result={"document": "the doc"})
    summarize = FakeService("summarize", result={"summary": "short"})

    async def go():
        cp, app = make_app(search, summarize)
        await cp.registry.put(
            ServiceRecord(
                name="search",
                endpoint="local://search",
                description="search documents by query",
                input_schema={"query": "str"},
                output_schema={"document": "str"},
            )
        )
        await cp.registry.put(
            ServiceRecord(
                name="summarize",
                endpoint="local://summarize",
                description="summarize a document",
                input_schema={"document": "str"},
                output_schema={"summary": "str"},
            )
        )

        async def drive(client):
            # /plan (reference wire: PlanRequest{intent} -> PlanResponse{graph})
            r = await client.post("/plan", json={"intent": "search documents and summarize"})
            assert r.status == 200
            plan_body = await r.json()
            assert "graph" in plan_body and plan_body["explanation"]
            # /execute with the planned graph
            r = await client.post(
                "/execute", json={"graph": plan_body["graph"], "payload": {"query": "q"}}
            )
            assert r.status == 200
            body = await r.json()
            assert body["status"] == "ok"
            assert body["results"]["summarize"] == {"summary": "short"}
            assert body["trace"]["nodes"]
            # /plan_and_execute end to end
            r = await client.post(
                "/plan_and_execute",
                json={"intent": "search documents and summarize", "payload": {"query": "q"}},
            )
            assert r.status == 200
            body = await r.json()
            assert body["status"] == "ok"
            assert body["replans"] == 0

        await with_client(app, drive)

    asyncio.run(go())


def test_validation_errors():
    async def go():
        cp, app = make_app()

        async def drive(client):
            r = await client.post("/plan", json={"intent": ""})
            assert r.status == 400
            r = await client.post("/plan", data=b"{not json")
            assert r.status == 400
            r = await client.post("/execute", json={"graph": {"nodes": [{"name": "a"}], "edges": [{"from": "a", "to": "ghost"}]}})
            assert r.status == 422
            body = await r.json()
            assert any("ghost" in p for p in body["problems"])
            # Empty registry -> planning fails cleanly.
            r = await client.post("/plan", json={"intent": "do something"})
            assert r.status == 422

        await with_client(app, drive)

    asyncio.run(go())


def test_service_crud_and_observability():
    async def go():
        cp, app = make_app()

        async def drive(client):
            record = {
                "name": "svc-a",
                "endpoint": "local://svc-a",
                "input_schema": {"x": "str"},
                "output_schema": {"y": "str"},
            }
            r = await client.post("/services", json=record)
            assert r.status == 201
            r = await client.get("/services")
            body = await r.json()
            assert [s["name"] for s in body["services"]] == ["svc-a"]
            assert body["version"] == 1
            r = await client.get("/services/svc-a")
            assert (await r.json())["endpoint"] == "local://svc-a"
            r = await client.delete("/services/svc-a")
            assert r.status == 200
            r = await client.get("/services/svc-a")
            assert r.status == 404
            # Observability endpoints.
            r = await client.get("/healthz")
            assert (await r.json())["status"] == "ok"
            r = await client.get("/metrics")
            text = await r.text()
            assert "mcpx_requests_total" in text
            r = await client.get("/telemetry")
            assert r.status == 200

        await with_client(app, drive)

    asyncio.run(go())


def test_cache_endpoint_combines_plan_and_prefix_stats():
    """GET /cache (ISSUE 8 satellite): plan-cache hit accounting readable
    as JSON instead of scrape-only counters; the prefix block is null on a
    heuristic control plane (no engine) and reports enabled/nodes/hit_rate
    when an engine is attached."""

    async def go():
        cp, app = make_app()

        async def drive(client):
            await client.post(
                "/services",
                json={
                    "name": "svc-a",
                    "endpoint": "local://svc-a",
                    "input_schema": {"x": "str"},
                    "output_schema": {"y": "str"},
                },
            )
            r = await client.post("/plan", json={"intent": "use svc-a"})
            assert r.status == 200
            r = await client.post("/plan", json={"intent": "use svc-a"})
            assert r.status == 200
            r = await client.get("/cache")
            assert r.status == 200
            body = await r.json()
            pc = body["plan_cache"]
            assert pc["hits"] == 1 and pc["misses"] == 1
            assert pc["entries"] == 1 and pc["hit_rate"] == 0.5
            # Heuristic planner: no engine, no prefix tree.
            assert body["prefix_cache"] is None

        await with_client(app, drive)

        # With an engine-shaped planner the prefix block surfaces.
        class EngineStub:
            def prefix_cache_stats(self):
                return {"enabled": True, "nodes": 3, "hit_rate": 0.75}

        class PlannerStub:
            engine = EngineStub()

            async def plan(self, intent, context):
                raise AssertionError("unused")

        cp.planner = PlannerStub()
        assert cp.cache_stats()["prefix_cache"]["nodes"] == 3

    asyncio.run(go())


def test_cache_endpoint_surfaces_tier_and_governor_stats():
    """GET /cache (ISSUE 11 satellite): with the tiered KV cache armed the
    prefix block carries the host-tier accounting (resident host tokens/
    bytes, spills/readmits/destructive evictions) and the per-tenant
    governor spread; single-tier engines report both as null (the
    pass-through contract)."""
    from mcpx.core.config import MCPXConfig
    from mcpx.engine.engine import InferenceEngine

    eng = InferenceEngine(
        MCPXConfig.from_dict(
            {
                "model": {"size": "test"},
                "engine": {"kv_tier": {"enabled": True, "host_mb": 8.0}},
            }
        )
    )
    st = eng.prefix_cache_stats()
    tier = st["tier"]
    assert tier["enabled"] is True
    for key in (
        "host_tokens", "host_bytes", "host_bytes_budget", "spills",
        "readmits", "destructive_evictions", "denied_readmits",
    ):
        assert key in tier, key
    assert st["governor"] == {}  # no tenants observed yet
    assert "spilled_nodes" in st and "host_pages" in st
    # queue_stats prefix scoreboard extension rides the same counters.
    eng._governor.on_insert("gold", 32)
    assert eng.prefix_cache_stats()["governor"]["gold"]["resident_tokens"] == 32
    off = InferenceEngine(
        MCPXConfig.from_dict({"model": {"size": "test"}})
    )
    st_off = off.prefix_cache_stats()
    assert st_off["tier"] is None and st_off["governor"] is None


def test_failed_grammar_warm_is_visible_in_healthz_and_serving_continues():
    """ControlPlane.startup survives a failed registry-grammar warm, but
    never quietly: /healthz reaches ``started`` with the cause under
    ``warm_error`` (not ``engine_error`` — the engine is fine), and /plan
    keeps answering."""
    from mcpx.core.errors import PlannerError
    from mcpx.planner.heuristic import HeuristicPlanner
    from mcpx.registry import ServiceRecord

    class WarmFails(HeuristicPlanner):
        async def warm(self, registry):
            raise PlannerError("no column bucket for this trie")

    async def go():
        cp, app = make_app(planner=WarmFails())
        assert cp.started is False and cp.warm_error is None
        await cp.registry.put(
            ServiceRecord(
                name="search",
                endpoint="local://search",
                description="search documents by query",
                input_schema={"query": "str"},
                output_schema={"document": "str"},
            )
        )

        async def drive(client):
            for _ in range(100):  # startup() is the app's background task
                body = await (await client.get("/healthz")).json()
                if body["started"]:
                    break
                await asyncio.sleep(0.01)
            assert body["started"] is True and body["status"] == "ok"
            assert body["warm_error"] == "PlannerError: no column bucket for this trie"
            assert "engine_error" not in body
            r = await client.post("/plan", json={"intent": "search documents"})
            assert r.status == 200 and "graph" in await r.json()

        await with_client(app, drive)

    asyncio.run(go())


def test_injected_engine_counters_show_on_served_metrics():
    """build_control_plane(config, planner=...) adopts the injected
    planner's engine Metrics as the control plane's registry: the engine's
    series (the compile sentinel, the pool-reset counter) are on the SERVED
    GET /metrics, next to the server's own, not on a registry nothing
    scrapes."""
    from mcpx.engine.engine import InferenceEngine
    from mcpx.planner.heuristic import HeuristicPlanner
    from mcpx.planner.llm import LLMPlanner

    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {"use_pallas": False, "max_batch_size": 2, "max_decode_len": 16},
            "planner": {"kind": "llm"},
        }
    )
    eng = InferenceEngine(cfg)

    async def go():
        cp, app = make_app(config=cfg, planner=LLMPlanner(eng, cfg.planner))
        assert cp.metrics is eng.metrics
        # A planner with no engine still gets a registry of its own.
        other, _ = make_app(planner=HeuristicPlanner())
        assert other.metrics is not eng.metrics

        async def drive(client):
            for _ in range(600):  # startup() starts the engine in the background
                body = await (await client.get("/healthz")).json()
                if body["started"]:
                    break
                await asyncio.sleep(0.1)
            assert body["started"] is True and "engine_error" not in body
            # One generate compiles its executables in the serving path.
            await eng.generate(
                eng.tokenizer.encode("count my compiles"), max_new_tokens=4,
                constrained=False,
            )
            eng.metrics.engine_resets.inc()
            text = await (await client.get("/metrics")).text()
            assert 'mcpx_engine_compiles_total{executable="' in text
            assert "mcpx_engine_resets_total 1.0" in text
            assert 'mcpx_requests_total{endpoint="/healthz"' in text

        await with_client(app, drive)

    asyncio.run(asyncio.wait_for(go(), 240))


def test_missing_registration_returns_400():
    async def go():
        cp, app = make_app()

        async def drive(client):
            r = await client.post("/services", json={"name": "x"})  # no endpoint
            assert r.status == 400

        await with_client(app, drive)

    asyncio.run(go())


def test_profile_transition_in_progress_409(monkeypatch):
    """The concurrency contract of /profile/start|stop (ISSUE 7 satellite):
    while a start's ``device_trace.start`` is still in flight in a worker thread,
    a concurrent stop must 409 on the _STARTING sentinel ("transition in
    progress") and a concurrent start must 409 on the reservation — neither
    may race jax's single-session profiler state."""
    import threading

    from mcpx.telemetry import device_trace

    release = threading.Event()
    entered = threading.Event()
    calls = {"start": 0, "stop": 0}

    def fake_start():
        calls["start"] += 1
        entered.set()
        release.wait(10)
        return "session"

    def fake_stop(session, trace_dir):
        assert session == "session"
        calls["stop"] += 1

    async def go():
        cp, app = make_app()
        monkeypatch.setattr(device_trace, "start", fake_start)
        monkeypatch.setattr(device_trace, "stop", fake_stop)

        async def drive(client):
            task = asyncio.create_task(
                client.post("/profile/start", json={"dir": "/tmp/mcpx-prof-t"})
            )
            assert await asyncio.to_thread(entered.wait, 10)
            # start_trace is blocked in its thread: the reservation is live.
            r = await client.post("/profile/stop")
            assert r.status == 409
            assert "transition in progress" in (await r.json())["error"]
            r2 = await client.post("/profile/start", json={"dir": "/tmp/other"})
            assert r2.status == 409  # reservation counts as "already active"
            release.set()
            r0 = await task
            assert r0.status == 200
            r3 = await client.post("/profile/stop")
            assert r3.status == 200
            assert calls == {"start": 1, "stop": 1}

        await with_client(app, drive)

    asyncio.run(go())


def test_shutdown_during_profiler_transition_skips_flush(monkeypatch):
    """Shutdown racing an in-flight profiler transition must SKIP the
    at-shutdown flush (flushing would race the transition thread inside
    jax's profiler) and clear the sentinel — previously only a code
    comment, now pinned."""
    import threading

    from mcpx.telemetry import device_trace

    release = threading.Event()
    entered = threading.Event()
    calls = {"start": 0, "stop": 0}

    def fake_start():
        calls["start"] += 1

    def fake_stop(session, trace_dir):
        calls["stop"] += 1
        entered.set()
        release.wait(10)

    async def go():
        cp, app = make_app()
        monkeypatch.setattr(device_trace, "start", fake_start)
        monkeypatch.setattr(device_trace, "stop", fake_stop)

        async def drive(client):
            r = await client.post("/profile/start", json={"dir": "/tmp/mcpx-prof-s"})
            assert r.status == 200
            task = asyncio.create_task(client.post("/profile/stop"))
            assert await asyncio.to_thread(entered.wait, 10)
            # Stop is mid-flight (_STOPPING). Run the app's cleanup NOW —
            # the shutdown-during-transition path: it must not dispatch a
            # second stop (the flush) and must clear the sentinel.
            before = calls["stop"]
            for cb in app.on_cleanup:
                await cb(app)
            assert calls["stop"] == before  # no flush dispatched
            # Sentinel cleared: the profiler state no longer reads active.
            r2 = await client.post("/profile/stop")
            assert r2.status == 409
            assert "not active" in (await r2.json())["error"]
            release.set()
            r0 = await task
            assert r0.status == 200  # the in-flight stop still completes

        await with_client(app, drive)

    asyncio.run(go())


def test_profile_endpoints(tmp_path):
    """POST /profile/start captures a profiler trace of device work done
    while active; double-start and stop-without-start are 409s. The capture
    is ONE .xplane.pb that jax's own reader opens, and no Chrome-trace JSON
    beside it (the export that made a flush outlast the request timeout on
    the chip: telemetry/device_trace.py)."""

    async def go():
        cp, app = make_app()

        async def drive(client):
            trace_dir = str(tmp_path / "traces")
            r = await client.post("/profile/stop")
            assert r.status == 409
            r = await client.post("/profile/start", json={"dir": trace_dir})
            assert r.status == 200, await r.text()
            r2 = await client.post("/profile/start", json={"dir": trace_dir})
            assert r2.status == 409
            # Some device work while the trace is active.
            import jax.numpy as jnp

            jnp.ones((8, 8)).sum().block_until_ready()
            r3 = await client.post("/profile/stop")
            assert r3.status == 200
            assert (await r3.json())["dir"] == trace_dir
            import pathlib

            files = [f for f in pathlib.Path(trace_dir).rglob("*") if f.is_file()]
            assert [f.suffixes[-2:] for f in files] == [[".xplane", ".pb"]], files
            assert files[0].parent.parent == pathlib.Path(trace_dir) / "plugins" / "profile"
            from jax.profiler import ProfileData

            planes = [p.name for p in ProfileData.from_file(str(files[0])).planes]
            assert any(p.startswith("/host:") for p in planes), planes
            # the session is released: a second capture can start
            r4 = await client.post("/profile/start", json={"dir": trace_dir})
            assert r4.status == 200, await r4.text()
            assert (await client.post("/profile/stop")).status == 200

        await with_client(app, drive)

    asyncio.run(go())
