"""Request-tracing spine (mcpx/telemetry/tracing.py, ISSUE 4): span-tree
integrity under concurrency, ring eviction + tail sampling, Chrome
trace-event export, W3C traceparent round-trip through the HTTP layer,
exemplar linkage, and disabled-mode no-op equivalence on engine outputs."""

import asyncio
import json
import logging

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.orchestrator.transport import RouterTransport
from mcpx.server.app import build_app
from mcpx.server.factory import build_control_plane
from mcpx.telemetry import tracing
from mcpx.telemetry.tracing import (
    JsonLogFormatter,
    TraceLogFilter,
    Tracer,
    format_traceparent,
    parse_traceparent,
)

from tests.helpers import FakeService, make_transport


# ------------------------------------------------------------------ span tree
def test_span_tree_parent_links_and_attrs():
    tr = Tracer(enabled=True, sample_rate=1.0)
    root = tr.start_request("/plan", method="POST")
    with tracing.activate(root):
        with tracing.span("plan", path="primary") as sp:
            assert sp is not None
            with tracing.span("engine.generate") as esp:
                esp.set(tokens=7)
        assert tr.finish(root) is True
    rec = tr.get(root.record.trace_id)
    assert rec is not None
    by_name = {s.name: s for s in rec.spans}
    assert by_name["plan"].parent_id == root.span_id
    assert by_name["engine.generate"].parent_id == by_name["plan"].span_id
    assert by_name["engine.generate"].attrs["tokens"] == 7
    # Every span closed, every duration inside the root's window.
    for s in rec.spans:
        assert s.t1 >= s.t0
        assert s.t0 >= root.t0 - 1e-9


def test_span_noop_without_active_trace():
    # No active root: span() yields None and records nothing anywhere.
    with tracing.span("orphan") as sp:
        assert sp is None
    assert tracing.current_span() is None
    assert tracing.current_trace_id() is None


def test_concurrent_requests_do_not_leak_spans_across_contextvars():
    tr = Tracer(enabled=True, sample_rate=1.0, ring_size=64)

    async def one(i: int) -> str:
        root = tr.start_request(f"/req{i}")
        with tracing.activate(root):
            for j in range(3):
                with tracing.span(f"step{i}.{j}"):
                    await asyncio.sleep(0)
            tr.finish(root)
        return root.record.trace_id

    async def go():
        return await asyncio.gather(*(asyncio.create_task(one(i)) for i in range(8)))

    tids = asyncio.run(go())
    assert len(set(tids)) == 8
    for i, tid in enumerate(tids):
        rec = tr.get(tid)
        names = {s.name for s in rec.spans}
        assert names == {f"/req{i}"} | {f"step{i}.{j}" for j in range(3)}
        # No cross-request contamination: every span belongs to this record.
        assert all(s.record is rec for s in rec.spans)


def test_worker_thread_child_spans_with_explicit_timestamps():
    # The engine-worker pattern: explicit parent.child(t0=, t1=) from
    # another thread, no contextvar involvement.
    import threading

    tr = Tracer(enabled=True, sample_rate=1.0)
    root = tr.start_request("/plan")

    def worker():
        root.child("engine.segment", t0=root.t0, t1=root.t0 + 0.002, tokens=4)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    tr.finish(root)
    rec = tr.get(root.trace_id)
    seg = next(s for s in rec.spans if s.name == "engine.segment")
    assert seg.attrs["tokens"] == 4
    assert abs(seg.duration_ms - 2.0) < 0.5


# ------------------------------------------------------- sampling + retention
def test_ring_eviction_keeps_newest():
    tr = Tracer(enabled=True, sample_rate=1.0, ring_size=2)
    tids = []
    for i in range(4):
        root = tr.start_request(f"/r{i}")
        tr.finish(root)
        tids.append(root.record.trace_id)
    assert tr.get(tids[0]) is None and tr.get(tids[1]) is None
    assert tr.get(tids[2]) is not None and tr.get(tids[3]) is not None
    assert [r.trace_id for r in tr.traces()] == [tids[3], tids[2]]


def test_head_sampling_zero_drops_but_errors_are_always_kept():
    tr = Tracer(enabled=True, sample_rate=0.0, ring_size=8)
    dropped = tr.start_request("/ok")
    assert tr.finish(dropped) is False
    assert tr.get(dropped.record.trace_id) is None
    kept = tr.start_request("/boom")
    assert tr.finish(kept, error=True) is True
    rec = tr.get(kept.record.trace_id)
    assert rec.error and rec.root.status == "error"


def test_sealed_record_drops_late_worker_spans():
    # The timeout/disconnect race: tracer.finish seals the record; a worker
    # thread still holding the span may keep calling child() but the
    # retained trace stays immutable (and chrome export consistent).
    tr = Tracer(enabled=True, sample_rate=1.0)
    root = tr.start_request("/plan")
    root.child("engine.queue_wait", t0=root.t0, t1=root.t0 + 0.001)
    tr.finish(root)
    n_before = len(root.record.spans)
    late = root.child("engine.segment", t0=root.t0, t1=root.t0 + 9.0, tokens=3)
    assert late.attrs["tokens"] == 3  # caller still gets a writable span
    assert len(root.record.spans) == n_before  # …but the record didn't grow
    assert tr.get(root.trace_id).to_chrome()  # export unaffected


def test_client_4xx_is_not_tail_kept_but_5xx_is():
    # Tail sampling keeps SERVER faults; a stream of client 400s (bot scan,
    # malformed bodies) must not flush the ring of the rare 5xx traces.
    search = FakeService("search", result={"document": "d"})
    cfg = MCPXConfig()
    cfg.tracing.sample_rate = 0.0  # head sampling off: only the tail keeps

    async def go():
        cp, app = _make_app(search, config=cfg)
        await _seed(cp)

        async def run(client):
            bad = await client.post("/plan", json={"intent": "   "})
            assert bad.status == 400
            assert cp.tracer.traces() == []
            missing = await client.post("/no-such-route", json={})
            assert missing.status == 404
            assert cp.tracer.traces() == []
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_slo_breach_tail_sampling():
    tr = Tracer(enabled=True, sample_rate=0.0, ring_size=8, slo_breach_ms=1.0)
    root = tr.start_request("/slow")
    root.end(root.t0 + 0.050)  # 50 ms > 1 ms breach threshold
    assert tr.finish(root) is True
    fast = tr.start_request("/fast")
    fast.end(fast.t0 + 0.0001)
    assert tr.finish(fast) is False


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    assert tr.start_request("/plan") is None
    assert tr.finish(None) is False
    assert tr.traces() == []


# -------------------------------------------------------------- chrome export
def test_chrome_export_schema_and_duration_sum():
    tr = Tracer(enabled=True, sample_rate=1.0)
    root = tr.start_request("/plan")
    t0 = root.t0
    # Sequential phases + two CONCURRENT siblings (fan-out) to exercise
    # lane assignment.
    root.child("sched.acquire", t0=t0, t1=t0 + 0.010)
    root.child("plan", t0=t0 + 0.010, t1=t0 + 0.090)
    root.child("node:a", t0=t0 + 0.020, t1=t0 + 0.060)
    root.child("node:b", t0=t0 + 0.020, t1=t0 + 0.080)
    root.end(t0 + 0.100)
    tr.finish(root)
    chrome = tr.get(root.trace_id).to_chrome()
    assert isinstance(chrome["traceEvents"], list)
    assert chrome["displayTimeUnit"] == "ms"
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 5
    for e in xs:
        # Trace-event schema: required keys, numeric us timestamps.
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e, e
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["args"]["span_id"]
    # The export sums to the measured end-to-end latency: the root event's
    # duration IS the request wall time, and each child fits inside it.
    root_ev = next(e for e in xs if e["name"] == "/plan")
    assert abs(root_ev["dur"] - 100e3) < 1e3
    for e in xs:
        assert e["ts"] + e["dur"] <= root_ev["ts"] + root_ev["dur"] + 1.0
    # Sequential phases share a lane with the root only if contained;
    # concurrent siblings node:a/node:b must land on DIFFERENT lanes.
    tid_a = next(e["tid"] for e in xs if e["name"] == "node:a")
    tid_b = next(e["tid"] for e in xs if e["name"] == "node:b")
    assert tid_a != tid_b
    # Valid JSON end-to-end (what `mcpx trace dump` writes for Perfetto).
    json.loads(json.dumps(chrome))


# ---------------------------------------------------------------- traceparent
def test_traceparent_parse_and_format():
    assert parse_traceparent(None) is None
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
    tid, pid = "ab" * 16, "cd" * 8
    parsed = parse_traceparent(f"00-{tid}-{pid}-01")
    assert parsed == (tid, pid)
    tr = Tracer(enabled=True)
    root = tr.start_request("/plan", traceparent=f"00-{tid}-{pid}-01")
    assert root.record.trace_id == tid
    assert root.record.remote_parent == pid
    hdr = format_traceparent(root)
    assert parse_traceparent(hdr) == (tid, root.span_id)


# ----------------------------------------------------------- HTTP integration
def _make_app(*services, config=None):
    transport = RouterTransport(local=make_transport(*services))
    cp = build_control_plane(config or MCPXConfig(), transport=transport)
    return cp, build_app(cp)


async def _with_client(app, fn):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def _seed(cp):
    from mcpx.registry import ServiceRecord

    return cp.registry.put(
        ServiceRecord(
            name="search",
            endpoint="local://search",
            description="search documents",
            input_schema={"query": "str"},
            output_schema={"document": "str"},
        )
    )


def test_traceparent_round_trip_through_http_layer():
    search = FakeService("search", result={"document": "d"})
    upstream_trace = "f" * 31 + "e"
    upstream_span = "a" * 16

    async def go():
        cp, app = _make_app(search)
        await _seed(cp)

        async def run(client):
            resp = await client.post(
                "/plan",
                json={"intent": "search documents"},
                headers={"traceparent": f"00-{upstream_trace}-{upstream_span}-01"},
            )
            assert resp.status == 200
            # The response joins the caller's trace: same trace id, our
            # root's span id, plus the legacy X-Trace-Id.
            parsed = parse_traceparent(resp.headers["traceparent"])
            assert parsed is not None and parsed[0] == upstream_trace
            assert resp.headers["X-Trace-Id"] == upstream_trace
            # The retained record preserves the remote parent for stitching.
            rec = cp.tracer.get(upstream_trace)
            assert rec is not None
            assert rec.remote_parent == upstream_span
            # The spine covered scheduler-free /plan: plan + context spans.
            names = [s.name for s in rec.spans]
            assert "/plan" in names[0] and "plan" in names
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_traces_endpoints_and_error_body_trace_id():
    search = FakeService("search", result={"document": "d"})

    async def go():
        cp, app = _make_app(search)
        await _seed(cp)

        async def run(client):
            ok = await client.post("/plan", json={"intent": "search documents"})
            assert ok.status == 200
            listing = await (await client.get("/traces")).json()
            assert listing["traces"], "ring should retain the sampled trace"
            tid = listing["traces"][0]["trace_id"]
            full = await (await client.get(f"/traces/{tid}")).json()
            assert full["trace_id"] == tid
            assert any(s["name"] == "plan" for s in full["tree"])
            chrome = await (await client.get(f"/traces/{tid}?format=chrome")).json()
            assert chrome["traceEvents"]
            # A 4xx carries its trace id in the BODY so the error line a
            # user pastes is greppable straight to its trace.
            bad = await client.post("/plan", json={"intent": "   "})
            assert bad.status == 400
            body = await bad.json()
            assert body["trace_id"]
            err_rec = cp.tracer.get(body["trace_id"])
            assert err_rec is not None
            # Missing trace: structured 404, also with a trace id.
            missing = await client.get("/traces/deadbeef")
            assert missing.status == 404
            # Observability endpoints never trace THEMSELVES: polling
            # /traces//metrics must not grow the ring.
            await client.get("/traces")
            await client.get("/metrics")
            n_after = len((await (await client.get("/traces")).json())["traces"])
            assert n_after == len(listing["traces"]) + 1  # +1 = the 400 error trace
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_exemplars_rendered_in_openmetrics_exposition():
    search = FakeService("search", result={"document": "d"})

    async def go():
        cp, app = _make_app(search)
        await _seed(cp)

        async def run(client):
            resp = await client.post("/plan", json={"intent": "search documents"})
            assert resp.status == 200
            tid = resp.headers["X-Trace-Id"]
            om = await client.get(
                "/metrics", headers={"Accept": "application/openmetrics-text"}
            )
            assert "openmetrics" in om.headers["Content-Type"]
            text = await om.text()
            # The latency histogram carries the exemplar trace id: a spike
            # links to a concrete GET /traces/{id}.
            assert f'trace_id="{tid}"' in text
            # Classic text exposition still renders (exemplars dropped).
            plain = await client.get("/metrics")
            assert "mcpx_request_latency_seconds" in await plain.text()
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_tracing_disabled_restores_legacy_surface():
    search = FakeService("search", result={"document": "d"})
    cfg = MCPXConfig()
    cfg.tracing.enabled = False

    async def go():
        cp, app = _make_app(search, config=cfg)
        await _seed(cp)

        async def run(client):
            resp = await client.post("/plan", json={"intent": "search documents"})
            assert resp.status == 200
            assert "traceparent" not in resp.headers
            assert resp.headers["X-Trace-Id"]  # legacy id survives
            listing = await (await client.get("/traces")).json()
            assert listing["traces"] == []
            bad = await client.post("/plan", json={"intent": "   "})
            assert "trace_id" not in await bad.json()
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_executor_node_attempts_as_spans_and_metric():
    """Node retries/fallbacks appear inline in the request trace (not in a
    parallel format) and feed mcpx_node_attempts_total."""
    flaky = FakeService("search", fail_times=1, result={"document": "d"})

    async def go():
        cp, app = _make_app(flaky)
        await _seed(cp)

        async def run(client):
            resp = await client.post(
                "/plan_and_execute",
                json={"intent": "search documents", "payload": {"query": "q"}},
            )
            assert resp.status == 200
            rec = cp.tracer.traces()[0]
            by_name = {}
            for s in rec.spans:
                by_name.setdefault(s.name, []).append(s)
            node_span = by_name["node:search"][0]
            attempts = by_name["attempt"]
            # One failed primary, one ok retry — inline under the node span.
            assert [a.attrs["kind"] for a in attempts] == ["primary", "retry"]
            assert [a.attrs["status"] for a in attempts] == ["error", "ok"]
            assert all(a.parent_id == node_span.span_id for a in attempts)
            assert by_name["execute"][0].parent_id is not None
            text = cp.metrics.render().decode()
            assert 'mcpx_node_attempts_total{kind="primary",status="error"} 1.0' in text
            assert 'mcpx_node_attempts_total{kind="retry",status="ok"} 1.0' in text
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


# ------------------------------------------------------------ structured logs
def test_json_log_lines_carry_trace_ids():
    tr = Tracer(enabled=True)
    root = tr.start_request("/plan")
    handler_records = []

    class Capture(logging.Handler):
        def emit(self, record):
            handler_records.append(JsonLogFormatter().format(record))

    logger = logging.getLogger("mcpx.test.tracelog")
    logger.setLevel(logging.INFO)
    cap = Capture()
    cap.addFilter(TraceLogFilter())
    logger.addHandler(cap)
    try:
        with tracing.activate(root):
            logger.info("inside request")
        logger.info("outside request")
    finally:
        logger.removeHandler(cap)
    inside = json.loads(handler_records[0])
    outside = json.loads(handler_records[1])
    assert inside["trace_id"] == root.record.trace_id
    assert inside["span_id"] == root.span_id
    assert inside["msg"] == "inside request"
    assert "trace_id" not in outside


# ------------------------------------------------- engine no-op + attribution
def test_engine_outputs_identical_with_tracing_on_and_off_and_segment_spans():
    """Acceptance: with tracing disabled the engine emits byte-identical
    token streams (greedy) — and with tracing enabled the per-request spans
    cover queue-wait, prefill and per-segment decode whose token counts sum
    to the generated total."""
    from tests.test_engine import make_engine

    prompt_text = "plan: compose the services. JSON:"

    async def run_engine(traced: bool):
        eng = make_engine()
        await eng.start()
        try:
            prompt = eng.tokenizer.encode(prompt_text)
            tr = Tracer(enabled=True, sample_rate=1.0)
            root = tr.start_request("/plan") if traced else None
            with tracing.activate(root):
                res = await eng.generate(prompt, max_new_tokens=32)
            if root is not None:
                tr.finish(root)
                return res.token_ids, tr.get(root.trace_id)
            # Hot-path guard: nothing traced means the slab never saw a
            # traced row.
            assert eng._slab.n_traced == 0
            return res.token_ids, None
        finally:
            await eng.aclose()

    async def go():
        ids_off, _ = await run_engine(traced=False)
        ids_on, rec = await run_engine(traced=True)
        assert ids_on == ids_off, "tracing must not perturb engine outputs"
        names = [s.name for s in rec.spans]
        for expect in ("engine.generate", "engine.queue_wait", "engine.prefill",
                       "engine.decode", "engine.segment"):
            assert expect in names, names
        gen = next(s for s in rec.spans if s.name == "engine.generate")
        segs = [s for s in rec.spans if s.name == "engine.segment"]
        assert sum(s.attrs["tokens"] for s in segs) == gen.attrs["tokens"]
        assert all(s.attrs["dfa_id"] >= 0 for s in segs)
        assert all(s.attrs["cls"] == "constrained" for s in segs)
        # Phase spans tile the generate window (within scheduling noise).
        qw = next(s for s in rec.spans if s.name == "engine.queue_wait")
        dec = next(s for s in rec.spans if s.name == "engine.decode")
        assert qw.t0 >= gen.t0 - 1e-3
        assert dec.t1 <= gen.t1 + 1e-3

    asyncio.run(go())


def test_full_plan_trace_under_hetero_batch_covers_every_layer():
    """ISSUE 4 acceptance: one /plan served by the REAL stack (scheduler
    enabled, LLM planner, hetero-batching engine) yields one trace whose
    spans cover scheduler queue-wait, planner path, engine admit-wait +
    per-segment decode — and whose Chrome export validates against the
    trace-event schema and sums (within tolerance) to the measured
    end-to-end latency."""
    import time as _time

    from mcpx.registry import ServiceRecord

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
                "hetero_batch": True,
            },
            "planner": {"kind": "llm", "max_plan_retries": 0},
            "scheduler": {"enabled": True},
        }
    )

    async def go():
        cp, app = _make_app(config=cfg)
        for name, outs in (("search", {"document": "str"}), ("enrich", {"user": "str"})):
            await cp.registry.put(
                ServiceRecord(
                    name=name,
                    endpoint=f"local://{name}",
                    description=f"{name} things",
                    input_schema={"query": "str"},
                    output_schema=outs,
                )
            )

        async def run(client):
            while True:
                health = await (await client.get("/healthz")).json()
                if health.get("engine") == "ready":
                    break
                assert health.get("engine") != "failed", health
                await asyncio.sleep(0.2)
            t_req0 = _time.monotonic()
            resp = await client.post("/plan", json={"intent": "search then enrich"})
            measured_ms = (_time.monotonic() - t_req0) * 1e3
            assert resp.status == 200
            tid = resp.headers["X-Trace-Id"]
            rec = cp.tracer.get(tid)
            assert rec is not None
            names = {s.name for s in rec.spans}
            assert {
                "sched.acquire",
                "plan",
                "planner.grammar",
                "engine.generate",
                "engine.queue_wait",
                "engine.prefill",
                "engine.segment",
                "engine.decode",
            } <= names, names
            sched = next(s for s in rec.spans if s.name == "sched.acquire")
            assert sched.attrs["verdict"] == "admitted"
            plan_span = next(s for s in rec.spans if s.name == "plan")
            assert plan_span.attrs["path"] == "primary"
            # Hetero attribution: segments carry the stacked-DFA slot and
            # row class for this constrained request.
            segs = [s for s in rec.spans if s.name == "engine.segment"]
            assert all(s.attrs["cls"] == "constrained" for s in segs)
            assert all(s.attrs["dfa_id"] >= 1 for s in segs)
            # Chrome export: schema-valid events, and the root event's
            # duration is the trace's end-to-end latency — within the
            # client-measured wall time (which adds HTTP overhead on top).
            chrome = rec.to_chrome()
            xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
            for e in xs:
                for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
                    assert key in e
            root_ev = max(xs, key=lambda e: e["dur"])
            root_dur_ms = root_ev["dur"] / 1e3
            assert abs(root_dur_ms - rec.total_ms) < 1.0
            assert root_dur_ms <= measured_ms + 5.0
            # The instrumented phases tile the request: their sum accounts
            # for (almost) all of it and never exceeds it.
            phase_ms = sum(
                s.duration_ms
                for s in rec.spans
                if s.name in ("sched.acquire", "plan")
            )
            assert phase_ms <= rec.total_ms + 1.0
            assert phase_ms >= 0.5 * rec.total_ms, (phase_ms, rec.total_ms)
            return True

        return await _with_client(app, run)

    assert asyncio.run(go())


def test_hetero_engine_trace_covers_dfa_attribution():
    """A traced request under hetero_batch carries its stacked-DFA slot id
    on every decode segment (the hetero-batching attribution unit)."""
    from tests.test_engine import make_engine

    async def go():
        eng = make_engine(hetero_batch=True)
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("plan: compose. JSON:")
            tr = Tracer(enabled=True, sample_rate=1.0)
            root = tr.start_request("/plan")
            with tracing.activate(root):
                res = await eng.generate(prompt, max_new_tokens=16)
            tr.finish(root)
            rec = tr.get(root.trace_id)
            segs = [s for s in rec.spans if s.name == "engine.segment"]
            assert segs
            # Constrained default-grammar rows occupy stacked slot 1
            # (slot 0 is the trivial all-accept DFA).
            assert all(s.attrs["dfa_id"] == 1 for s in segs)
            assert sum(s.attrs["tokens"] for s in segs) == res.generated_tokens
        finally:
            await eng.aclose()

    asyncio.run(go())
