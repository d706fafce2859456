"""HTTP API surface (aiohttp) — wire-compatible with the reference.

Endpoints (reference ``control_plane.py:133-151``):
  POST /plan              {"intent": str} -> {"graph": {...}, "explanation", ...}
  POST /execute           {"graph": {...}, "payload": {...}} -> {"results", "errors", ...}
  POST /plan_and_execute  {"intent": str, "payload": {...}} -> plan + execution

plus the subsystems the reference only advertises:
  GET  /metrics    Prometheus text exposition (README.md:43-44, made real)
  GET  /costs      per-executable XLA cost accounting + compile sentinel +
                   device peaks/HBM stats (mcpx/telemetry/costs.py)
  GET  /healthz    liveness + engine readiness, and where start-up stands
                   (``startup``: the phases, the open one, the compile cache)
  GET  /traces/startup   the start-up timeline as a trace (``?format=chrome``)
  GET  /telemetry  per-service rolling stats snapshot
  GET/POST /services, GET/DELETE /services/{name}   registry CRUD
             (the reference has no registration API at all, README.md:86)
  POST /profile/start, /profile/stop   jax.profiler device-trace capture

Handlers are thin JSON shims over ``ControlPlane``; every request gets a
trace ID and latency metrics. Fully async — planning never blocks the event
loop (reference bug B6).
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Any

from aiohttp import web

from mcpx.core.dag import Plan, PlanValidationError
from mcpx.core.errors import PlannerError, RegistryError
from mcpx.registry.base import ServiceRecord
from mcpx.scheduler import ShedError
from mcpx.server.control import ControlPlane
from mcpx.telemetry import ledger as ledger_mod
from mcpx.telemetry import metrics as metrics_mod
from mcpx.telemetry import provenance
from mcpx.telemetry import tracing

log = logging.getLogger("mcpx.server")


def _json_error(
    status: int, message: str, *, headers: Any = None, **extra: Any
) -> web.Response:
    """Error envelope. Always carries the active trace id (satellite of the
    tracing spine): a user-reported failure line is then greppable straight
    to its trace via GET /traces/{id}."""
    tid = tracing.current_trace_id()
    if tid is not None and "trace_id" not in extra:
        extra["trace_id"] = tid
    return web.json_response({"error": message, **extra}, status=status, headers=headers)


async def _body(request: web.Request) -> dict[str, Any]:
    try:
        obj = await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise web.HTTPBadRequest(
            text=json.dumps({"error": f"invalid JSON body: {e}"}),
            content_type="application/json",
        )
    if not isinstance(obj, dict):
        raise web.HTTPBadRequest(
            text=json.dumps({"error": "request body must be a JSON object"}),
            content_type="application/json",
        )
    return obj


CONTROL_PLANE_KEY: web.AppKey[ControlPlane] = web.AppKey("control_plane", ControlPlane)
TRACE_ID_KEY = "mcpx_trace_id"

# Endpoints subject to the server.max_concurrency admission limit (the
# planning/execution paths; observability and CRUD stay always-available).
# Shared with the flight recorder's latency-quantile derivation.
_LIMITED = metrics_mod.LIMITED_ENDPOINTS

# Observability surfaces are never traced (by route template): a scraper
# polling /metrics or an operator paging through /traces would otherwise
# flush the ring with traces OF the observability itself — and `mcpx trace
# dump`'s "newest trace" would be its own /traces listing.
_UNTRACED = {
    "/metrics", "/costs", "/cache", "/traces", "/traces/startup", "/traces/{trace_id}",
    "/healthz", "/telemetry", "/debug/anomalies",
    "/debug/anomalies/{bundle_id}", "/usage", "/slo", "/cluster",
    "/explain/{trace_id}",
}

# Request key the /plan handler uses to tell the middleware's SLO observe
# about the degradation-ladder verdict when no ledger bill is active.
DEGRADED_KEY = "mcpx_degraded"


def build_app(cp: ControlPlane) -> web.Application:
    metrics = cp.metrics
    server_cfg = cp.config.server
    inflight = {"n": 0}

    def _tenant_of(request: web.Request) -> str:
        """Cache-governance tenant when no scheduler grant carries one:
        the scheduler-config tenant header directly (same name either
        way, so enabling the scheduler never changes a client's identity
        contract). Absent header = single-tenant "default"."""
        return request.headers.get(cp.config.scheduler.tenant_header) or "default"

    @web.middleware
    async def observability(request: web.Request, handler) -> web.StreamResponse:
        """Every request: root tracing span (W3C ``traceparent`` in/out),
        trace ID, latency histogram (+ exemplar trace id), request counter,
        admission control (429) and a hard request timeout (504)."""
        from mcpx.core.trace import new_trace_id

        # Label by route template, not raw path: bounded metric cardinality.
        resource = getattr(request.match_info.route, "resource", None)
        endpoint = resource.canonical if resource is not None else "unmatched"
        # Read per-request so a tracer can be attached/detached on a LIVE
        # server.
        tracer = cp.tracer
        root = (
            tracer.start_request(
                endpoint,
                traceparent=request.headers.get("traceparent"),
                method=request.method,
            )
            if endpoint not in _UNTRACED
            else None
        )
        trace_id = root.record.trace_id if root is not None else new_trace_id()
        request[TRACE_ID_KEY] = trace_id
        t0 = time.monotonic()
        limited_path = request.path in _LIMITED
        # Cost ledger (mcpx/telemetry/ledger.py): one bill per serving-path
        # request while the ledger is attached (read per-request so it
        # can be attached/detached live, like the tracer). The bill rides a
        # contextvar through the handler's task; scheduler/engine/executor
        # items fold in along the way, and the finalize below rolls it
        # into the per-tenant usage ledger + the root span.
        ledger = cp.ledger
        bill = bill_token = None
        if ledger is not None and limited_path:
            bill = ledger_mod.RequestBill(
                tenant=_tenant_of(request), endpoint=endpoint, t0=t0
            )
            bill_token = ledger_mod.activate(bill)
        # Decision-provenance trail (mcpx/telemetry/provenance.py): rides
        # the same contextvar pattern as the ledger bill. begin() is a
        # no-op returning None while the recorder is disabled (the
        # default), so the off path stays byte-identical pass-through.
        prov_token = (
            provenance.begin(cp.provenance)
            if root is not None and limited_path
            else None
        )
        status = "error"
        # HTTP status class for tail sampling: only SERVER faults (5xx /
        # timeout) are always-kept — a bot scan of 404s or a stream of
        # malformed 400s must not flush the ring of the rare 5xx/SLO
        # traces keep_errors exists to preserve.
        http_status = 500
        limited = limited_path
        try:
            with tracing.activate(root):
                if limited and inflight["n"] >= server_cfg.max_concurrency:
                    status = "throttled"
                    http_status = 429
                    return _json_error(
                        429, "server at max concurrency, retry later"
                    )
                if limited:
                    inflight["n"] += 1
                try:
                    resp = await asyncio.wait_for(
                        handler(request), timeout=server_cfg.request_timeout_s
                    )
                except asyncio.TimeoutError:
                    status = "timeout"
                    http_status = 504
                    return _json_error(
                        504, f"request exceeded {server_cfg.request_timeout_s}s"
                    )
                except web.HTTPException as he:
                    status = "ok" if he.status < 400 else "error"
                    http_status = he.status
                    raise
                except Exception as e:  # noqa: BLE001 - errors must be JSON, never HTML
                    status = "error"
                    http_status = 500
                    log.exception("unhandled error on %s", endpoint)
                    return _json_error(500, f"{type(e).__name__}: {e}")
                finally:
                    if limited:
                        inflight["n"] -= 1  # mcpx: ignore[async-shared-mutation] - balanced dec of the inc above; int ops don't yield, so no lost update on one loop
                status = "ok" if resp.status < 400 else "error"
                http_status = resp.status
                resp.headers["X-Trace-Id"] = trace_id
                if root is not None:
                    resp.headers["traceparent"] = tracing.format_traceparent(root)
                return resp
        finally:
            provenance.end(prov_token)
            if root is not None:
                root.set(status=status)
            elapsed_s = time.monotonic() - t0  # mcpx: ignore[span-across-await-blocking] - the latency metric must exist when tracing is disabled or the trace unsampled
            if bill is not None:
                ledger_mod.deactivate(bill_token)
                bill.finalize(status=status, total_ms=elapsed_s * 1e3)
                if root is not None:
                    # The itemized bill rides the root span (attached
                    # before tracer.finish so retained traces carry it).
                    root.set(bill=bill.to_dict())
                ledger.observe(bill)
            slo = cp.slo
            if slo is not None and limited_path and http_status != 429:
                # SLO error-budget observe (telemetry/slo.py): every
                # SERVED request on the limited endpoints; shed/throttled
                # 429s are excluded — burn must measure served quality,
                # not the load shedder doing its job.
                slo.observe(
                    tenant=(
                        bill.tenant if bill is not None else _tenant_of(request)
                    ),
                    endpoint=endpoint,
                    latency_ms=elapsed_s * 1e3,
                    error=status == "timeout" or http_status >= 500,
                    degraded=(
                        bill.degraded
                        if bill is not None
                        else bool(request.get(DEGRADED_KEY, False))
                    ),
                )
            # Retention decided BEFORE the histogram observation so the
            # exemplar only ever names a trace GET /traces/{id} can serve.
            kept = tracer.finish(
                root, error=status == "timeout" or http_status >= 500
            )
            metrics.requests.labels(endpoint=endpoint, status=status).inc()
            exemplar = (
                {"trace_id": trace_id}
                if kept and cp.config.tracing.exemplars
                else None
            )
            metrics.request_latency.labels(endpoint=endpoint).observe(
                elapsed_s,
                exemplar=exemplar,
            )

    app = web.Application(client_max_size=16 * 1024 * 1024, middlewares=[observability])
    app[CONTROL_PLANE_KEY] = cp

    # ------------------------------------------------------------------ plan
    async def plan(request: web.Request) -> web.Response:
        body = await _body(request)
        intent = body.get("intent")
        if not isinstance(intent, str) or not intent.strip():
            return _json_error(400, "'intent' must be a non-empty string")
        # SLO-aware admission scheduler (mcpx/scheduler/): read per-request
        # so it can be attached/detached on a live server.
        # None = the pre-scheduler pass-through path, byte-identical
        # responses included (no "planner" field).
        sched = cp.scheduler
        slot = None
        if sched is not None:
            ctx = sched.context_from_headers(request.headers)
            with tracing.span(
                "sched.acquire", tenant=ctx.tenant, weight=ctx.weight
            ) as ssp:
                try:
                    slot = await sched.acquire(ctx)
                except ShedError as e:
                    # The shed verdict is trace data too: a 429'd caller's
                    # trace must say WHICH gate refused (rate/queue/deadline).
                    if ssp is not None:
                        ssp.set(verdict=e.outcome, retry_after_s=e.retry_after_s)
                    provenance.emit(
                        "sched",
                        f"shed ({e.outcome})",
                        signals={"retry_after_s": e.retry_after_s},
                        tenant=ctx.tenant,
                        weight=ctx.weight,
                    )
                    return _json_error(
                        429,
                        f"admission refused: {e}",
                        retry_after_s=e.retry_after_s,
                        headers={"Retry-After": e.retry_after_header()},
                    )
                if ssp is not None:
                    # Queue wait + the degradation-ladder decision taken at
                    # grant time (primary vs shortlist-planner tier).
                    ssp.set(
                        verdict="degraded" if slot.degraded else "admitted",
                        queue_wait_ms=round(slot.queue_wait_s * 1e3, 3),
                    )
                provenance.emit(
                    "sched",
                    (
                        "admitted to degraded tier (shortlist planner)"
                        if slot.degraded
                        else "admitted (primary tier)"
                    ),
                    alternatives=["admitted", "degraded", "shed"],
                    signals={
                        "queue_wait_ms": round(slot.queue_wait_s * 1e3, 3)
                    },
                    tenant=slot.ctx.tenant,
                    weight=ctx.weight,
                )
        bill = ledger_mod.current_bill()
        if slot is not None:
            if bill is not None:
                # Scheduler queue wait + the grant's identity/tier become
                # bill items (the grant's tenant wins over the raw header:
                # it is what every downstream quota charges).
                bill.sched_queue_ms += slot.queue_wait_s * 1e3
                bill.tenant = slot.ctx.tenant
                bill.degraded = slot.degraded
            if slot.degraded:
                # SLO plan-quality observe needs the verdict even when no
                # ledger is attached.
                request[DEGRADED_KEY] = True
        # Engine wall before/after the plan call: the difference between
        # the control plane's plan latency and what the engine billed is
        # the planner's own overhead (retrieval, grammar, prompt render).
        eng0 = bill.engine_wall_ms() if bill is not None else 0.0
        try:
            p, latency_ms = await cp.plan(
                intent,
                degraded=slot.degraded if slot is not None else False,
                # The scheduler grant's EDF deadline rides to the engine so
                # prefix-locality admission never regroups a request whose
                # deadline can't afford the wait (scheduler/locality.py).
                deadline_at=slot.ctx.deadline_at if slot is not None else None,
                # Cache-governance identity: the grant's tenant, or the
                # tenant header directly when no scheduler is attached —
                # the engine's cache governor charges radix-tree KV
                # residency to it (engine/cache_governor.py).
                tenant=(
                    slot.ctx.tenant
                    if slot is not None
                    else _tenant_of(request)
                ),
            )
        except PlannerError as e:
            return _json_error(422, f"planning failed: {e}")
        finally:
            if slot is not None:
                sched.release(slot)
        if bill is not None:
            bill.note_plan(latency_ms, bill.engine_wall_ms() - eng0)
            bill.origin = p.origin or ""
        resp = {
            "graph": p.to_wire(),
            "explanation": p.explanation,
            # Which planner authored the plan ("llm" | "heuristic" | ...):
            # lets clients/benchmarks attribute accept rate per request.
            "origin": p.origin,
            "latency_ms": round(latency_ms, 3),
        }
        if slot is not None:
            # Which serving tier the degradation ladder picked: "primary" =
            # the configured planner, "degraded" = routed to the shortlist
            # planner under sustained overload.
            resp["planner"] = "degraded" if slot.degraded else "primary"
        return web.json_response(resp)

    # --------------------------------------------------------------- execute
    async def execute(request: web.Request) -> web.Response:
        body = await _body(request)
        graph = body.get("graph")
        payload = body.get("payload", {})
        if payload is None:
            payload = {}
        if not isinstance(graph, dict):
            return _json_error(400, "'graph' must be an object")
        if not isinstance(payload, dict):
            return _json_error(400, "'payload' must be an object")
        try:
            plan_obj = Plan.from_wire(graph)
        except PlanValidationError as e:
            return _json_error(422, "invalid graph", problems=e.problems)
        # Deadline-budget propagation (mcpx/resilience/): the deadline
        # header becomes the request's attempt budget. Read per-request and
        # only while resilience is wired — with ResilienceConfig disabled
        # the header is not even parsed and this path is byte-identical to
        # the pre-resilience pass-through.
        deadline_ms = None
        if cp.orchestrator.resilience is not None:
            raw = request.headers.get(cp.config.resilience.deadline_header)
            if raw:
                try:
                    deadline_ms = float(raw)
                except ValueError:
                    pass  # scheduling hints never 400 a valid graph
        bill = ledger_mod.current_bill()
        t_ex = time.monotonic() if bill is not None else 0.0
        result = await cp.execute(plan_obj, payload, deadline_ms=deadline_ms)
        if bill is not None:
            # Tool-execution bill items: the DAG wall plus attempt counts
            # by kind from the execution trace.
            bill.add_tools(
                result.trace.to_dict() if result.trace else None,
                (time.monotonic() - t_ex) * 1e3,
            )
        return web.json_response(result.to_dict())

    # ------------------------------------------------------ plan_and_execute
    async def plan_and_execute(request: web.Request) -> web.Response:
        body = await _body(request)
        intent = body.get("intent")
        payload = body.get("payload", {})
        if payload is None:
            payload = {}
        if not isinstance(intent, str) or not intent.strip():
            return _json_error(400, "'intent' must be a non-empty string")
        if not isinstance(payload, dict):
            return _json_error(400, "'payload' must be an object")
        bill = ledger_mod.current_bill()
        eng0 = bill.engine_wall_ms() if bill is not None else 0.0
        t_ex = time.monotonic() if bill is not None else 0.0
        try:
            out = await cp.plan_and_execute(
                intent, payload, tenant=_tenant_of(request)
            )
        except PlannerError as e:
            return _json_error(422, f"planning failed: {e}")
        if bill is not None:
            # Plan+execute is one structured program: the engine items
            # folded in during planning/replanning; everything else (tool
            # attempts, replan overhead) lands in the tool item, with
            # attempt counts from the execution trace.
            bill.origin = str(out.get("origin") or "")
            wall_ms = (time.monotonic() - t_ex) * 1e3
            eng_delta = bill.engine_wall_ms() - eng0
            bill.add_tools(out.get("trace"), max(0.0, wall_ms - eng_delta))
        return web.json_response(out)

    # -------------------------------------------------------------- registry
    async def list_services(request: web.Request) -> web.Response:
        records = await cp.registry.list_services()
        return web.json_response(
            {"services": [r.to_dict() for r in records], "version": await cp.registry.version()}
        )

    async def register_service(request: web.Request) -> web.Response:
        body = await _body(request)
        try:
            record = ServiceRecord.from_dict(body)
        except RegistryError as e:
            return _json_error(400, str(e))
        await cp.registry.put(record)
        return web.json_response({"registered": record.name}, status=201)

    async def get_service(request: web.Request) -> web.Response:
        record = await cp.registry.get(request.match_info["name"])
        if record is None:
            return _json_error(404, f"no such service '{request.match_info['name']}'")
        return web.json_response(record.to_dict())

    async def delete_service(request: web.Request) -> web.Response:
        existed = await cp.registry.delete(request.match_info["name"])
        if not existed:
            return _json_error(404, f"no such service '{request.match_info['name']}'")
        return web.json_response({"deleted": request.match_info["name"]})

    # --------------------------------------------------------- observability
    async def metrics_handler(request: web.Request) -> web.Response:
        # HBM pressure gauges refresh at scrape time. Gated on engine
        # READINESS, not presence: a heuristic-only server must not
        # initialise jax to serve its own metrics, and while an engine is
        # cold or warming its worker is loading and compiling on the
        # device — the scrape waits until it is ready, when
        # memory_stats() is a cheap C call.
        engine = getattr(cp.planner, "engine", None)
        if engine is not None and getattr(engine, "state", None) == "ready":
            from mcpx.telemetry.costs import update_hbm_gauges

            update_hbm_gauges(cp.metrics)
        if cp.slo is not None:
            # mcpx_slo_* gauges refresh at scrape time, like the HBM
            # pressure gauges (cheap dict math over the bucket rings).
            cp.slo.update_gauges(cp.metrics)
        # OpenMetrics on request (Accept negotiation): the exposition that
        # renders the exemplar trace ids the latency histograms carry —
        # a latency spike links to a concrete GET /traces/{id} trace.
        if "application/openmetrics-text" in request.headers.get("Accept", ""):
            from prometheus_client.openmetrics.exposition import (
                CONTENT_TYPE_LATEST as OPENMETRICS_CONTENT_TYPE,
            )

            return web.Response(
                body=cp.metrics.render(openmetrics=True),
                headers={"Content-Type": OPENMETRICS_CONTENT_TYPE},
            )
        return web.Response(body=cp.metrics.render(), content_type="text/plain", charset="utf-8")

    async def traces_handler(request: web.Request) -> web.Response:
        """Retained trace summaries, newest first (ring-buffer contents:
        head-sampled + always-kept error/SLO-breach traces)."""
        return web.json_response(
            {"traces": [r.summary() for r in cp.tracer.traces()]}
        )

    async def trace_get(request: web.Request) -> web.Response:
        tid = request.match_info["trace_id"]
        rec = cp.tracer.get(tid)
        if rec is None:
            return _json_error(
                404, f"no trace '{tid}' (evicted, unsampled, or never existed)"
            )
        if request.query.get("format") == "chrome":
            # Chrome trace-event JSON: loads directly in Perfetto /
            # chrome://tracing (docs/observability.md; `mcpx trace dump`).
            return web.json_response(rec.to_chrome())
        return web.json_response(rec.to_dict())

    async def trace_startup(request: web.Request) -> web.Response:
        """The engine's start-up timeline (mcpx/telemetry/startup.py), in a
        request trace's two formats. Kept outside the sampled ring: never
        evicted, and there with tracing off."""
        timeline = getattr(getattr(cp.planner, "engine", None), "startup", None)
        if timeline is None:
            return _json_error(404, "no engine, so no start-up timeline")
        return web.json_response(timeline.trace(chrome=request.query.get("format") == "chrome"))

    async def explain_handler(request: web.Request) -> web.Response:
        """Decision-provenance explanation for one retained trace
        (mcpx/telemetry/provenance.py, docs/observability.md): the
        ``decision.*`` spans a request's consequential choice points
        emitted, re-rendered as structured JSON plus a human-readable
        narrative — admission verdict, plan origin with retrieval scores,
        routing winner with per-policy contributions, resilience events,
        replans, prefix-cache outcomes, in request order. Works on any
        retained trace; a trace recorded while provenance was disabled
        answers with an empty decision list and says so in the narrative."""
        tid = request.match_info["trace_id"]
        rec = cp.tracer.get(tid)
        if rec is None:
            return _json_error(
                404, f"no trace '{tid}' (evicted, unsampled, or never existed)"
            )
        return web.json_response(provenance.build_explanation(rec))

    async def costs_handler(request: web.Request) -> web.Response:
        """Roofline cost observatory (mcpx/telemetry/costs.py,
        docs/observability.md): per-executable XLA cost_analysis table +
        compile counts (the retrace sentinel's raw data), device peaks and
        per-device HBM stats. Engine-gated like the HBM gauges above."""
        engine = getattr(cp.planner, "engine", None)
        if engine is None or getattr(engine, "costs", None) is None:
            return web.json_response(
                {
                    "engine": None,
                    "device": None,
                    "reason": "no inference engine attached "
                    "(heuristic/mock planner serves this control plane)",
                }
            )
        if engine.state != "ready":
            # Cold/warming engine: the compile history so far is readable
            # (materialize=False — no lazy AOT compiles), but device
            # queries are deferred until the worker has finished start-up.
            return web.json_response(
                {
                    "engine": engine.costs.snapshot(materialize=False),
                    "engine_state": engine.state,
                    # Per-path ragged-kernel engagement (route resolved at
                    # engine construction, so even a warming engine answers).
                    "pallas": engine.pallas_paths(),
                    "device": None,
                    "reason": "engine not ready; device stats deferred",
                }
            )
        from mcpx.telemetry.costs import device_peaks, hbm_stats, model_cost, update_hbm_gauges

        # Off the event loop: materialising pending cost entries lazily
        # AOT-compiles (seconds per signature, first scrape only), and the
        # device queries belong with it.
        def _read():
            import jax

            update_hbm_gauges(cp.metrics)
            mesh = getattr(engine, "_mesh", None)
            device = {
                "peaks": device_peaks(),
                "hbm": hbm_stats(),
                # The engine's mesh (axis -> size) and where this process
                # keeps its persistent compilation cache (None = nowhere).
                "mesh": dict(mesh.shape) if mesh is not None else None,
                "compilation_cache_dir": jax.config.jax_compilation_cache_dir,
            }
            return engine.costs.snapshot(), device

        snap, device = await asyncio.to_thread(_read)
        return web.json_response(
            {
                "engine": snap,
                "engine_state": engine.state,
                # Per-path ragged-kernel engagement + dispatch counts
                # (decode / suffix-prefill / spec-verify) with the blocking
                # reason when a path is not kernel-routed — the /costs
                # twin of /healthz's engine_queue.pallas block.
                "pallas": engine.pallas_paths(),
                "device": device,
                # Parameters held against parameters a token reads (an
                # engine pool's replicas each answer for their own).
                "model": model_cost(engine.model_cfg) if hasattr(engine, "model_cfg") else None,
            }
        )

    async def cache_handler(request: web.Request) -> web.Response:
        """Combined cache stats (control-plane plan cache + engine radix
        prefix KV cache): hit rates, resident pages, evictions — the
        operator's one-call view instead of scrape-only counters."""
        return web.json_response(cp.cache_stats())

    async def anomalies_handler(request: web.Request) -> web.Response:
        """Flight recorder status (mcpx/telemetry/flight.py): detector
        states, bundle index, the latest flight snapshot. A disabled
        recorder answers enabled:false rather than 404 so operators can
        tell "off" from "wrong URL"."""
        if cp.flight is None:
            return web.json_response(
                {"enabled": False, "detectors": {}, "bundles": []}
            )
        return web.json_response(cp.flight.status())

    async def anomaly_bundle_handler(request: web.Request) -> web.Response:
        """One diagnostic bundle by id (the full JSON the trip wrote —
        flight window, traces, costs, breakers, log tail). Disk read runs
        off the event loop inside load_bundle."""
        if cp.flight is None:
            return _json_error(404, "flight recorder disabled")
        bid = request.match_info["bundle_id"]
        bundle = await cp.flight.load_bundle(bid)
        if bundle is None:
            return _json_error(404, f"no bundle '{bid}' (pruned or never captured)")
        return web.json_response(bundle)

    async def usage_handler(request: web.Request) -> web.Response:
        """Per-tenant usage ledger (mcpx/telemetry/ledger.py): itemized
        cost aggregates per tenant + the recent-bill ring. A disabled
        ledger answers enabled:false rather than 404 (operators can tell
        "off" from "wrong URL", the /debug/anomalies convention)."""
        if cp.ledger is None:
            return web.json_response({"enabled": False})
        return web.json_response(cp.ledger.snapshot())

    async def slo_handler(request: web.Request) -> web.Response:
        """SLO error-budget state (mcpx/telemetry/slo.py): per-objective
        burn rates over every window, budget remaining, global + per
        tenant — and a gauge refresh so /metrics agrees with what this
        endpoint just served."""
        if cp.slo is None:
            return web.json_response({"enabled": False})
        cp.slo.update_gauges(cp.metrics)
        return web.json_response(cp.slo.status())

    async def telemetry_handler(request: web.Request) -> web.Response:
        return web.json_response(
            {name: s.to_dict() for name, s in cp.telemetry.snapshot().items()}
        )

    async def healthz(request: web.Request) -> web.Response:
        engine = getattr(cp.planner, "engine", None)
        engine_state = getattr(engine, "state", "n/a") if engine is not None else "n/a"
        from mcpx.server.control import _mcpx_version

        # Build identity (ISSUE 14 satellite): liveness probes and bundle
        # consumers attribute this serving process to a concrete build —
        # the same version label mcpx_build_info carries.
        body: dict[str, Any] = {
            "status": "ok",
            "version": _mcpx_version(),
            "engine": engine_state,
            # startup() done: engine ready and registry grammar warmed, so
            # no compile is left on the serving path.
            "started": cp.started,
        }
        timeline = getattr(engine, "startup", None)
        if timeline is not None:
            # Where start-up stands or stood (mcpx/telemetry/startup.py):
            # present from the first phase on, so an operator watching a cold
            # start's "warming" sees WHICH phase is open and what the compile
            # cache held.
            body["startup"] = timeline.snapshot()
        if engine_state == "ready":
            # Engine load snapshot (the scheduler's queue_stats() feed):
            # occupancy, per-class backlog, head-of-line age and resident
            # grammar count — a remote operator's one-call view of whether
            # the slab is starving a traffic class, without Prometheus.
            # float()/int() also strip numpy scalar types (service_ewma_s is
            # an np.float64), which json.dumps would reject. Nested blocks
            # (the per-path "pallas" report, worker_profile while a
            # profiler is attached) are plain JSON-native dicts already —
            # pass them through untouched.
            body["engine_queue"] = {
                k: (
                    v
                    if isinstance(v, dict)
                    else round(float(v), 3) if isinstance(v, float) else int(v)
                )
                for k, v in engine.queue_stats().items()
            }
        # Surface the startup failure cause: a remote operator (or the chip
        # benchmark's failure line) must be able to see WHY the engine is down without
        # shell access to the server's stderr — e.g. a device OOM string.
        err = getattr(engine, "_startup_error", None) if engine is not None else None
        if err is not None:
            body["engine_error"] = f"{type(err).__name__}: {err}"
        # A failed registry-grammar warm leaves a healthy engine serving
        # (the first plan pays the compile): its own field, not the
        # engine's.
        if cp.warm_error is not None:
            body["warm_error"] = f"{type(cp.warm_error).__name__}: {cp.warm_error}"
        return web.json_response(body)

    # Device-side profiling (SURVEY.md §5 tracing): capture a jax.profiler
    # trace of live serving (prefill/decode/collectives) for TensorBoard /
    # Perfetto, without restarting the server.
    # profile["dir"]: None = idle, _STARTING/_STOPPING = a trace transition
    # in flight (a reservation no other handler may touch), any other str =
    # active trace directory.
    _STARTING = "<starting>"
    _STOPPING = "<stopping>"
    profile = {"dir": None, "session": None}

    async def profile_start(request: web.Request) -> web.Response:
        body = await _body(request) if request.can_read_body else {}
        if profile["dir"] is not None:
            return _json_error(409, f"profiling already active (dir={profile['dir']})")
        trace_dir = body.get("dir") or server_cfg.profile_dir
        if not isinstance(trace_dir, str) or not trace_dir:
            return _json_error(400, "'dir' must be a non-empty string")
        try:
            import jax  # noqa: F401 - absent = 501, before any state changes
        except ImportError:
            return _json_error(501, "jax unavailable; device profiling disabled")
        from mcpx.telemetry import device_trace
        # Reserve BEFORE the await: a concurrent start arriving while this
        # one is mid-await must hit the already-active 409 above, and a
        # concurrent STOP must see the _STARTING sentinel and back off —
        # neither may race jax's single-session profiler state.
        profile["dir"] = _STARTING
        started = False
        try:
            profile["session"] = await asyncio.to_thread(device_trace.start)
            started = True
        except Exception as e:  # mcpx: ignore[broad-except] - profiler state errors -> client as 409
            return _json_error(409, f"could not start trace: {e}")
        finally:
            # ALWAYS resolves the reservation — including cancellation mid-
            # await (CancelledError skips except Exception), which would
            # otherwise leak the sentinel and wedge both endpoints forever.
            profile["dir"] = trace_dir if started else None  # mcpx: ignore[async-shared-mutation] - resolving this handler's own reservation; racers were 409'd by it
        return web.json_response({"profiling": "started", "dir": trace_dir})

    async def profile_stop(request: web.Request) -> web.Response:
        if profile["dir"] is None:
            return _json_error(409, "profiling not active")
        if profile["dir"] in (_STARTING, _STOPPING):
            # A start or stop is still in flight in a worker thread:
            # dispatching a stop now would race it inside the profiler's
            # single-session state.
            return _json_error(409, "profiler transition in progress; retry")
        from mcpx.telemetry import device_trace

        # Reserve: concurrent stops (and starts) 409 on the sentinel above
        # instead of racing the in-flight stop below.
        trace_dir, profile["dir"] = profile["dir"], _STOPPING
        stopped = False
        try:
            # Off the event loop: collecting the capture costs ~25 us a
            # device event, tens of seconds under real decode traffic.
            await asyncio.to_thread(device_trace.stop, profile["session"], trace_dir)
            stopped = True
        except Exception as e:  # mcpx: ignore[broad-except] - error -> client as 500
            return _json_error(500, f"could not stop trace: {e}")
        finally:
            # ALWAYS resolves the reservation (cancellation included). On
            # failure restore the active state: jax's session is unknown,
            # and dropping it would wedge both endpoints behind 409s.
            profile["dir"] = None if stopped else trace_dir  # mcpx: ignore[async-shared-mutation] - resolving this handler's own reservation; racers were 409'd by it
            if stopped:
                profile["session"] = None
        return web.json_response({"profiling": "stopped", "dir": trace_dir})

    app.router.add_post("/plan", plan)
    app.router.add_post("/execute", execute)
    app.router.add_post("/plan_and_execute", plan_and_execute)
    app.router.add_get("/services", list_services)
    app.router.add_post("/services", register_service)
    app.router.add_get("/services/{name}", get_service)
    app.router.add_delete("/services/{name}", delete_service)
    app.router.add_get("/metrics", metrics_handler)
    app.router.add_get("/costs", costs_handler)
    app.router.add_get("/cache", cache_handler)
    app.router.add_get("/traces", traces_handler)
    app.router.add_get("/traces/startup", trace_startup)  # before the template
    app.router.add_get("/traces/{trace_id}", trace_get)
    app.router.add_get("/explain/{trace_id}", explain_handler)
    app.router.add_get("/debug/anomalies", anomalies_handler)
    app.router.add_get("/debug/anomalies/{bundle_id}", anomaly_bundle_handler)
    async def cluster_handler(request: web.Request) -> web.Response:
        """Replica-pool scoreboard (mcpx/cluster/, docs/cluster.md):
        per-replica lifecycle/depth/ETA/error-rate rows, routing tallies,
        the bounded recent-decision ring (entries carry trace ids) and
        the routing/failover journal. Disabled-subsystem convention:
        {"enabled": false}, not a 404 (same as /usage and /slo)."""
        pool = getattr(cp, "cluster", None)
        if pool is None:
            return web.json_response({"enabled": False})
        return web.json_response(pool.scoreboard_snapshot())

    app.router.add_get("/usage", usage_handler)
    app.router.add_get("/slo", slo_handler)
    app.router.add_get("/cluster", cluster_handler)
    app.router.add_get("/telemetry", telemetry_handler)
    app.router.add_get("/healthz", healthz)
    app.router.add_post("/profile/start", profile_start)
    app.router.add_post("/profile/stop", profile_stop)

    startup_task: dict[str, asyncio.Task] = {}

    async def _mirror_loop() -> None:
        # Telemetry Redis mirror (reference README.md:43-44 made real):
        # periodic export of local stats + import of peer replicas'.
        interval = cp.config.telemetry.mirror_interval_s
        while True:
            try:
                await cp.telemetry_mirror.sync()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - mirror loss must not kill serving
                log.exception("telemetry mirror sync failed; retrying next interval")
            await asyncio.sleep(interval)

    async def on_startup(app: web.Application) -> None:
        # Engine bring-up (weight load + bucket compile warmup) runs as a
        # background task, not inline: on_startup fires before the listening
        # socket binds, so awaiting a minutes-long TPU warmup here would
        # leave /healthz connection-refused the whole time (liveness probes
        # would restart-loop the pod). Requests that arrive while warming
        # wait inside engine.start(), which coalesces concurrent callers
        # (SURVEY.md §3.4: startup is a first-class, observable phase).
        startup_task["t"] = asyncio.create_task(cp.startup())
        if cp.telemetry_mirror is not None:
            startup_task["mirror"] = asyncio.create_task(_mirror_loop())
        if cp.flight is not None:
            # Flight-recorder sampling loop: ~1 Hz snapshot of signals the
            # stack already exposes; bundle writes happen off the loop
            # inside the recorder (asyncio.to_thread).
            startup_task["flight"] = asyncio.create_task(cp.flight.run())
        if getattr(cp, "cluster", None) is not None:
            # Cluster scoreboard refresh: per-replica health pulled OFF the
            # request path (routing scores read the cached snapshots).
            startup_task["cluster"] = asyncio.create_task(
                cp.cluster.run_scoreboard()
            )

    app.on_startup.append(on_startup)

    async def on_cleanup(app: web.Application) -> None:
        cl = startup_task.pop("cluster", None)
        if cl is not None:
            cl.cancel()
            try:
                await cl
            except asyncio.CancelledError:
                pass  # the cancel above landing, not a failure
            except Exception:
                log.exception("cluster scoreboard loop died with an error")
        fl = startup_task.pop("flight", None)
        if fl is not None:
            fl.cancel()
            try:
                await fl
            except asyncio.CancelledError:
                pass  # the cancel above landing, not a failure
            except Exception:
                log.exception("flight recorder loop died with an error")
        m = startup_task.pop("mirror", None)
        if m is not None:
            m.cancel()
            try:
                await m
            except asyncio.CancelledError:
                pass  # the cancel above landing, not a failure
            except Exception:
                log.exception("telemetry mirror loop died with an error")
            try:
                await cp.telemetry_mirror.aclose()
            except Exception:  # broad: best-effort at shutdown, and logged
                log.exception("telemetry mirror close failed")
        t = startup_task.pop("t", None)
        if t is not None:
            if not t.done():
                t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass  # shutdown raced a still-warming engine; expected
            except Exception:
                # Startup failures already surface via engine.state and
                # /healthz; debug-log so shutdown stays quiet but traceable.
                log.debug("engine startup task ended with an error", exc_info=True)
        if profile["dir"] in (_STARTING, _STOPPING):
            # Shutdown raced an in-flight profiler transition: stopping
            # concurrently would race that thread (an in-flight stop is
            # already flushing the capture; an in-flight start has nothing
            # to flush yet).
            log.warning("shutdown during profiler transition; skipping flush")
            profile["dir"] = None
        if profile["dir"] is not None:
            # stop is what flushes the capture to disk; without this a
            # trace active at shutdown would vanish silently.
            from mcpx.telemetry import device_trace

            try:
                await asyncio.to_thread(
                    device_trace.stop, profile["session"], profile["dir"]
                )
            except Exception:  # broad: best-effort at shutdown, and logged
                log.exception("failed to flush active profiler trace")
            profile["dir"] = None  # mcpx: ignore[async-shared-mutation] - shutdown path; no handler can race on_cleanup
        await cp.orchestrator.aclose()
        engine = getattr(cp.planner, "engine", None)
        if engine is not None and engine.state in ("ready", "warming"):
            await engine.aclose()

    app.on_cleanup.append(on_cleanup)
    return app
