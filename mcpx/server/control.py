"""ControlPlane: the use-case layer tying planner, orchestrator, retrieval
and telemetry together, independent of HTTP.

This is the testable core behind the API surface (the reference fuses this
into FastAPI handlers over module singletons, ``control_plane.py:133-151``).
Includes the replan loop (baseline config 4) and an LRU plan cache keyed by
(intent, registry version) — a large plans/sec lever given immutable
registries (SURVEY.md §5 checkpoint/resume).
"""

from __future__ import annotations

import contextlib
import logging
import time
from collections import OrderedDict
from typing import Any, Optional

from mcpx.core.config import MCPXConfig
from mcpx.core.dag import Plan
from mcpx.core.trace import ExecutionTrace
from mcpx.orchestrator.executor import ExecuteResult, Orchestrator
from mcpx.planner.base import PlanContext, Planner
from mcpx.planner.heuristic import HeuristicPlanner
from mcpx.registry.base import RegistryBackend
from mcpx.telemetry import provenance, tracing
from mcpx.telemetry.metrics import Metrics
from mcpx.telemetry.replan import ReplanPolicy
from mcpx.telemetry.stats import TelemetryStore

log = logging.getLogger("mcpx.control")


def _mcpx_version() -> str:
    import mcpx

    return getattr(mcpx, "__version__", "unknown")


def _jax_version() -> str:
    """jax's installed version WITHOUT importing it (package metadata):
    build identity must not initialise the JAX runtime on heuristic-only
    servers."""
    try:
        from importlib.metadata import version

        return version("jax")
    except Exception:  # mcpx: ignore[broad-except] - build identity is best-effort metadata, never a startup failure
        return "unknown"


def _backend_label(config: MCPXConfig) -> str:
    """The build-identity backend label at CONSTRUCTION: "none" without an
    engine, else "starting". The label is what jax reports, not a guess
    from the environment, and an engine may not have asked jax yet (only a
    ``use_pallas`` engine checks the backend when it is built): startup()
    re-stamps it once the engine is ready and can say."""
    return "starting" if config.planner.kind == "llm" else "none"


class ControlPlane:
    def __init__(
        self,
        *,
        config: Optional[MCPXConfig] = None,
        registry: RegistryBackend,
        planner: Planner,
        orchestrator: Orchestrator,
        telemetry: Optional[TelemetryStore] = None,
        metrics: Optional[Metrics] = None,
        retriever: Any = None,  # mcpx.retrieval.Index (duck-typed: async shortlist(intent, k))
        replan_policy: Optional[ReplanPolicy] = None,
        telemetry_mirror: Any = None,  # mcpx.telemetry.mirror.RedisTelemetryMirror
        redis_plan_cache: Any = None,  # mcpx.server.plan_cache.RedisPlanCache
        scheduler: Any = None,  # mcpx.scheduler.Scheduler (None = pass-through)
        tracer: Any = None,  # mcpx.telemetry.tracing.Tracer (None = built from config)
    ) -> None:
        self.config = config or MCPXConfig()
        self.registry = registry
        self.planner = planner
        self.orchestrator = orchestrator
        self.telemetry = telemetry or TelemetryStore(self.config.telemetry.ewma_alpha)
        self.metrics = metrics or Metrics()
        self.retriever = retriever
        self.replan_policy = replan_policy or ReplanPolicy(self.config.telemetry)
        self.telemetry_mirror = telemetry_mirror
        self.redis_plan_cache = redis_plan_cache
        # SLO-aware admission scheduler (mcpx/scheduler/). Read per-request
        # by the /plan handler, so it can be attached/detached at runtime.
        self.scheduler = scheduler
        # Request-tracing spine (mcpx/telemetry/tracing.py). Read per-request
        # by the server middleware so it can be attached/detached on a live
        # server.
        if tracer is None:
            from mcpx.telemetry.tracing import Tracer

            tracer = Tracer(self.config.tracing)
        self.tracer = tracer
        # Per-request cost ledger + per-tenant usage attribution
        # (mcpx/telemetry/ledger.py) and the SLO error-budget engine
        # (mcpx/telemetry/slo.py). Both None while disabled — the serving
        # path then carries no bill and no SLO observe. Read per-request
        # by the middleware so they can be attached/detached on a live
        # server, like the tracer and the scheduler.
        from mcpx.telemetry.ledger import build_ledger
        from mcpx.telemetry.slo import build_slo_tracker

        self.ledger = build_ledger(self.config, self.metrics)
        self.slo = build_slo_tracker(self.config)
        if (
            self.scheduler is not None
            and self.slo is not None
            and self.config.scheduler.burn_aware
        ):
            # Burn-aware degradation (config-gated): the ladder consults
            # the error-budget engine's global fast-burn state, so
            # overload sheds burn-aware instead of blind.
            attach = getattr(self.scheduler, "attach_slo", None)
            if attach is not None:
                attach(self.slo.burning)
        # Build identity (ISSUE 14 satellite): stamp mcpx_build_info so
        # every scrape/bundle/usage report names the serving build. jax's
        # version comes from package metadata — never an import, which
        # would pull the whole runtime into heuristic-only servers.
        self.metrics.set_build_info(
            version=_mcpx_version(),
            jax=_jax_version(),
            backend=_backend_label(self.config),
        )
        # Cluster pool (mcpx/cluster/): present iff the factory wrapped the
        # planner's engine in an EnginePool. The pool's burn-aware placement
        # reads the ledger/SLO built just above — they don't exist yet when
        # the factory constructs the pool, so the signals late-bind here.
        _eng = getattr(self.planner, "engine", None)
        self.cluster = _eng if hasattr(_eng, "scoreboard_snapshot") else None
        if self.cluster is not None:
            self.cluster.attach_signals(slo=self.slo, ledger=self.ledger)
        # Flight recorder & anomaly observatory (mcpx/telemetry/flight.py):
        # the always-on telemetry timeseries + SPC detectors + diagnostic
        # bundles. None while telemetry.flight.enabled=false — the serving
        # path is then byte-identical (no sampling task, no state). Built
        # AFTER the SLO tracker: the recorder's slo_burn detector watches
        # its fast-burn signal.
        from mcpx.telemetry.flight import build_flight_recorder

        self.flight = build_flight_recorder(self)
        # Decision-provenance recorder (mcpx/telemetry/provenance.py):
        # per-request "why" records + GET /explain. None while
        # telemetry.provenance.enabled=false — the middleware then never
        # begins a trail and every emit() stays a no-op (byte-identical
        # pass-through, parity-tested).
        self.provenance = provenance.build_provenance(self)
        # Degradation target: the model-free shortlist planner — it still
        # plans over the retrieval shortlist via _context, so degraded
        # service is the "shortlist planner" tier, not a blind fallback.
        self.degraded_planner = HeuristicPlanner(self.config.planner)
        self._plan_cache: OrderedDict[tuple[str, int], Plan] = OrderedDict()
        self._cache_writes: set = set()  # in-flight shared-tier writes
        # Plain-int plan-cache counters for GET /cache (the Prometheus
        # counters stay the scrape surface; an operator endpoint should
        # not have to parse the exposition text for a hit rate).
        self.plan_cache_stats = {"hits": 0, "redis_hits": 0, "misses": 0}
        # startup() progress for GET /healthz: ``started`` flips once the
        # engine is ready AND the registry grammar is warm (or its warm
        # failed, recorded in ``warm_error``) — "engine: ready" alone is
        # earlier than "no compile left on the serving path".
        self.started = False
        self.warm_error: Optional[BaseException] = None

    # ------------------------------------------------------------- lifecycle
    async def startup(self) -> None:
        """Bring the planner's inference engine up (mesh build, weight load,
        bucket warmup) BEFORE serving traffic. Startup is minutes, not ms,
        on TPU (SURVEY.md §3.4) — it must never hide inside the first
        request, where per-request timeouts would shoot it down."""
        ensure = getattr(self.planner, "ensure_ready", None)
        if ensure is not None:
            await ensure()
            import jax  # the engine already initialised the backend

            self.metrics.build_info.clear()
            self.metrics.set_build_info(
                version=_mcpx_version(),
                jax=_jax_version(),
                backend=jax.default_backend(),
            )
        # The engine's start-up timeline (mcpx/telemetry/startup.py): the
        # registry grammar is the control plane's own phase of it, and
        # ``started`` is where it ends. A replica pool has none of its own.
        timeline = getattr(getattr(self.planner, "engine", None), "startup", None)
        warm = getattr(self.planner, "warm", None)
        if warm is not None:
            phase = (
                timeline.phase("startup.registry_grammar")
                if timeline is not None
                else contextlib.nullcontext()
            )
            with phase as sp:
                try:
                    await warm(self.registry)
                except Exception as e:  # broad: serving continues, /healthz says why
                    # Not fatal — the first plan then pays the compile — but
                    # never quiet: GET /healthz reports it as warm_error, and
                    # the timeline's phase ends failed, with the type.
                    self.warm_error = e
                    if sp is not None:
                        timeline.end(sp, error=e)
                    log.exception(
                        "registry-grammar warmup failed; first plan pays the compile"
                    )
        self.started = True
        if timeline is not None:
            timeline.finish()

    # ------------------------------------------------------------------ plan
    async def plan(
        self,
        intent: str,
        *,
        use_cache: bool = True,
        degraded: bool = False,
        deadline_at: Optional[float] = None,
        tenant: str = "default",
    ) -> tuple[Plan, float]:
        """Plan an intent; returns (plan, latency_ms).

        ``degraded=True`` (scheduler degradation ladder) serves the
        shortlist/heuristic planner instead of the configured one. Cache
        READS stay on — a hit returns a previously LLM-authored plan at
        heuristic cost, the best possible degraded response — but degraded
        plans are never WRITTEN to any cache tier (they would keep serving
        heuristic plans after the ladder recovers). ``deadline_at`` (the
        scheduler grant's EDF deadline, monotonic) rides the PlanContext to
        the engine so prefix-locality admission never regroups a request
        whose deadline can't afford it. ``tenant`` (the scheduler grant's
        tenant, or the tenant header when no scheduler runs) rides the
        PlanContext to the engine's cache governor so radix-tree KV
        insertions are charged to the right weighted-fair quota."""
        t0 = time.monotonic()
        with tracing.span(
            "plan", path="degraded" if degraded else "primary"
        ) as sp:
            version = await self.registry.version()
            key = (intent, version)
            local_tier = self.config.planner.plan_cache_size > 0
            if use_cache and local_tier:
                cached = self._plan_cache.get(key)
                if cached is not None:
                    self._plan_cache.move_to_end(key)
                    self.plan_cache_stats["hits"] += 1
                    self.metrics.plan_cache.labels(result="hit").inc()
                    if sp is not None:
                        sp.set(cache="hit", origin=cached.origin)
                    provenance.emit(
                        "plan", "plan-cache hit (local tier)",
                        origin=cached.origin or "unknown",
                    )
                    return cached, (time.monotonic() - t0) * 1e3  # mcpx: ignore[span-across-await-blocking] - latency_ms is a client response field, served with tracing off too
            if use_cache and self.redis_plan_cache is not None:
                # Second tier: shared across replicas/restarts, independent of
                # the local LRU (plan_cache_size=0 disables only the local
                # tier); a hit here still warms the LRU when enabled.
                shared = await self.redis_plan_cache.get(intent, version)
                if shared is not None:
                    if local_tier:
                        self._cache_put(key, shared)
                    self.plan_cache_stats["redis_hits"] += 1
                    self.metrics.plan_cache.labels(result="redis_hit").inc()
                    if sp is not None:
                        sp.set(cache="redis_hit", origin=shared.origin)
                    provenance.emit(
                        "plan", "plan-cache hit (redis tier)",
                        origin=shared.origin or "unknown",
                    )
                    return shared, (time.monotonic() - t0) * 1e3  # mcpx: ignore[span-across-await-blocking] - latency_ms is a client response field, served with tracing off too
            if use_cache and (local_tier or self.redis_plan_cache is not None):
                self.plan_cache_stats["misses"] += 1
                self.metrics.plan_cache.labels(result="miss").inc()
                if sp is not None:
                    sp.set(cache="miss")

            planner = self.degraded_planner if degraded else self.planner
            if sp is not None:
                sp.set(planner=type(planner).__name__)
            with tracing.span("plan.context"):
                context = await self._context(
                    intent, version=version, deadline_at=deadline_at,
                    tenant=tenant,
                )
            n_spans0 = len(sp.record.spans) if sp is not None else 0
            tier0 = self._tier_counts() if provenance.active() else None
            try:
                plan = await planner.plan(intent, context)
                self.metrics.plans.labels(
                    planner=type(planner).__name__,
                    origin=plan.origin or "unknown",
                    status="ok",
                ).inc()
            except Exception:
                self.metrics.plans.labels(
                    planner=type(planner).__name__, origin="none", status="error"
                ).inc()
                raise
            if sp is not None:
                sp.set(origin=plan.origin or "unknown")
            if provenance.active():
                self._emit_plan_provenance(
                    intent, plan, planner, context, degraded=degraded
                )
                self._emit_prefix_provenance(
                    sp.record.spans[n_spans0:] if sp is not None else [],
                    tier0,
                )
            if use_cache and not degraded and self.config.planner.plan_cache_size > 0:
                self._cache_put(key, plan)
            if use_cache and not degraded and self.redis_plan_cache is not None:
                self._redis_cache_write(intent, version, plan)
            return plan, (time.monotonic() - t0) * 1e3  # mcpx: ignore[span-across-await-blocking] - latency_ms is a client response field, served with tracing off too

    # ------------------------------------------------------------ provenance
    def _emit_plan_provenance(
        self, intent: str, plan: Plan, planner: Any, context: PlanContext,
        *, degraded: bool,
    ) -> None:
        """DecisionRecord for the planner outcome (active trail only):
        origin, grammar mode, the retrieval shortlist that formed the
        planner's universe — with its embedding scores when the retriever
        can produce them (contributions)."""
        scores: dict[str, float] = {}
        sf = getattr(self.retriever, "scores_for", None)
        if sf is not None and context.shortlist:
            try:
                scores = sf(intent, list(context.shortlist))
            except Exception:  # mcpx: ignore[broad-except] - provenance must never fail a plan; the record just loses its scores
                scores = {}
        provenance.emit(
            "plan",
            f"planned via {type(planner).__name__} "
            f"(origin={plan.origin or 'unknown'})",
            alternatives=list(context.shortlist or []),
            contributions=scores,
            origin=plan.origin or "unknown",
            grammar_mode=self.config.planner.constrain_names,
            degraded=degraded,
            shortlist_k=self.config.planner.shortlist_top_k,
            excluded=sorted(context.exclude) if context.exclude else [],
        )

    def _tier_counts(self) -> Optional[dict]:
        """Cumulative KV spill/readmit counts (provenance-only read): the
        plan window's delta attributes tier churn to the request that
        observed it."""
        engine = getattr(self.planner, "engine", None)
        if engine is None or getattr(engine, "state", None) != "ready":
            return None
        try:
            qs = engine.queue_stats()
        except Exception:  # mcpx: ignore[broad-except] - provenance must never fail a plan; the record just loses tier signals
            return None
        return {
            "spills": int(qs.get("prefix_spills", 0)),
            "readmits": int(qs.get("prefix_readmits", 0)),
        }

    def _emit_prefix_provenance(
        self, new_spans: list, tier0: Optional[dict]
    ) -> None:
        """Prefix-cache/tier DecisionRecords from the engine-worker spans
        the plan just added. The worker thread cannot emit (contextvars
        don't cross threads), so the loop re-emits from the span tree
        after generate returns; spill/readmit churn over the plan window
        rides as signals."""
        for s in list(new_spans):
            if s.name != "engine.prefill":
                continue
            a = s.attrs
            if "prefix_matched_tokens" not in a:
                continue
            matched = int(a.get("prefix_matched_tokens", 0))
            provenance.emit(
                "prefix",
                "prefix cache "
                + (f"hit ({matched} tokens)" if a.get("prefix_hit") else "miss"),
                signals={"matched_tokens": matched},
            )
        tier1 = self._tier_counts() if tier0 is not None else None
        if tier0 is not None and tier1 is not None:
            d_spill = tier1["spills"] - tier0["spills"]
            d_readmit = tier1["readmits"] - tier0["readmits"]
            if d_spill > 0 or d_readmit > 0:
                provenance.emit(
                    "prefix",
                    f"kv tier churn during plan window ({d_spill} spill(s), "
                    f"{d_readmit} readmit(s))",
                    signals={"spills": d_spill, "readmits": d_readmit},
                )

    def _redis_cache_write(self, intent: str, version: int, plan: Plan) -> None:
        """Fire-and-forget write to the shared tier: put() swallows its own
        errors, and the plan response must not wait out a slow Redis. The
        task set keeps references so the event loop can't GC in-flight
        writes."""
        import asyncio

        task = asyncio.create_task(self.redis_plan_cache.put(intent, version, plan))
        self._cache_writes.add(task)
        task.add_done_callback(self._cache_writes.discard)

    def _cache_put(self, key: tuple[str, int], plan: Plan) -> None:
        self._plan_cache[key] = plan
        self._plan_cache.move_to_end(key)
        while len(self._plan_cache) > self.config.planner.plan_cache_size:
            self._plan_cache.popitem(last=False)

    async def _context(
        self,
        intent: str,
        exclude: Optional[set[str]] = None,
        version: Optional[int] = None,
        *,
        deadline_at: Optional[float] = None,
        replan_prior: Optional[tuple[str, ...]] = None,
        tenant: str = "default",
    ) -> PlanContext:
        shortlist = None
        exclude = exclude or set()
        if self.retriever is not None:
            refresh = getattr(self.retriever, "maybe_refresh", None)
            if refresh is not None:
                await refresh(self.registry, version)
            # Over-fetch so excluded (replanned-around) services don't starve
            # the shortlist of viable candidates.
            k = self.config.planner.shortlist_top_k
            size = getattr(self.retriever, "size", None)
            if size is None or k < size or exclude:
                names = await self.retriever.shortlist(intent, k + len(exclude))
                shortlist = [n for n in names if n not in exclude][:k]
            # else: a shortlist that covers the registry ranks nothing: the
            # planner is shown the registry itself, in its own order (a
            # catalogue, planner/llm.py), and retrieval is skipped.
        if version is None:
            version = await self.registry.version()
        return PlanContext(
            registry=self.registry,
            telemetry=self.telemetry.snapshot(),
            shortlist=shortlist,
            exclude=exclude,
            registry_version=version,
            deadline_at=deadline_at,
            replan_prior=replan_prior,
            tenant=tenant,
        )

    # --------------------------------------------------------------- execute
    async def execute(
        self,
        plan: Plan,
        payload: dict[str, Any],
        trace: Optional[ExecutionTrace] = None,
        *,
        deadline_ms: Optional[float] = None,
    ) -> ExecuteResult:
        """``deadline_ms`` (the /execute deadline header, parsed by the
        handler only while resilience is wired) becomes the request's
        deadline budget inside the orchestrator's attempt chains."""
        return await self.orchestrator.execute(
            plan, payload, trace, deadline_ms=deadline_ms
        )

    # ------------------------------------------------------- plan_and_execute
    async def plan_and_execute(
        self, intent: str, payload: dict[str, Any], *, tenant: str = "default"
    ) -> dict[str, Any]:
        """Plan, execute, and adaptively replan around observed failures
        (bounded by ``telemetry.max_replans``).

        With the engine's radix prefix cache this is a structured program,
        not three independent calls: the plan's prompt KV is PINNED for the
        whole execution (tool calls take seconds — long enough for eviction
        to reclaim an unpinned prefix under load), and a failure-triggered
        replan renders its prompt as the ORIGINAL prompt plus a spliced-in
        suffix (Avoid line carrying the breaker/replan exclusions, PR 5),
        so the replan decode continues from the cached prefix at
        incremental-decode cost instead of cold re-planning."""
        trace = ExecutionTrace()
        plan, _ = await self.plan(intent, tenant=tenant)
        engine = getattr(self.planner, "engine", None)
        pin = None
        if engine is not None and plan.prompt_ids:
            try:
                pin = await engine.pin_prefix(plan.prompt_ids)
            except Exception:  # noqa: BLE001 - pinning is an optimisation
                log.debug("prefix pin failed; replans run unpinned", exc_info=True)
        try:
            result = await self.execute(plan, payload, trace)
            exclude: set[str] = set()
            prior = tuple(plan.prompt_services or ())
            while (
                result.status != "ok"
                and trace.replans < self.replan_policy.max_replans
            ):
                records = {r.name: r for r in await self.registry.list_services()}
                decision = self.replan_policy.assess(
                    plan, result, self.telemetry, records
                )
                if not decision.should_replan:
                    break
                exclude |= decision.exclude
                self.metrics.replans.inc()
                trace.replans += 1
                provenance.emit(
                    "replan",
                    f"replan attempt {trace.replans}: "
                    + ("; ".join(decision.reasons) or "policy"),
                    alternatives=sorted(decision.exclude),
                    signals={"status": result.status},
                    excluded=sorted(exclude),
                )
                context = await self._context(
                    intent, exclude, replan_prior=prior or None, tenant=tenant
                )
                try:
                    plan = await self.planner.plan(intent, context)
                except Exception:
                    # Nothing viable left to route around; keep the last
                    # result — but say so, or a planner crash mid-replan is
                    # invisible.
                    log.exception(
                        "replan attempt %d failed; keeping last result",
                        trace.replans,
                    )
                    break
                if provenance.active():
                    # The repaired plan's origin record (the replan loop
                    # calls the planner directly, not through plan()).
                    self._emit_plan_provenance(
                        intent, plan, self.planner, context, degraded=False
                    )
                result = await self.execute(plan, payload, trace)
        finally:
            if pin is not None:
                engine.unpin_prefix(pin)
        if trace.replans and result.status == "ok":
            # The repaired plan is the one worth caching — in EVERY enabled
            # tier; a stale failing plan left in Redis would keep re-warming
            # every replica's LRU (this one included, after eviction) with
            # the plan that triggers the fail->replan cycle.
            version = await self.registry.version()
            if self.config.planner.plan_cache_size > 0:
                self._cache_put((intent, version), plan)
            if self.redis_plan_cache is not None:
                self._redis_cache_write(intent, version, plan)
        return {
            "graph": plan.to_wire(),
            "results": result.results,
            "errors": result.errors,
            "status": result.status,
            "replans": trace.replans,
            # Which planner authored the final plan — lets benchmarks gate on
            # the LLM accept rate end-to-end (VERDICT r2 #9).
            "origin": plan.origin,
            "trace": result.trace.to_dict() if result.trace else None,
        }

    # ------------------------------------------------------------ cache stats
    def cache_stats(self) -> dict[str, Any]:
        """Combined cache observability for ``GET /cache``: the plan cache
        (local LRU tier) and the engine's radix prefix KV cache — hit
        rates, residency and evictions in one JSON read instead of
        scrape-only Prometheus counters."""
        s = self.plan_cache_stats
        lookups = s["hits"] + s["redis_hits"] + s["misses"]
        out: dict[str, Any] = {
            "plan_cache": {
                "entries": len(self._plan_cache),
                "capacity": self.config.planner.plan_cache_size,
                "redis_tier": self.redis_plan_cache is not None,
                **s,
                "hit_rate": (
                    (s["hits"] + s["redis_hits"]) / lookups if lookups else 0.0
                ),
            },
            "prefix_cache": None,
        }
        engine = getattr(self.planner, "engine", None)
        stats_fn = getattr(engine, "prefix_cache_stats", None)
        if stats_fn is not None:
            out["prefix_cache"] = stats_fn()
        return out
