"""Application factory: config → fully wired ControlPlane.

All construction is lazy and injected — nothing touches the network or the
TPU at import time (the reference connects to Postgres at import, bug B8).
"""

from __future__ import annotations

import logging
from typing import Optional

from mcpx.core.config import MCPXConfig
from mcpx.orchestrator.executor import Orchestrator
from mcpx.orchestrator.transport import RouterTransport, Transport
from mcpx.planner.base import Planner
from mcpx.planner.heuristic import HeuristicPlanner
from mcpx.planner.mock import MockPlanner
from mcpx.registry import make_registry
from mcpx.registry.base import RegistryBackend
from mcpx.server.control import ControlPlane
from mcpx.telemetry.metrics import Metrics
from mcpx.telemetry.replan import ReplanPolicy
from mcpx.telemetry.stats import TelemetryStore


def build_control_plane(
    config: Optional[MCPXConfig] = None,
    *,
    registry: Optional[RegistryBackend] = None,
    planner: Optional[Planner] = None,
    transport: Optional[Transport] = None,
    retriever=None,
) -> ControlPlane:
    config = config or MCPXConfig()
    config.validate()
    registry = registry if registry is not None else make_registry(config.registry)
    transport = transport if transport is not None else RouterTransport()
    if retriever is None and config.retrieval.enabled:
        try:
            from mcpx.retrieval import RetrievalIndex  # deferred: pulls in JAX
        except ImportError as e:
            logging.getLogger("mcpx.factory").warning(
                "retrieval disabled: JAX stack unavailable (%s)", e
            )
            RetrievalIndex = None
        if RetrievalIndex is not None:
            if config.cluster.enabled and config.cluster.shard_registry:
                # Registry sharding (docs/cluster.md): row-partitioned
                # embedding table, shard-local top-k merged host-side.
                from mcpx.cluster.sharding import ShardedRetrievalIndex

                retriever = ShardedRetrievalIndex(
                    config.retrieval,
                    n_shards=config.cluster.registry_shards
                    or config.cluster.replicas,
                )
            else:
                retriever = RetrievalIndex(config.retrieval)
            if config.retrieval.snapshot_path:
                try:
                    retriever.load(config.retrieval.snapshot_path)
                except Exception as e:  # noqa: BLE001 - snapshot is rebuildable
                    logging.getLogger("mcpx.factory").warning(
                        "retrieval snapshot %s unusable (%s); will rebuild from registry",
                        config.retrieval.snapshot_path,
                        e,
                    )
    telemetry = TelemetryStore(config.telemetry.ewma_alpha)
    telemetry_mirror = None
    if config.telemetry.enabled and config.telemetry.redis_url:
        from mcpx.telemetry.mirror import RedisTelemetryMirror

        telemetry_mirror = RedisTelemetryMirror(telemetry, config.telemetry.redis_url)
    redis_plan_cache = None
    if config.planner.plan_cache_redis_url:
        from mcpx.server.plan_cache import RedisPlanCache

        redis_plan_cache = RedisPlanCache(
            config.planner.plan_cache_redis_url,
            ttl_s=config.planner.plan_cache_redis_ttl_s,
        )
    # An injected planner's engine (or pool) made its own registry at
    # construction; the from_config path below hands ONE registry to both.
    # Adopt it, so the engine's series (mcpx_engine_compiles_total,
    # mcpx_engine_resets_total, ...) show on the served GET /metrics.
    metrics = getattr(getattr(planner, "engine", None), "metrics", None)
    if not isinstance(metrics, Metrics):
        metrics = Metrics()
    chaos_profile = None
    if config.resilience.chaos_profile:
        # Chaos injection (`mcpx serve --chaos profile.json`): every
        # microservice call crosses the seeded fault injector. Wrapped
        # OUTSIDE the resilience gate on purpose — the same fault profile
        # can then be served with resilience on and off. The profile's
        # optional "cluster" section is NOT a transport fault — the engine
        # pool consumes it below (kill-a-replica / rejoin schedule).
        from mcpx.resilience.chaos import ChaosProfile, ChaosTransport

        chaos_profile = ChaosProfile.from_file(config.resilience.chaos_profile)
        transport = ChaosTransport(transport, chaos_profile)
    resilience = None
    if config.resilience.enabled:
        from mcpx.resilience import Resilience

        resilience = Resilience(
            config.resilience, telemetry=telemetry, metrics=metrics
        )
    orchestrator = Orchestrator(
        transport,
        config.orchestrator,
        registry=registry,
        telemetry=telemetry,
        metrics=metrics,
        resilience=resilience,
    )
    if planner is None:
        if config.planner.kind == "heuristic":
            planner = HeuristicPlanner(config.planner)
        elif config.planner.kind == "mock":
            planner = MockPlanner()
        else:  # "llm"
            try:
                from mcpx.planner.llm import LLMPlanner  # deferred: pulls in JAX
            except ImportError as e:
                from mcpx.core.errors import ConfigError

                raise ConfigError(f"planner.kind=llm unavailable: {e}") from e
            if config.cluster.enabled:
                # Cluster layer (mcpx/cluster/): N engine replicas behind
                # the same duck-typed surface a bare engine exposes, so the
                # scheduler/app/flight wiring below is untouched. Disabled
                # (the default) takes the from_config path — byte-identical
                # single-engine pass-through.
                from mcpx.cluster import EnginePool

                pool = EnginePool(
                    config,
                    metrics=metrics,
                    chaos=chaos_profile.cluster if chaos_profile else None,
                )
                planner = LLMPlanner(pool, config.planner)
            else:
                planner = LLMPlanner.from_config(
                    config, retriever=retriever, metrics=metrics
                )
    scheduler = None
    if config.scheduler.enabled:
        from mcpx.scheduler import Scheduler

        # The engine's queue ETA (depth x service-time EWMA) floors the
        # scheduler's own estimate; heuristic/mock planners have no engine
        # and the scheduler then estimates from its own grant/release
        # accounting alone.
        engine = getattr(planner, "engine", None)
        scheduler = Scheduler(
            config.scheduler,
            metrics,
            engine_stats=engine.queue_stats if engine is not None else None,
        )
    return ControlPlane(
        config=config,
        registry=registry,
        planner=planner,
        orchestrator=orchestrator,
        telemetry=telemetry,
        metrics=metrics,
        retriever=retriever,
        replan_policy=ReplanPolicy(
            config.telemetry,
            # Breaker state feeds replan exclusions: a learned-down endpoint
            # is routed around at PLAN time, not rediscovered per execute.
            breakers=resilience.breakers if resilience is not None else None,
        ),
        telemetry_mirror=telemetry_mirror,
        redis_plan_cache=redis_plan_cache,
        scheduler=scheduler,
    )
