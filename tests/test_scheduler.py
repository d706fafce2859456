"""Scheduler subsystem unit tests (mcpx/scheduler/): token-bucket refill,
deadline/ETA shedding, fair-queuing ordering, degradation hysteresis, and
the engine's queue_stats surface."""

import asyncio
import math

import pytest

from mcpx.core.config import MCPXConfig, SchedulerConfig
from mcpx.core.errors import ConfigError
from mcpx.scheduler import (
    DegradeController,
    FairQueue,
    RequestContext,
    Scheduler,
    ShedError,
    TokenBucket,
)


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ----------------------------------------------------------- token bucket
def test_token_bucket_burst_drain_and_refill():
    clock = FakeClock()
    b = TokenBucket(rate=10.0, burst=3, clock=clock)
    assert [b.try_acquire() for _ in range(3)] == [True, True, True]
    assert not b.try_acquire()  # burst exhausted, no time passed
    assert b.eta_s() == pytest.approx(0.1)  # one token at 10/s
    clock.advance(0.05)
    assert not b.try_acquire()  # half a token
    clock.advance(0.06)
    assert b.try_acquire()
    # Refill caps at burst: a long idle gap doesn't bank unlimited tokens.
    clock.advance(100.0)
    assert b.tokens == pytest.approx(3.0)


def test_token_bucket_rejects_nonpositive_rate():
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0, burst=1)


# ------------------------------------------------------------- fair queue
def test_fair_queue_quiet_tenant_jumps_hot_backlog():
    q = FairQueue()
    for i in range(5):
        q.push("hot", f"h{i}")
    q.push("cold", "c0")
    order = [q.pop() for _ in range(6)]
    # The cold tenant's single item dispatches ahead of the hot tenant's
    # backlog (entered at the global virtual time, not behind 5 tags).
    assert "c0" in order[:2], order
    assert order.count(None) == 0
    assert q.pop() is None


def test_fair_queue_weight_shares():
    q = FairQueue()
    for i in range(4):
        q.push("big", f"b{i}", weight=2.0)
        q.push("small", f"s{i}", weight=1.0)
    first6 = [q.pop() for _ in range(6)]
    n_big = sum(1 for x in first6 if x.startswith("b"))
    # weight 2 vs 1 -> a 2:1 dispatch share under contention.
    assert n_big == 4, first6


def test_fair_queue_edf_within_tenant():
    q = FairQueue()
    q.push("t", "late", deadline_at=300.0)
    q.push("t", "soon", deadline_at=100.0)
    q.push("t", "never")  # deadline-less ranks last
    q.push("t", "mid", deadline_at=200.0)
    assert [q.pop() for _ in range(4)] == ["soon", "mid", "late", "never"]


def test_fair_queue_depths():
    q = FairQueue()
    q.push("a", 1)
    q.push("a", 2)
    q.push("b", 3)
    assert q.depth() == 3
    assert q.tenant_depths() == {"a": 2, "b": 1}


# ------------------------------------------------------------ degradation
def test_degrade_hysteresis():
    clock = FakeClock()
    d = DegradeController(
        slo_s=0.1,
        degrade_threshold=0.5,  # engage above 50 ms EWMA wait
        recover_threshold=0.25,  # recover below 25 ms
        ewma_alpha=1.0,  # no smoothing: thresholds hit exactly
        min_hold_s=2.0,
        clock=clock,
    )
    assert not d.observe_wait(0.04)  # below hi: stays normal
    assert d.observe_wait(0.2)  # overload: engages
    # Pressure drops immediately — but the hold keeps the ladder engaged
    # (no flapping at the boundary).
    assert d.observe_wait(0.0)
    clock.advance(1.0)
    assert d.observe_wait(0.0)  # still inside min_hold_s
    clock.advance(1.5)
    assert not d.observe_wait(0.0)  # held long enough AND below lo: recovers
    # Between lo and hi after recovery: stays normal (hysteresis band).
    assert not d.observe_wait(0.04)


def test_degrade_requires_ordered_thresholds():
    with pytest.raises(ValueError):
        DegradeController(slo_s=1.0, degrade_threshold=0.2, recover_threshold=0.5)


# -------------------------------------------------------------- scheduler
def _sched(clock=None, **overrides) -> Scheduler:
    cfg = SchedulerConfig(enabled=True, **overrides)
    return Scheduler(cfg, None, clock=clock or FakeClock())


def test_scheduler_deadline_shed_at_enqueue():
    async def go():
        clock = FakeClock()
        s = _sched(clock, max_parallel=1)
        # A learned service time of 10s/request means a 100ms-deadline
        # request cannot possibly be served: shed synchronously.
        s._service_ewma_s = 10.0
        ctx = RequestContext(tenant="t", deadline_at=clock() + 0.1, enqueued_at=clock())
        with pytest.raises(ShedError) as ei:
            await s.acquire(ctx)
        assert ei.value.outcome == "shed_deadline"
        assert ei.value.retry_after_s >= 1.0
        assert int(ei.value.retry_after_header()) >= 1

    asyncio.run(go())


def test_scheduler_no_deadline_never_deadline_sheds():
    async def go():
        clock = FakeClock()
        s = _sched(clock, max_parallel=1)
        s._service_ewma_s = 10.0
        # deadline_at=None: remaining budget is infinite, never shed.
        slot = await s.acquire(RequestContext(tenant="t", enqueued_at=clock()))
        assert not slot.degraded
        s.release(slot)

    asyncio.run(go())


def test_scheduler_queue_cap_sheds():
    async def go():
        s = _sched(max_parallel=1, max_queue_depth=1)
        held = await s.acquire(RequestContext(tenant="t"))  # occupies the slot
        waiter = asyncio.ensure_future(s.acquire(RequestContext(tenant="t")))
        await asyncio.sleep(0)  # waiter enqueued (depth 1 = cap)
        with pytest.raises(ShedError) as ei:
            await s.acquire(RequestContext(tenant="t"))
        assert ei.value.outcome == "shed_queue"
        s.release(held)
        s.release(await waiter)

    asyncio.run(go())


def test_scheduler_dispatch_time_deadline_shed():
    """A request admitted on an optimistic ETA whose deadline expires while
    queued is shed at dispatch, not served as a corpse."""

    async def go():
        clock = FakeClock()
        s = _sched(clock, max_parallel=1)
        held = await s.acquire(RequestContext(tenant="t", enqueued_at=clock()))
        waiter = asyncio.ensure_future(
            s.acquire(
                RequestContext(tenant="t", deadline_at=clock() + 0.5, enqueued_at=clock())
            )
        )
        await asyncio.sleep(0)
        clock.advance(1.0)  # deadline passes while queued
        s.release(held)
        with pytest.raises(ShedError) as ei:
            await waiter
        assert ei.value.outcome == "shed_deadline"

    asyncio.run(go())


def test_scheduler_rate_limit_sheds_with_retry_after():
    async def go():
        clock = FakeClock()
        s = _sched(clock, rate_limit=10.0, burst=1, max_parallel=4)
        slot = await s.acquire(RequestContext(tenant="t"))
        s.release(slot)
        with pytest.raises(ShedError) as ei:
            await s.acquire(RequestContext(tenant="t"))
        assert ei.value.outcome == "shed_rate"
        assert ei.value.retry_after_s > 0

    asyncio.run(go())


def test_scheduler_service_ewma_and_engine_eta_floor():
    async def go():
        clock = FakeClock()
        eng = {"eta_s": 7.5}
        s = Scheduler(
            SchedulerConfig(enabled=True, max_parallel=1),
            None,
            engine_stats=lambda: eng,
            clock=clock,
        )
        slot = await s.acquire(RequestContext(tenant="t", enqueued_at=clock()))
        clock.advance(2.0)
        s.release(slot)
        assert s.service_ewma_s == pytest.approx(2.0)  # first sample seeds
        # Own estimate is (0+1)*2.0/1 = 2.0; engine's 7.5 floors it up.
        assert s.queue_eta_s() == pytest.approx(7.5)
        eng["eta_s"] = 0.0
        assert s.queue_eta_s() == pytest.approx(2.0)

    asyncio.run(go())


def test_scheduler_context_from_headers():
    clock = FakeClock()
    s = _sched(clock, default_deadline_ms=2000.0)
    ctx = s.context_from_headers(
        {"X-MCPX-Tenant": "acme", "X-MCPX-Deadline-Ms": "150", "X-MCPX-Priority": "4"}
    )
    assert ctx.tenant == "acme"
    assert ctx.deadline_at == pytest.approx(clock() + 0.15)
    assert ctx.weight == 4.0
    # Absent/malformed headers: defaults, never a rejection.
    ctx = s.context_from_headers({"X-MCPX-Deadline-Ms": "soon", "X-MCPX-Priority": "x"})
    assert ctx.tenant == "default"
    assert ctx.deadline_at == pytest.approx(clock() + 2.0)
    assert ctx.weight == 1.0


def test_scheduler_purges_abandoned_waiters_before_shedding():
    """Cancelled-while-queued entries (client disconnects) must not count
    as backlog: a full-of-phantoms queue purges instead of 429ing a live
    request."""
    import contextlib

    async def go():
        s = _sched(max_parallel=1, max_queue_depth=2)
        held = await s.acquire(RequestContext(tenant="t"))
        w1 = asyncio.ensure_future(s.acquire(RequestContext(tenant="t")))
        w2 = asyncio.ensure_future(s.acquire(RequestContext(tenant="t")))
        await asyncio.sleep(0)  # both enqueued: depth == cap
        w1.cancel()
        w2.cancel()
        for w in (w1, w2):
            with contextlib.suppress(asyncio.CancelledError):
                await w
        # Queue still holds the two dead entries — a live arrival purges
        # them instead of shedding shed_queue.
        live = asyncio.ensure_future(s.acquire(RequestContext(tenant="t")))
        await asyncio.sleep(0)
        s.release(held)
        slot = await live
        s.release(slot)

    asyncio.run(go())


def test_scheduler_per_tier_service_ewma():
    """Degraded (~ms) completions must not blind the primary-tier ETA
    estimate — each tier learns its own EWMA, and queue_eta_s costs the
    backlog at the tier the ladder would currently serve."""
    from mcpx.scheduler import Slot

    async def go():
        clock = FakeClock()
        s = _sched(clock, max_parallel=1)
        slot = await s.acquire(RequestContext(tenant="t", enqueued_at=clock()))
        clock.advance(1.0)
        s.release(slot)  # primary tier: 1.0s
        fake = Slot(
            ctx=RequestContext(tenant="t", enqueued_at=clock()),
            degraded=True,
            granted_at=clock(),
            queue_wait_s=0.0,
        )
        s._inflight += 1
        clock.advance(0.002)
        s.release(fake)  # degraded tier: 2ms
        assert s.service_ewma_s == pytest.approx(1.0)  # unpolluted
        assert s._degraded_ewma_s == pytest.approx(0.002)
        # Ladder off: ETA priced at the primary tier.
        assert s.queue_eta_s() == pytest.approx(1.0)
        # Ladder on: priced at the degraded tier (the tier that would
        # actually serve), so recovery-adjacent requests aren't shed on
        # the primary tier's cost.
        s._degrade.observe_wait(10.0)
        assert s.degraded
        assert s.queue_eta_s() == pytest.approx(0.002)

    asyncio.run(go())


# ---------------------------------------------------------- config wiring
def test_scheduler_config_validation():
    cfg = MCPXConfig.from_dict({"scheduler": {"enabled": True, "slo_ms": 100}})
    assert cfg.scheduler.enabled and cfg.scheduler.slo_ms == 100
    with pytest.raises(ConfigError):
        MCPXConfig.from_dict(
            {"scheduler": {"degrade_threshold": 0.2, "recover_threshold": 0.5}}
        )
    with pytest.raises(ConfigError):
        MCPXConfig.from_dict({"scheduler": {"slo_ms": 0}})
    with pytest.raises(ConfigError):
        MCPXConfig.from_dict({"scheduler": {"max_parallel": 0}})


def test_engine_queue_stats_surface():
    """queue_stats must be readable on a cold engine (scheduler attaches
    before/without start) and do fair-share ETA math on the EWMA."""
    from mcpx.engine.engine import InferenceEngine

    cfg = MCPXConfig.from_dict(
        {"model": {"size": "test", "max_seq_len": 256}, "engine": {"max_batch_size": 4}}
    )
    eng = InferenceEngine(cfg)
    st = eng.queue_stats()
    # Tracing is on by default and brings the worker profiler with it
    # (ISSUE 24); a cold engine's profile is empty. With tracing and
    # telemetry.flight.profile_worker both off the key is absent
    # (test_flight.test_engine_worker_profile_attribution_and_parity).
    assert st.pop("worker_profile")["iterations"] == 0
    assert st == {
        # Per-path ragged-kernel engagement (ISSUE 15): resolved at
        # construction (config + head-dim probe) so a COLD engine already
        # answers; the "test" model's head_dim aligns off-TPU only via
        # interpret, which this config leaves off -> jnp route, reasoned.
        "pallas": {
            "enabled": False,
            "interpret": False,
            "reason": (
                "head_dim 32 % 128 != 0: Mosaic lane tiling rejects the "
                "kernel on hardware (engine.interpret=true lifts the "
                "constraint off-TPU)"
            ),
            "paths": {
                "decode": {
                    "engaged": False,
                    "dispatches": 0,
                    "reason": (
                        "head_dim 32 % 128 != 0: Mosaic lane tiling "
                        "rejects the kernel on hardware "
                        "(engine.interpret=true lifts the constraint "
                        "off-TPU)"
                    ),
                },
                "prefill": {
                    "engaged": False,
                    "dispatches": 0,
                    "reason": (
                        "head_dim 32 % 128 != 0: Mosaic lane tiling "
                        "rejects the kernel on hardware "
                        "(engine.interpret=true lifts the constraint "
                        "off-TPU)"
                    ),
                },
                "spec_verify": {
                    "engaged": False,
                    "dispatches": 0,
                    "reason": (
                        "head_dim 32 % 128 != 0: Mosaic lane tiling "
                        "rejects the kernel on hardware "
                        "(engine.interpret=true lifts the constraint "
                        "off-TPU)"
                    ),
                },
            },
        },
        # Radix prefix-cache scoreboard (prefix-locality admission): empty
        # tree, no lookups yet.
        "prefix_nodes": 0,
        "prefix_resident_pages": 0,
        "prefix_hit_rate": 0.0,
        "prefix_token_hit_rate": 0.0,
        # Tiered-KV additions (ISSUE 11): host-tier residency and the
        # spill/readmit/destructive tallies — zeros single-tier and on a
        # cold tiered engine alike.
        "prefix_host_pages": 0,
        "prefix_spills": 0,
        "prefix_readmits": 0,
        "prefix_destructive_evictions": 0,
        "depth": 0,
        "active": 0,
        "service_ewma_s": 0.0,
        "eta_s": 0.0,
        # Heterogeneous-batching additions: per-class backlog, head-of-line
        # age, resident stacked grammars — all zero on a cold engine.
        "depth_constrained": 0,
        "depth_free": 0,
        "hol_wait_ms": 0.0,
        "resident_grammars": 0,
        # Speculative-decoding additions: accept rates, zero until the
        # drafter has proposed anything.
        "spec_accept_rate": 0.0,
        "spec_accept_rate_constrained": 0.0,
        "spec_accept_rate_free": 0.0,
    }
    eng._ewma_service_s = 2.0
    for _ in range(5):  # 4 fit the free slab rows; 1 overflows = 1 drain
        eng._queue.put(object())
    st = eng.queue_stats()
    assert st["depth"] == 5
    assert st["eta_s"] == pytest.approx(math.ceil(1 / 4) * 2.0)
