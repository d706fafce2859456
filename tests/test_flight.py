"""Flight recorder & anomaly observatory (mcpx/telemetry/flight.py):
detector semantics over seeded synthetic series, worker-profiler phase
accounting, recorder-off parity, and the end-to-end chaos-trips-a-detector
acceptance — a seeded ChaosTransport degrades /execute, the p99 detector
trips, and the captured bundle names the offending requests' trace ids
(`mcpx debug bundle` round-trips it)."""

import asyncio
import json
import random

import pytest
from aiohttp.test_utils import TestClient, TestServer

from mcpx.core.config import MCPXConfig
from mcpx.orchestrator.transport import RouterTransport
from mcpx.resilience.chaos import ChaosProfile, ChaosTransport
from mcpx.server.app import build_app
from mcpx.server.factory import build_control_plane
from mcpx.telemetry.flight import (
    AnomalyDetector,
    FlightRecorder,
    WorkerProfiler,
    validate_bundle,
)

from tests.helpers import FakeService, make_transport


# ------------------------------------------------------------------ detectors
def _det(**kw):
    base = dict(direction="high", alpha=0.3, k=5.0, min_samples=10,
                hysteresis=3, floor=5.0)
    base.update(kw)
    return AnomalyDetector("d", "s", **base)


def test_detector_no_trip_on_stationary_noise():
    rng = random.Random(7)
    det = _det()
    for _ in range(400):
        assert det.observe(100.0 + rng.uniform(-3.0, 3.0)) is False
    assert det.trips == 0 and not det.active
    assert det.mean == pytest.approx(100.0, abs=3.0)


def test_detector_trips_exactly_once_per_excursion_and_rearms():
    rng = random.Random(11)
    det = _det(hysteresis=3)
    for _ in range(50):
        det.observe(100.0 + rng.uniform(-1.0, 1.0))
    # Sustained shift: trips on the 3rd consecutive out-of-band sample,
    # then stays silent for the rest of the excursion.
    fired = [det.observe(300.0) for _ in range(20)]
    assert fired.count(True) == 1
    assert fired[:3] == [False, False, True]
    assert det.active and det.trips == 1
    # Baseline frozen during the excursion: the mean did not chase 300.
    assert det.mean == pytest.approx(100.0, abs=2.0)
    # Recovery re-arms after `hysteresis` in-band samples…
    for _ in range(5):
        assert det.observe(100.0) is False
    assert not det.active
    # …so a second excursion trips again (exactly once).
    fired = [det.observe(300.0) for _ in range(10)]
    assert fired.count(True) == 1 and det.trips == 2


def test_detector_hysteresis_swallows_single_spikes():
    det = _det(hysteresis=3)
    for _ in range(30):
        det.observe(100.0)
    # Two isolated spikes (streak < hysteresis, reset between) never trip.
    assert det.observe(500.0) is False
    assert det.observe(100.0) is False
    assert det.observe(500.0) is False
    assert det.observe(500.0) is False
    assert det.trips == 0 and not det.active


def test_detector_low_direction_and_none_skipped():
    det = _det(direction="low", floor=0.1, hysteresis=2, min_samples=5)
    for _ in range(10):
        det.observe(0.8)
    assert det.observe(None) is False  # skipped: no streaks, no baseline move
    assert det.observe(0.2) is False
    assert det.observe(0.2) is True
    assert det.trips == 1
    st = det.state()
    assert st["active"] and st["direction"] == "low" and st["trips"] == 1


# ------------------------------------------------------------------- profiler
def test_profiler_laps_tile_and_carves_subtract():
    t = {"now": 0.0}

    def clock():
        return t["now"]

    prof = WorkerProfiler(clock=clock)
    prof.loop_tick()
    t["now"] = 1.0
    prof.lap("drain")                    # 1.0s drain
    t0 = prof.mark()
    t["now"] = 1.4
    prof.carve("prefix_match", t0)       # 0.4s carved out of the next lap
    t["now"] = 2.0
    prof.lap("admit")                    # 1.0s interval - 0.4 carved = 0.6
    snap = prof.snapshot()
    ph = snap["phases"]
    assert ph["drain"]["total_s"] == pytest.approx(1.0)
    assert ph["prefix_match"]["total_s"] == pytest.approx(0.4)
    assert ph["admit"]["total_s"] == pytest.approx(0.6)
    # Laps tile the loop: everything between first and last lap is named.
    assert snap["attributed_frac"] == pytest.approx(1.0)
    assert snap["wall_s"] == pytest.approx(2.0)
    # window(): the phases since the previous call (the first: since the
    # first tick), closed at the caller's stamp; they sum to its length.
    t_sync = prof.mark()
    t["now"] = 2.5
    t_ready = prof.carve("sync", t_sync)  # returns the now it read
    assert t_ready == 2.5
    start, w = prof.window("harvest", t_ready)
    assert start == 0.0
    assert w["drain"] == pytest.approx(1.0) and w["admit"] == pytest.approx(0.6)
    assert w["sync"] == pytest.approx(0.5) and w["harvest"] == 0.0
    assert sum(w.values()) == pytest.approx(t_ready - start)
    t["now"] = 2.75
    prof.lap("harvest")                  # bookkeeping after the ready stamp
    t0 = prof.mark()
    t["now"] = 3.0
    prof.carve("idle", t0)
    t["now"] = 3.5
    start, w = prof.window("harvest", 3.5)
    assert start == 2.5
    assert w["harvest"] == pytest.approx(0.75) and w["idle"] == pytest.approx(0.25)
    assert w["sync"] == 0.0 and w["drain"] == 0.0
    assert sum(w.values()) == pytest.approx(1.0)
    # "hold" (ISSUE 29: waiting for arrivals to join the segment being
    # held) is carved out of the drain lap like "idle", twice in one lap
    # when an arrival came in between; the window still tiles.
    for t_from, t_to in ((3.5, 3.8), (3.9, 4.3)):
        t["now"] = t_from
        t0 = prof.mark()
        t["now"] = t_to
        prof.carve("hold", t0)
    t["now"] = 4.4
    prof.lap("drain")
    start, w = prof.window("harvest", 4.5)
    assert start == 3.5
    assert w["hold"] == pytest.approx(0.7) and w["drain"] == pytest.approx(0.2)
    assert w["harvest"] == pytest.approx(0.1) and w["idle"] == 0.0
    assert sum(w.values()) == pytest.approx(1.0)
    assert prof.snapshot()["phases"]["hold"]["count"] == 2


def test_profiler_reads_the_spans_clock_and_survives_a_late_attach():
    """The profiler's default clock is the spans' (time.monotonic), and a
    profiler attached to a live worker mid-iteration (window before any
    loop_tick) starts its first window there instead of raising."""
    import time

    prof = WorkerProfiler()
    assert prof._clock is time.monotonic
    t_sync = prof.mark()
    t_ready = prof.carve("sync", t_sync)
    start, w = prof.window("harvest", t_ready)
    assert start == t_ready and sum(w.values()) == pytest.approx(0.0, abs=1e-3)


# ---------------------------------------------------------- recorder mechanics
def _flight_cfg(tmp_path, **kw):
    base = dict(enabled=True, interval_s=1.0, min_samples=3, hysteresis=2,
                cooldown_s=0.0, bundle_dir=str(tmp_path), max_bundles=2)
    base.update(kw)
    return MCPXConfig.from_dict({"telemetry": {"flight": base}}).telemetry.flight


def test_recorder_derives_window_worker_shares(tmp_path):
    """Worker phase shares in the ring are WINDOW deltas of the profiler's
    cumulative totals, not lifetime shares — an excursion must move them."""
    raw = {"worker_phase_totals": {"idle": 0.0, "dispatch": 0.0}}
    clock = {"now": 0.0}
    rec = FlightRecorder(
        _flight_cfg(tmp_path), lambda: dict(raw), clock=lambda: clock["now"]
    )
    rec.sample()  # first sample: no prev -> no share signals
    assert "worker_idle_share" not in rec.ring[-1]["signals"]
    # A long dispatch-heavy history...
    raw["worker_phase_totals"] = {"idle": 10.0, "dispatch": 990.0}
    clock["now"] += 1.0
    rec.sample()
    assert rec.ring[-1]["signals"]["worker_dispatch_share"] == 0.99
    # ...then one all-idle window: the WINDOW share flips to idle even
    # though the lifetime share barely moved.
    raw["worker_phase_totals"] = {"idle": 11.0, "dispatch": 990.0}
    clock["now"] += 1.0
    rec.sample()
    assert rec.ring[-1]["signals"]["worker_idle_share"] == 1.0
    assert rec.ring[-1]["signals"]["worker_dispatch_share"] == 0.0


def test_recorder_window_ratio_catches_late_collapse(tmp_path):
    """The frozen-tree shape on a LONG-RUNNING server: after a deep
    history of healthy hits, a total token-hit collapse must still trip
    token_hit_collapse — only a per-window ratio (counter deltas) can
    move; the lifetime ratio would drift ~1e-4/window and never alarm."""
    raw = {"prefix_matched_tokens_total": 0.0, "prefill_tokens_total": 0.0}
    clock = {"now": 0.0}
    rec = FlightRecorder(
        _flight_cfg(tmp_path, ring_size=512),
        lambda: dict(raw),
        clock=lambda: clock["now"],
        bundle_sources={"traces": lambda: []},
    )

    async def go():
        bundles = []
        # A long healthy history: 80 tokens matched + 20 prefilled per
        # window, hit rate 0.8, for far longer than the warmup.
        for _ in range(60):
            clock["now"] += 1.0
            raw["prefix_matched_tokens_total"] += 80.0
            raw["prefill_tokens_total"] += 20.0
            bundles += await rec.tick()
        assert not bundles
        assert rec.ring[-1]["signals"]["prefix_token_hit_rate"] == 0.8
        # Frozen tree: every subsequent window prefills everything.
        for _ in range(6):
            clock["now"] += 1.0
            raw["prefill_tokens_total"] += 100.0
            bundles += await rec.tick()
        assert rec.ring[-1]["signals"]["prefix_token_hit_rate"] == 0.0
        assert len(bundles) == 1
        det = {d.name: d for d in rec.detectors}["token_hit_collapse"]
        assert det.trips == 1 and det.active

    asyncio.run(go())


def test_recorder_rates_ring_and_compile_burst_bundle(tmp_path):
    raw = {"compiles_total": 0.0}
    clock = {"now": 0.0}
    cfg = _flight_cfg(tmp_path, ring_size=8)
    rec = FlightRecorder(
        cfg, lambda: dict(raw), clock=lambda: clock["now"],
        bundle_sources={"traces": lambda: [{"trace_id": "t1"}]},
    )

    async def go():
        bundles = []
        # Stationary baseline: no compiles after warmup.
        for _ in range(8):
            clock["now"] += 1.0
            bundles += await rec.tick()
        assert not bundles
        latest = rec.ring[-1]["signals"]
        assert latest["compile_rate"] == 0.0
        # Compile storm: 10 compiles/s sustained -> recompile_burst trips
        # on the `hysteresis`th out-of-band window, capturing ONE bundle.
        for _ in range(6):
            clock["now"] += 1.0
            raw["compiles_total"] += 10.0
            bundles += await rec.tick()
        assert len(bundles) == 1
        det = {d.name: d for d in rec.detectors}["recompile_burst"]
        assert det.trips == 1 and det.active
        # Ring stays bounded.
        assert len(rec.ring) == 8
        # The bundle round-trips from disk and passes the schema gate.
        bundle = await rec.load_bundle(bundles[0])
        assert bundle is not None
        assert validate_bundle(bundle) == []
        assert bundle["trigger"]["detector"] == "recompile_burst"
        assert bundle["traces"] == [{"trace_id": "t1"}]
        assert rec.status()["bundles"][0]["bundle_id"] == bundles[0]

    asyncio.run(go())


def test_recorder_cooldown_suppresses_and_retention_prunes(tmp_path):
    raw = {"compiles_total": 0.0}
    clock = {"now": 0.0}
    cfg = _flight_cfg(tmp_path, cooldown_s=1000.0, hysteresis=1)
    rec = FlightRecorder(cfg, lambda: dict(raw), clock=lambda: clock["now"])

    async def go():
        for _ in range(4):
            clock["now"] += 1.0
            await rec.tick()
        det = {d.name: d for d in rec.detectors}["recompile_burst"]
        bundles = []
        # Trip, recover past the hysteresis, trip again INSIDE cooldown:
        # the second trip counts but captures no second bundle.
        for burst in (True, False, True):
            for _ in range(3):
                clock["now"] += 1.0
                raw["compiles_total"] += 10.0 if burst else 0.0
                bundles += await rec.tick()
        assert det.trips == 2
        assert det.suppressed_trips == 1
        assert len(bundles) == 1

    asyncio.run(go())


# ------------------------------------------------------ engine worker profiler
def test_engine_worker_profile_attribution_and_parity():
    """ISSUE 13 acceptance (engine side), on ISSUE 24's terms: with the
    profiler on the worker thread's wall time is >=95% attributed to named
    phases, surfaced in queue_stats and, per harvested segment, as flat
    attributes of the engine.segment spans. With tracing.enabled false AND
    profile_worker false the engine has no profiler: no worker_profile
    key, none of the new attributes even on a request that carries a span,
    and greedy token outputs byte-identical."""
    from mcpx.engine.engine import InferenceEngine
    from mcpx.telemetry import tracing
    from mcpx.telemetry.flight import PROFILE_PHASES, SEGMENT_PARTS
    from mcpx.telemetry.tracing import Tracer

    timeline = {"seq", "prefill_rows", "hold_joined_rows", "period_ms",
                "sync_ms", "idle_ms", "hold_ms", "host_ms", *SEGMENT_PARTS}

    def cfg(on):
        return MCPXConfig.from_dict(
            {
                "model": {"size": "test", "max_seq_len": 256},
                "engine": {"max_batch_size": 4, "max_decode_len": 12},
                "tracing": {"enabled": on},
                "telemetry": {"flight": {"profile_worker": on}},
            }
        )

    async def traced(eng, tracer, ids):
        root = tracer.start_request("/plan")
        with tracing.activate(root):
            res = await eng.generate(
                ids, max_new_tokens=8, constrained=False, temperature=0.0
            )
        tracer.finish(root)
        return res, tracer.get(root.record.trace_id).spans

    async def go():
        eng_on = InferenceEngine(cfg(True))
        eng_off = InferenceEngine(cfg(False))
        assert eng_on._profiler is not None and eng_off._profiler is None
        # Either switch alone builds one (tracing is on by default).
        assert InferenceEngine(MCPXConfig.from_dict(
            {"model": {"size": "test"}, "tracing": {"enabled": False},
             "telemetry": {"flight": {"profile_worker": True}}}
        ))._profiler is not None
        assert InferenceEngine(MCPXConfig.from_dict(
            {"model": {"size": "test"}}
        ))._profiler is not None
        await eng_on.start()
        await eng_off.start()
        try:
            ids = eng_on.tokenizer.encode("profile this plan please")
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            r_on, spans_on = await traced(eng_on, tracer, ids)
            r_off, spans_off = await traced(eng_off, tracer, ids)
            # Parity: profiling only observes.
            assert r_on.token_ids == r_off.token_ids
            assert eng_off._profiler is None
            assert "worker_profile" not in eng_off.queue_stats()
            wp = eng_on.queue_stats()["worker_profile"]
            assert set(wp["phases"]) == set(PROFILE_PHASES)
            assert "hold" in wp["phases"] and wp["hold_joined_rows"] >= 0
            assert wp["iterations"] >= 1
            assert wp["attributed_frac"] >= 0.95
            # The decode-heavy phases actually saw time (dispatch split
            # into submit vs sync since ISSUE 15 — submit is the host-side
            # enqueue cost the fused window amortises, sync the blocking
            # device_get waits carved out of harvest).
            assert wp["phases"]["dispatch_submit"]["total_s"] > 0
            assert wp["phases"]["sync"]["count"] >= 1
            assert wp["phases"]["harvest"]["count"] >= 1
            # The phases rode the trace per harvested segment, as flat
            # numbers; engine.decode carries no per-request residency dict
            # of whole-loop totals any more.
            by_name = lambda spans, n: [s for s in spans if s.name == n]  # noqa: E731
            segs = by_name(spans_on, "engine.segment")
            assert segs and all(timeline <= set(s.attrs) for s in segs)
            assert all(
                isinstance(s.attrs[k], (int, float)) for s in segs for k in timeline
            )
            qw = by_name(spans_on, "engine.queue_wait")[0]
            assert {"unseen_ms", "free_row_ms"} <= set(qw.attrs)
            decode = by_name(spans_on, "engine.decode")[0]
            assert not any(isinstance(v, dict) for v in decode.attrs.values())
            # Off: the same spans, none of the new attributes.
            segs_off = by_name(spans_off, "engine.segment")
            assert segs_off and [s.attrs["tokens"] for s in segs_off] == [
                s.attrs["tokens"] for s in segs
            ]
            for s in spans_off:
                assert not (timeline | {"unseen_ms", "free_row_ms"}) & set(s.attrs)
        finally:
            await eng_on.aclose()
            await eng_off.aclose()

    asyncio.run(asyncio.wait_for(go(), 240))


# ------------------------------------------------------------- e2e chaos trip
GRAPH = {
    "nodes": [
        {"name": "a", "service": "svc", "endpoint": "local://svc",
         "retries": 0, "timeout_s": 2.0},
    ],
    "edges": [],
}


def test_chaos_trips_detector_and_bundle_names_offending_traces(tmp_path):
    """The end-to-end acceptance: a seeded ChaosTransport degrades
    /execute latency, the p99_shift detector trips, and the diagnostic
    bundle (schema-valid, served over /debug/anomalies, fetched by
    `mcpx debug bundle`) contains the offending requests' trace ids."""
    svc = FakeService("svc", result={"ok": True})
    transport = RouterTransport(local=make_transport(svc))
    config = MCPXConfig.from_dict(
        {
            "telemetry": {
                "flight": {
                    "enabled": True,
                    # Huge interval: the app's background loop stays quiet
                    # and the test drives tick() deterministically.
                    "interval_s": 3600.0,
                    "min_samples": 3,
                    "hysteresis": 2,
                    "cooldown_s": 0.0,
                    "bundle_dir": str(tmp_path),
                }
            }
        }
    )
    cp = build_control_plane(config, transport=transport)
    app = build_app(cp)
    chaos = ChaosTransport(
        transport,
        ChaosProfile.from_dict(
            {"seed": 99, "endpoints": {"local://svc": {"latency_ms": 250}}}
        ),
    )

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            fl = cp.flight
            assert fl is not None

            async def burst(n=3):
                tids = []
                for _ in range(n):
                    resp = await client.post(
                        "/execute", json={"graph": GRAPH, "payload": {}}
                    )
                    assert resp.status == 200
                    tids.append(resp.headers["X-Trace-Id"])
                return tids

            # Baseline: healthy transport, fast /execute, detector arms.
            for _ in range(6):
                await burst()
                assert await fl.tick() == []
            # Fault injection: the seeded chaos profile slows every call.
            cp.orchestrator._transport = chaos
            slow_tids = []
            bundle_ids = []
            for _ in range(3):
                slow_tids += await burst()
                bundle_ids += await fl.tick()
            assert bundle_ids, "chaos did not trip any detector"
            det = {d.name: d for d in fl.detectors}["p99_shift"]
            assert det.trips == 1 and det.active

            # The bundle is schema-valid and names the offending traces.
            bundle = await fl.load_bundle(bundle_ids[0])
            assert validate_bundle(bundle) == []
            assert bundle["trigger"]["detector"] == "p99_shift"
            bundle_tids = {t["trace_id"] for t in bundle["traces"]}
            assert bundle_tids & set(slow_tids), (
                "bundle traces miss the injected-fault requests"
            )
            # Window snapshots include the degraded p99 the trigger saw.
            assert bundle["window"][-1]["signals"]["request_p99_ms"] >= 200

            # Served over the debug endpoints…
            resp = await client.get("/debug/anomalies")
            status = await resp.json()
            assert status["enabled"] and status["detectors"]["p99_shift"]["active"]
            assert [b["bundle_id"] for b in status["bundles"]] == bundle_ids
            resp = await client.get(f"/debug/anomalies/{bundle_ids[0]}")
            assert resp.status == 200
            assert (await resp.json())["bundle_id"] == bundle_ids[0]
            resp = await client.get("/debug/anomalies/nope")
            assert resp.status == 404

            # …and round-tripped by the CLI (sync urllib, off the loop).
            from mcpx.cli.main import main as cli_main

            base = f"http://{client.server.host}:{client.server.port}"
            out_path = str(tmp_path / "fetched.json")
            rc = await asyncio.to_thread(
                cli_main,
                ["debug", "bundle", "--url", base, "--out", out_path],
            )
            assert rc == 0
            with open(out_path) as f:
                fetched = json.load(f)
            assert validate_bundle(fetched) == []
            assert fetched["bundle_id"] == bundle_ids[0]
        finally:
            cp.orchestrator._transport = transport
            await client.close()

    asyncio.run(go())


def test_recorder_off_is_pass_through():
    """Parity: the default config builds NO recorder, /debug/anomalies
    answers enabled:false, and the queue_stats surface is untouched (no
    worker_profile key — the full key set is pinned by
    test_scheduler.test_engine_queue_stats_surface)."""
    svc = FakeService("svc", result={"ok": True})
    cp = build_control_plane(
        MCPXConfig(), transport=RouterTransport(local=make_transport(svc))
    )
    assert cp.flight is None
    app = build_app(cp)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/debug/anomalies")
            assert resp.status == 200
            body = await resp.json()
            assert body == {"enabled": False, "detectors": {}, "bundles": []}
            resp = await client.get("/debug/anomalies/any")
            assert resp.status == 404
        finally:
            await client.close()

    asyncio.run(go())


def test_bundle_schema_validator_rejects_malformed():
    assert validate_bundle(None) == ["bundle is not an object"]
    problems = validate_bundle({"version": 0})
    assert any("version" in p for p in problems)
    assert any("trigger" in p for p in problems)
    assert any("window" in p for p in problems)


def test_recorder_derives_cluster_decision_outcome_signals(tmp_path):
    """ISSUE 19: per-window deltas of the pool's routing-journal counts
    become decision-outcome signals; without a pool the keys are absent
    and every cluster detector skips (recorder parity untouched)."""
    raw = {}
    clock = {"now": 0.0}
    rec = FlightRecorder(
        _flight_cfg(tmp_path), lambda: dict(raw), clock=lambda: clock["now"]
    )
    rec.sample()
    assert "affinity_hit_rate" not in rec.ring[-1]["signals"]
    # A pool appears: first sampled window with the journal counters.
    raw.update({
        "cluster_routed_total": 100.0,
        "cluster_affinity_hit_total": 80.0,
        "cluster_degraded_route_total": 10.0,
        "cluster_resteer_total": 0.0,
    })
    clock["now"] += 1.0
    rec.sample()
    # Next window: 100 more routes, 20 affinity hits, 30 degraded, 2
    # resteers — the signals are THIS window's ratios, not lifetime.
    raw.update({
        "cluster_routed_total": 200.0,
        "cluster_affinity_hit_total": 100.0,
        "cluster_degraded_route_total": 40.0,
        "cluster_resteer_total": 2.0,
    })
    clock["now"] += 1.0
    rec.sample()
    sig = rec.ring[-1]["signals"]
    assert sig["affinity_hit_rate"] == 0.2
    assert sig["degraded_route_share"] == 0.3
    assert sig["resteer_rate"] == 2.0
    # The SPC detectors watching them are registered by default.
    watched = {d.signal for d in rec.detectors}
    assert {"affinity_hit_rate", "resteer_rate",
            "degraded_route_share"} <= watched


def test_bundle_carries_cluster_attribution(tmp_path):
    """ISSUE 19 acceptance: with a replica pool attached, bundles carry a
    ``cluster_attribution`` source — per-replica decision attribution
    (lifetime counters, recent ring decisions + policy winners, signal
    rings) plus the failover journal."""
    from mcpx.telemetry.flight import build_flight_recorder
    from tests.test_cluster import _pool

    svc = FakeService("svc", result={"ok": True})
    transport = RouterTransport(local=make_transport(svc))
    config = MCPXConfig.from_dict(
        {"telemetry": {"flight": {
            "enabled": True, "interval_s": 3600.0,
            "bundle_dir": str(tmp_path),
        }}}
    )
    cp = build_control_plane(config, transport=transport)

    async def go():
        pool, _ = _pool(2)
        await pool.start()
        for _ in range(3):
            await pool.generate([1, 2, 3])
        await pool.kill(1)
        cp.cluster = pool
        fl = build_flight_recorder(cp)
        fl.sample()
        bundle = fl._assemble(
            {"detector": "replica_skew", "signal": "replica_skew",
             "direction": "high", "value": 3.0, "mean": 1.0, "band": 0.2}
        )
        attr = bundle["cluster_attribution"]
        assert set(attr["replicas"]) == {"0", "1"}
        assert sum(r["routed"] for r in attr["replicas"].values()) == 3
        assert attr["journal_counts"]["kill"] == 1
        assert any(e["kind"] == "kill" for e in attr["journal"])
        # The scoreboard source rides along and the bundle stays valid.
        assert "journal_counts" in bundle["cluster"]
        assert validate_bundle(bundle) == []
        await pool.aclose()

    asyncio.run(go())
