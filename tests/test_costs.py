"""Roofline cost observatory (mcpx/telemetry/costs.py): per-executable XLA
cost accounting, the mcpx_engine_compiles_total retrace sentinel, span
wiring, spec-rate gauges, and the GET /costs surface."""

import asyncio
from types import SimpleNamespace

import numpy as np

from mcpx.core.config import MCPXConfig
from mcpx.telemetry.costs import CostRegistry, hbm_stats
from mcpx.telemetry.metrics import Metrics


def _compiles(metrics: Metrics, executable: str) -> float:
    return (
        metrics.registry.get_sample_value(
            "mcpx_engine_compiles_total", {"executable": executable}
        )
        or 0.0
    )


def make_engine(**engine_overrides):
    from mcpx.engine.engine import InferenceEngine

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
                **engine_overrides,
            },
        }
    )
    return InferenceEngine(cfg)


# ------------------------------------------------------------- the sentinel
def test_retrace_sentinel_increments_exactly_once_per_retrace():
    """ISSUE 7 acceptance: a deliberate retrace (new shape into a tracked
    executable) increments mcpx_engine_compiles_total exactly once for that
    executable — and repeat calls at a known signature increment nothing."""
    import jax
    import jax.numpy as jnp

    metrics = Metrics()
    reg = CostRegistry(metrics=metrics)
    f = reg.wrap("toy", jax.jit(lambda x: (x * 2.0).sum()))
    f(jnp.ones((8,)))
    assert _compiles(metrics, "toy") == 1.0
    f(jnp.ones((8,)))
    f(jnp.zeros((8,)))  # same signature, different values: no retrace
    assert _compiles(metrics, "toy") == 1.0
    f(jnp.ones((16,)))  # the deliberate retrace
    assert _compiles(metrics, "toy") == 2.0
    snap = reg.snapshot()
    assert snap["executables"]["toy"]["compiles"] == 2
    calls = sum(s["calls"] for s in snap["executables"]["toy"]["signatures"])
    assert calls == 4


def test_static_args_key_signatures():
    """Static-argument values are part of the signature (a new static IS a
    compile — jit semantics); repeats of a known static are not."""
    import jax
    import jax.numpy as jnp

    metrics = Metrics()
    reg = CostRegistry(metrics=metrics)
    f = reg.wrap(
        "stat",
        jax.jit(lambda x, *, k: x * k, static_argnames=("k",)),
        static_argnames=("k",),
    )
    x = jnp.ones((4,))
    f(x, k=2)
    f(x, k=2)
    assert _compiles(metrics, "stat") == 1.0
    f(x, k=3)
    assert _compiles(metrics, "stat") == 2.0


def test_costs_harvested_and_outputs_match_plain_jit():
    """The AOT-compiled path must be a pure accounting layer: outputs
    byte-identical to plain jit dispatch, with XLA cost_analysis captured
    (flops > 0, basis labeled) and executed-work totals accumulating."""
    import jax
    import jax.numpy as jnp

    def g(a, b):
        return a @ b + 1.0

    metrics = Metrics()
    reg = CostRegistry(metrics=metrics)
    tracked = reg.wrap("mm", jax.jit(g))
    a = jnp.arange(16.0).reshape(4, 4)
    b = jnp.ones((4, 4))
    got = tracked(a, b)
    want = jax.jit(g)(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    snap = reg.snapshot()
    sig = snap["executables"]["mm"]["signatures"][0]
    assert sig["cost_basis"] == "xla_cost_analysis"
    assert sig["flops"] and sig["flops"] > 0
    assert sig["bytes_accessed"] and sig["bytes_accessed"] > 0
    assert snap["totals"]["flops_executed"] >= sig["flops"]
    tracked(a, b)
    assert reg.snapshot()["totals"]["flops_executed"] == 2 * sig["flops"]


def test_donation_honored_through_tracked_path():
    import jax
    import jax.numpy as jnp

    reg = CostRegistry(metrics=Metrics())
    f = reg.wrap(
        "donate",
        jax.jit(lambda x, buf: (x + buf, buf * 0), donate_argnames=("buf",)),
    )
    buf = jnp.ones((8,))
    f(jnp.ones((8,)), buf)
    assert buf.is_deleted()


def test_disabled_registry_is_a_passthrough():
    import jax

    jitted = jax.jit(lambda x: x + 1)
    reg = CostRegistry(metrics=Metrics(), enabled=False)
    assert reg.wrap("noop", jitted) is jitted
    assert reg.snapshot()["enabled"] is False
    assert reg.snapshot()["executables"] == {}


def test_release_drops_executables_keeps_history():
    import jax
    import jax.numpy as jnp

    metrics = Metrics()
    reg = CostRegistry(metrics=metrics)
    f = reg.wrap("rel", jax.jit(lambda x: x * 3))
    f(jnp.ones((4,)))
    reg.release()
    snap = reg.snapshot()
    assert snap["executables"]["rel"]["compiles"] == 1
    # Still callable post-release (falls back to the jit path).
    out = f(jnp.ones((4,)))
    assert float(out[0]) == 3.0


def test_hbm_stats_labeled_unavailable_on_cpu():
    rows = hbm_stats()
    assert rows, "no local devices?"
    for row in rows:
        assert "device" in row and "available" in row
        if not row["available"]:
            assert "bytes_in_use" not in row


# ------------------------------------------------------- engine integration
def test_engine_costs_snapshot_spans_and_close():
    """The engine's executables are cost-tracked end to end: a traced
    generate leaves prefill/segment entries whose costs the snapshot
    (``GET /costs``) materialises, off the worker thread; the
    engine.prefill / engine.segment / engine.decode spans carry NO
    roofline attribute (ISSUE 40: they divided XLA's estimate by a host
    wall two segments deep, and made the worker compile to do it), and the
    snapshot stays readable after aclose."""
    from mcpx.telemetry import tracing
    from mcpx.telemetry.tracing import Tracer

    async def go():
        eng = make_engine()
        await eng.start()
        try:
            tracer = Tracer(enabled=True, sample_rate=1.0)
            root = tracer.start_request("bench")
            with tracing.activate(root):
                res = await eng.generate(
                    eng.tokenizer.encode("plan: compose. JSON:"),
                    max_new_tokens=16,
                )
            tracer.finish(root)
            assert res.generated_tokens > 0
            snap = eng.costs.snapshot()
            assert snap["enabled"] is True
            for name in ("prefill", "admit", "segment", "admit_merge"):
                ex = snap["executables"][name]
                assert ex["compiles"] >= 1, name
                assert sum(s["calls"] for s in ex["signatures"]) >= 1, name
            assert snap["totals"]["flops_executed"] > 0
            assert _compiles(eng.metrics, "prefill") >= 1.0
            rec = tracer.get(root.record.trace_id)
            by_name = {}
            for s in rec.spans:
                by_name.setdefault(s.name, s)
            gone = {"mfu", "hbm_bw_util", "roofline_bound", "achieved_flops_s",
                    "achieved_bytes_s", "arithmetic_intensity"}
            for span_name in ("engine.prefill", "engine.segment", "engine.decode"):
                sp = by_name.get(span_name)
                assert sp is not None, f"missing span {span_name}"
                assert not gone & set(sp.attrs), (span_name, sp.attrs)
            # The numbers are where they were computed from: the registry's
            # snapshot, which compiles for them on the reader's thread.
            for name in ("prefill", "segment"):
                sig = next(s for s in snap["executables"][name]["signatures"] if s["calls"])
                assert sig["cost_basis"] == "xla_cost_analysis", (name, sig)
                assert sig["flops"] > 0 and sig["bytes_accessed"] > 0
                assert sig["flops"] / sig["bytes_accessed"] > 0  # its arithmetic intensity
            return eng
        finally:
            await eng.aclose()

    eng = asyncio.run(go())
    # History survives close; executables were dropped.
    snap = eng.costs.snapshot()
    assert snap["executables"]["prefill"]["compiles"] >= 1


def test_spec_accept_rate_gauges_exported():
    """ISSUE 7 satellite: queue_stats()'s spec accept-rate fields are
    scrapeable gauges — per row class AND overall — next to the drafted/
    accepted counters."""
    eng = make_engine()  # never started: _account_speculation is host-only
    dr = np.array([4, 2, 0, 0])
    ac = np.array([3, 1, 0, 0])
    cons = np.array([True, False, False, False])
    eng._account_speculation(dr, ac, cons)
    g = eng.metrics.registry.get_sample_value
    assert g("mcpx_engine_spec_accept_rate", {"cls": "constrained"}) == 0.75
    assert g("mcpx_engine_spec_accept_rate", {"cls": "free"}) == 0.5
    assert g("mcpx_engine_spec_accept_rate", {"cls": "overall"}) == 4 / 6
    assert g("mcpx_engine_spec_drafted_total", {"cls": "constrained"}) == 4.0
    assert g("mcpx_engine_spec_accepted_total", {"cls": "free"}) == 1.0
    # And the dict view agrees (the satellite's "exists in both" contract).
    qs = eng.queue_stats()
    assert abs(qs["spec_accept_rate"] - 4 / 6) < 1e-9


# ------------------------------------------------------------ /costs surface
def test_costs_endpoint_without_engine_is_labeled():
    from aiohttp.test_utils import TestClient, TestServer

    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane

    async def go():
        cp = build_control_plane(MCPXConfig())
        app = build_app(cp)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/costs")
            assert r.status == 200
            body = await r.json()
            assert body["engine"] is None
            assert "no inference engine" in body["reason"]
            # /metrics must not trip over the engine-gated HBM refresh.
            r = await client.get("/metrics")
            assert r.status == 200
        finally:
            await client.close()

    asyncio.run(go())


def test_costs_endpoint_with_engine_serves_snapshot():
    from aiohttp.test_utils import TestClient, TestServer

    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane

    async def go():
        eng = make_engine()
        await eng.start()
        cp = build_control_plane(MCPXConfig())
        # The handler reads cp.planner.engine — the llm-planner attachment
        # point — and nothing else off the planner.
        cp.planner = SimpleNamespace(engine=eng)
        app = build_app(cp)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await eng.generate(eng.tokenizer.encode("x"), max_new_tokens=4)
            r = await client.get("/costs")
            assert r.status == 200
            body = await r.json()
            assert body["engine_state"] == "ready"
            assert body["engine"]["executables"]["prefill"]["compiles"] >= 1
            assert body["engine"]["totals"]["flops_executed"] > 0
            # Per-path kernel engagement (ISSUE 15): this engine forces
            # use_pallas=False, so every path reports the jnp route WITH
            # its blocking reason, and the decode path counted dispatches.
            pal = body["pallas"]
            assert set(pal["paths"]) == {"decode", "prefill", "spec_verify"}
            assert pal["enabled"] is False
            assert "use_pallas=false" in pal["reason"]
            assert pal["paths"]["decode"]["dispatches"] >= 1
            peaks = body["device"]["peaks"]
            assert "device_kind" in peaks and "n_devices" in peaks
            assert isinstance(body["device"]["hbm"], list)
            r = await client.get("/metrics")
            text = await r.text()
            assert "mcpx_engine_compiles_total" in text
        finally:
            await client.close()
            await eng.aclose()

    asyncio.run(go())
