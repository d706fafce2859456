"""Roofline cost observatory (mcpx/telemetry/costs.py): per-executable XLA
cost accounting, the mcpx_engine_compiles_total retrace sentinel, span
wiring, spec-rate gauges, and the GET /costs surface."""

import asyncio
import os
import re
import time
from types import SimpleNamespace

import numpy as np
import pytest

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


# ------------------------------------------------- what a compile cost (ISSUE 54)
def _sample(metrics: Metrics, name: str, labels: dict | None = None):
    return metrics.registry.get_sample_value(name, labels or {})


def test_a_new_signature_adds_its_seconds_and_a_known_one_adds_nothing():
    """``mcpx_engine_compile_seconds_total{executable}`` beside the compile
    counter: the first call at a signature stamps its wall (trace + lower +
    compile + dispatch), the path of a known signature stamps nothing."""
    import jax
    import jax.numpy as jnp

    metrics = Metrics()
    reg = CostRegistry(metrics=metrics)
    f = reg.wrap("toy", jax.jit(lambda x: (x * 3.0).sum()))
    labels = {"executable": "toy"}
    assert _sample(metrics, "mcpx_engine_compile_seconds_total", labels) is None
    assert float(f(jnp.ones((8,)))) == 24.0
    first = _sample(metrics, "mcpx_engine_compile_seconds_total", labels)
    assert first > 0.0 and _compiles(metrics, "toy") == 1.0
    f(jnp.ones((8,)))
    f(jnp.zeros((8,)))
    assert _sample(metrics, "mcpx_engine_compile_seconds_total", labels) == first
    f(jnp.ones((16,)))  # a retrace has its seconds too
    assert _sample(metrics, "mcpx_engine_compile_seconds_total", labels) > first
    # ... and the cost table's second lowering stamps its own wall.
    assert _sample(metrics, "mcpx_engine_cost_analysis_seconds_total") == 0.0
    reg.snapshot(materialize=True)
    assert _sample(metrics, "mcpx_engine_cost_analysis_seconds_total") > 0.0


def test_the_sentinels_log_lines_carry_the_seconds(caplog):
    import logging

    import jax
    import jax.numpy as jnp

    reg = CostRegistry(metrics=Metrics())
    f = reg.wrap("toy", jax.jit(lambda x: x + 1))
    with caplog.at_level(logging.INFO, logger="mcpx.costs"):
        f(jnp.ones((4,)))
        f(jnp.ones((5,)))
        reg.arm()
        f(jnp.ones((6,)))
    first, startup, retrace = [r for r in caplog.records if "toy" in r.getMessage()]
    assert "signature #1 in " in first.getMessage() and first.levelno == logging.INFO
    assert "(startup) in " in startup.getMessage() and " s: leaf[0]" in startup.getMessage()
    assert retrace.levelno == logging.WARNING and "RETRACED in the serving path" in retrace.getMessage()
    assert re.search(r"compile #3, \d+\.\d{3} s\): leaf\[0\]", retrace.getMessage())


# ------------------------------------------------- the start-up timeline (ISSUE 54)
JAX_EVENTS = {
    "lower_s": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "backend_s": "/jax/core/compile/backend_compile_duration",
    "cache_load_s": "/jax/compilation_cache/cache_retrieval_time_sec",
    "cache_requests": "/jax/compilation_cache/compile_requests_use_cache",
    "cache_hits": "/jax/compilation_cache/cache_hits",
    "cache_misses": "/jax/compilation_cache/cache_misses",
}


def _record(attr: str, amount: float) -> None:
    import jax.monitoring

    if attr.endswith("_s"):
        jax.monitoring.record_event_duration_secs(JAX_EVENTS[attr], amount)
    else:
        for _ in range(int(amount)):
            jax.monitoring.record_event(JAX_EVENTS[attr])


def test_a_process_start_is_read_off_proc_and_claimed_once():
    from mcpx.telemetry import startup

    t_proc = startup._process_start_monotonic()
    if os.path.exists("/proc/self/stat"):
        assert t_proc is not None and 0.0 < time.monotonic() - t_proc < 7 * 86400
    first, second = startup.StartupTimeline(), startup.StartupTimeline()
    # Whoever built this process's first timeline (an earlier test's engine,
    # or ``first``) holds the one startup.import; a later one never does.
    assert "startup.import" not in [s.name for s in second.record.spans]
    assert second.record.spans[1].name == "startup.build"
    assert [s.name for s in first.record.spans].count("startup.import") <= 1


def test_a_second_engine_in_one_process_writes_no_startup_import():
    engines = [make_engine(), make_engine()]
    names = [[s.name for s in e.startup.record.spans] for e in engines]
    assert "startup.import" not in names[1]
    assert names[1] == ["startup", "startup.build"]
    assert engines[1].startup.snapshot()["current"] == "startup.build"


@pytest.mark.parametrize("attr", list(JAX_EVENTS))
def test_an_event_lands_on_the_innermost_open_phase(attr):
    """The listeners' arithmetic without a compile: an event belongs to the
    phase open when it ends, a parent's sums include its children's, and an
    event with no phase open lands nowhere."""
    from mcpx.telemetry.startup import StartupTimeline

    tl = StartupTimeline()
    tl.end_build()
    _record(attr, 2)  # no phase open: counted under none
    with tl.phase("startup.warmup") as outer:
        _record(attr, 1)
        with tl.phase("warmup.prefill", A=1, T=64) as inner:
            _record(attr, 2)
            assert tl.snapshot()["current"] == "warmup.prefill"
        assert inner.attrs[attr] == 2 and tl.snapshot()["current"] == "startup.warmup"
    assert outer.attrs[attr] == 3 and (inner.attrs["A"], inner.attrs["T"]) == (1, 64)
    _record(attr, 2)
    assert tl.record.root.attrs[attr] == 3
    build = next(s for s in tl.record.spans if s.name == "startup.build")
    assert build.attrs[attr] == 0
    for sp in (outer, inner):
        wall = sp.t1 - sp.t0
        assert sp.attrs["other_s"] == pytest.approx(wall - sp.attrs["lower_s"] - sp.attrs["backend_s"])


def test_the_trace_event_is_not_summed():
    import jax.monitoring

    from mcpx.telemetry.startup import PHASE_ATTRS, StartupTimeline

    tl = StartupTimeline()
    tl.end_build()
    with tl.phase("startup.warmup") as sp:
        jax.monitoring.record_event_duration_secs("/jax/core/compile/jaxpr_trace_duration", 5.0)
        jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert [sp.attrs[k] for k in PHASE_ATTRS] == [0] * len(PHASE_ATTRS)


def test_a_real_compile_inside_a_phase_leaves_its_seconds_and_one_executable():
    """One new signature of a CPU jit inside an open phase: JAX's own events
    reach the phase (``backend_s`` > 0) and the registry's new signature is
    its one executable. The CPU backend gets no persistent cache, so hits and
    misses are the chip's to show."""
    import jax
    import jax.numpy as jnp

    from mcpx.telemetry.startup import StartupTimeline

    tl = StartupTimeline()
    tl.end_build()
    reg = CostRegistry(metrics=Metrics(), startup=tl)
    f = reg.wrap("toy", jax.jit(lambda x: jnp.tanh(x @ x.T).sum()))
    with tl.phase("startup.warmup") as sp:
        f(jnp.ones((24, 8)))
        f(jnp.ones((24, 8)))  # known: neither an executable nor an event
    f(jnp.ones((25, 8)))  # no phase open: under none
    assert sp.attrs["executables"] == 1 and tl.record.root.attrs["executables"] == 1
    assert sp.attrs["backend_s"] > 0.0 and sp.attrs["lower_s"] > 0.0
    assert sp.attrs["lower_s"] + sp.attrs["backend_s"] <= sp.t1 - sp.t0
    assert sp.attrs["cache_load_s"] == 0.0 and sp.attrs["cache_hits"] == 0


def test_finish_writes_the_gauges_once_and_leaves_the_hit_ratio_out_without_a_cache():
    from mcpx.telemetry.startup import StartupTimeline

    metrics = Metrics()
    tl = StartupTimeline(metrics)
    tl.end_build()
    with tl.phase("startup.warmup"):
        for T in (64, 128):
            with tl.phase("warmup.prefill", A=1, T=T):
                _record("lower_s", 0.25)
                _record("backend_s", 0.5)
        with tl.phase("warmup.cost_table"):
            pass
    assert _sample(metrics, "mcpx_startup_ready_seconds") is None  # absent, not 0, until started
    tl.finish()
    ready = _sample(metrics, "mcpx_startup_ready_seconds")
    assert ready == tl.ready_s > 0.0
    prefill = [s for s in tl.record.spans if s.name == "warmup.prefill"]
    assert _sample(metrics, "mcpx_startup_phase_seconds", {"phase": "warmup.prefill"}) == pytest.approx(
        sum(s.t1 - s.t0 for s in prefill))  # the per-bucket phases summed a kind
    assert _sample(metrics, "mcpx_startup_phase_seconds", {"phase": "warmup.cost_table"}) >= 0.0
    assert _sample(metrics, "mcpx_startup_warmup_jax_seconds", {"stage": "lower"}) == 0.5
    assert _sample(metrics, "mcpx_startup_warmup_jax_seconds", {"stage": "backend"}) == 1.0
    assert _sample(metrics, "mcpx_startup_executables") == 0.0
    assert _sample(metrics, "mcpx_startup_cache_events", {"event": "hit"}) == 0.0
    assert _sample(metrics, "mcpx_startup_cache_hit_ratio") is None
    tl.finish()  # constant after it
    assert _sample(metrics, "mcpx_startup_ready_seconds") == ready
    # ... and with a cache that was asked, the ratio is hits / (hits + misses).
    warm = StartupTimeline(Metrics())
    warm.end_build()
    with warm.phase("startup.warmup"):
        _record("cache_requests", 5)
        _record("cache_hits", 3)
        _record("cache_misses", 1)
    warm.finish()
    assert _sample(warm._metrics, "mcpx_startup_cache_hit_ratio") == 0.75
    assert _sample(warm._metrics, "mcpx_startup_cache_events", {"event": "miss"}) == 1.0


def test_an_engines_phases_tile_its_start_and_count_its_executables():
    """The worker's phases in order, tiling ``startup.build``'s end to the
    warm-up's; ``warmup.*`` tile ``startup.warmup``; the executables they
    count are the compile counter's."""

    async def go():
        eng = make_engine(warmup_compile=True, warmup_max_len=64)
        try:
            await eng.start()
            return eng, eng.startup.snapshot(), eng.metrics.render().decode()
        finally:
            await eng.aclose()

    eng, snap, text = asyncio.run(go())
    rows = snap["phases"]
    top = [r["name"] for r in rows if r["name"].startswith("startup.")]
    assert [n for n in top if n != "startup.import"] == [
        "startup.build", "startup.backend", "startup.weights", "startup.pools", "startup.warmup"]
    kinds = [r["name"] for r in rows if r["name"].startswith("warmup.")]
    assert kinds[0] == "warmup.grammar_tables" and kinds[-3:] == [
        "warmup.segment", "warmup.merge", "warmup.cost_table"]
    table = eng._cohort_table([64])  # warmup_max_len 64: one prefill bucket
    assert kinds.count("warmup.admit") == len(table)
    assert kinds.count("warmup.prefill") == sum(len(shapes) for shapes in table.values())
    assert snap["current"] is None and snap["ready_s"] is None  # no control plane said started
    warmup = next(r for r in rows if r["name"] == "startup.warmup")
    children = [r for r in rows if r["name"].startswith("warmup.")]
    assert sum(r["t1_s"] - r["t0_s"] for r in children) == pytest.approx(
        warmup["t1_s"] - warmup["t0_s"], rel=0.02, abs=0.02)
    assert sum(r["executables"] for r in children) == warmup["executables"]
    compiles = sum(v for k, v in _prom(text).items() if k.startswith("mcpx_engine_compiles_total{"))
    assert warmup["executables"] == compiles > 0
    for r in rows:
        assert r["lower_s"] + r["backend_s"] <= (r["t1_s"] - r["t0_s"]) + 0.002, r
        assert r["cache_load_s"] <= r["backend_s"]
    weights = next(r for r in rows if r["name"] == "startup.weights")
    assert _prom(text)["mcpx_engine_weights_init_seconds"] == pytest.approx(
        weights["t1_s"] - weights["t0_s"], abs=0.002)  # one pair of stamps, two names
    assert snap["cache"] == {"dir": None, "files": 0, "bytes": 0, "max_bytes": snap["cache"]["max_bytes"]}


def _prom(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def test_the_control_plane_appends_its_phase_and_serves_the_timeline():
    """``ControlPlane.startup`` appends ``startup.registry_grammar`` and
    finishes the timeline at ``started``; a failed warm ends the phase with
    ``error=true`` and the exception's type, so /healthz's ``startup`` and
    ``warm_error`` agree; GET /traces/startup serves both formats."""
    from aiohttp.test_utils import TestClient, TestServer

    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane

    async def go():
        eng = make_engine()

        async def warm(registry):
            raise RuntimeError("trie too wide")

        cp = build_control_plane(MCPXConfig())
        r404 = None
        cp.planner = SimpleNamespace(engine=eng, ensure_ready=eng.start, warm=warm)
        client = TestClient(TestServer(build_app(cp)))
        await client.start_server()  # on_startup launches cp.startup()
        try:
            for _ in range(3000):
                if cp.started:
                    break
                await asyncio.sleep(0.02)
            health = await (await client.get("/healthz")).json()
            tree = await (await client.get("/traces/startup")).json()
            chrome = await (await client.get("/traces/startup?format=chrome")).json()
            listed = await (await client.get("/traces")).json()
            cp.planner = SimpleNamespace()
            r404 = (await client.get("/traces/startup")).status
            return health, tree, chrome, listed, r404, eng.metrics
        finally:
            await client.close()
            await eng.aclose()

    health, tree, chrome, listed, r404, metrics = asyncio.run(go())
    st = health["startup"]
    assert health["started"] is True and "RuntimeError: trie too wide" in health["warm_error"]
    last = st["phases"][-1]
    assert last["name"] == "startup.registry_grammar"
    assert last["error"] is True and last["error_type"] == "RuntimeError"
    assert st["current"] is None and st["ready_s"] > 0.0
    assert st["ready_s"] == pytest.approx(_sample(metrics, "mcpx_startup_ready_seconds"), abs=0.001)
    assert st["t0_unix"] == pytest.approx(tree["started_at"], abs=0.01) and st["t0_unix"] < time.time()
    assert tree["name"] == "startup" and tree["tree"][0]["parent_id"] is None
    failed = next(s for s in tree["tree"] if s["name"] == "startup.registry_grammar")
    assert failed["status"] == "error" and failed["attrs"]["error_type"] == "RuntimeError"
    assert {e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"} >= {
        "startup", "startup.build", "startup.weights", "startup.registry_grammar"}
    assert listed["traces"] == []  # kept outside the sampled ring
    assert r404 == 404
    assert _sample(metrics, "mcpx_startup_phase_seconds", {"phase": "registry_grammar"}) >= 0.0


def test_events_from_many_threads_lose_no_update():
    """The listeners run on whichever thread compiled while phases open and
    close on others: sums under the timeline's lock, no lost update."""
    import sys
    import threading

    from mcpx.telemetry.startup import StartupTimeline

    tl = StartupTimeline()
    tl.end_build()
    n_threads, n_events = 16, 400
    stop = threading.Event()

    def churn():  # phases opening and closing under the one that counts
        while not stop.is_set():
            tl.end(tl.begin("warmup.prefill", A=1, T=64))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with tl.phase("startup.warmup") as sp:
            workers = [threading.Thread(target=lambda: [_record("cache_hits", 1) for _ in range(n_events)])
                       for _ in range(n_threads)]
            churner = threading.Thread(target=churn)
            churner.start()
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=60)
            stop.set()
            churner.join(timeout=60)
            assert not churner.is_alive() and not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    assert sp.attrs["cache_hits"] == n_threads * n_events == tl.record.root.attrs["cache_hits"]
