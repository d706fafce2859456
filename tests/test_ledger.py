"""Per-request cost ledger & per-tenant usage attribution (ISSUE 14):
bill itemization, bounded tenant fold, the conservation contracts (tenant
roll-ups exactly sum member bills; >= 95% of a traced request's wall
attributed; FLOP apportionment sums to the engine's harvested totals),
and ledger-off pass-through parity on the engine and the server."""

import asyncio
import json
import math
import random

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.telemetry import ledger as ledger_mod
from mcpx.telemetry.ledger import (
    RequestBill,
    UsageLedger,
    count_tool_attempts,
)


def _lcfg(**kw):
    cfg = MCPXConfig.from_dict(
        {"telemetry": {"ledger": {"enabled": True, **kw}}}
    )
    return cfg.telemetry.ledger


# ------------------------------------------------------------------- bill
def test_bill_itemization_finalize_and_to_dict():
    bill = RequestBill(tenant="acme", endpoint="/plan")
    bill.sched_queue_ms += 5.0
    bill.add_engine(
        {
            "engine_queue_ms": 2.0, "prefill_ms": 10.0, "decode_ms": 80.0,
            "prefill_tokens": 30, "prefix_saved_tokens": 16,
            "decode_tokens": 12, "decode_forwards": 12,
            "spec_accepted_tokens": 4, "spill_copy_tokens": 16,
            "kv_page_seconds": 0.5, "flops": 1e9, "hbm_bytes": 2e9,
        }
    )
    # A replanning request generates twice and pays for both.
    bill.add_engine({"decode_ms": 20.0, "decode_tokens": 3, "flops": 1e8})
    bill.note_plan(120.0, 112.0)  # plan wall minus what the engine billed
    bill.add_tools(
        {"nodes": [{"attempts": [
            {"kind": "primary", "status": "error"},
            {"kind": "retry", "status": "ok"},
            {"kind": "hedge", "status": "cancelled"},
        ]}]},
        40.0,
    )
    bill.finalize(status="ok", total_ms=200.0)
    assert bill.generates == 2
    assert bill.decode_tokens == 15
    assert bill.flops == pytest.approx(1.1e9)
    assert bill.tool_attempts == 3
    assert bill.tool_attempts_by_kind == {"primary": 1, "retry": 1, "hedge": 1}
    attributed = 5.0 + 2.0 + 10.0 + (80.0 + 20.0) + 8.0 + 40.0  # = 165
    assert bill.attributed_ms() == pytest.approx(attributed)
    d = bill.to_dict()
    assert d["other_ms"] == pytest.approx(200.0 - attributed, abs=1e-6)
    assert d["attributed_frac"] == pytest.approx(attributed / 200.0, abs=1e-3)
    json.dumps(d)  # bills ride spans/bundles: must stay serializable


def test_count_tool_attempts_survives_malformed_traces():
    assert count_tool_attempts(None) == {}
    assert count_tool_attempts({"nodes": "garbage"}) == {}
    assert count_tool_attempts({"nodes": [{"attempts": [None, 7]}]}) == {}
    assert count_tool_attempts(
        {"nodes": [{"attempts": [{"kind": "fallback"}]}, "junk"]}
    ) == {"fallback": 1}


def test_contextvar_activate_deactivate():
    assert ledger_mod.current_bill() is None
    bill = RequestBill()
    token = ledger_mod.activate(bill)
    assert ledger_mod.current_bill() is bill
    ledger_mod.deactivate(token)
    assert ledger_mod.current_bill() is None


# ---------------------------------------------------------------- usage fold
def test_usage_ledger_folds_tenant_cardinality():
    led = UsageLedger(_lcfg(max_tenants=2))
    for i, tenant in enumerate(["a", "b", "c", "d", "a"]):
        bill = RequestBill(tenant=tenant)
        bill.add_engine({"decode_tokens": i})
        bill.finalize(status="ok", total_ms=1.0)
        led.observe(bill)
    snap = led.snapshot()
    assert set(snap["tenants"]) == {"a", "b", "other"}
    assert snap["tenants"]["other"]["requests"] == 2  # c + d folded
    assert snap["totals"]["requests"] == 5


def test_tenant_rollups_exactly_sum_member_bills():
    """Conservation (ISSUE 14 acceptance): per-tenant ledger totals equal
    the sum of member request bills — property-tested over seeded
    mixed-tenant traffic, exact float equality (same fold, same order)."""
    rng = random.Random(1234)
    led = UsageLedger(_lcfg(max_tenants=8, recent=512))
    tenants = ["t0", "t1", "t2", "t3", "t4"]
    bills: list[RequestBill] = []
    for _ in range(300):
        bill = RequestBill(
            tenant=rng.choice(tenants), endpoint="/plan",
            degraded=rng.random() < 0.2,
        )
        bill.sched_queue_ms += rng.uniform(0, 5)
        for _g in range(rng.randint(1, 3)):
            bill.add_engine(
                {
                    "engine_queue_ms": rng.uniform(0, 2),
                    "prefill_ms": rng.uniform(0, 20),
                    "decode_ms": rng.uniform(0, 200),
                    "prefill_tokens": rng.randint(0, 64),
                    "prefix_saved_tokens": rng.randint(0, 32),
                    "decode_tokens": rng.randint(1, 48),
                    "decode_forwards": rng.randint(1, 48),
                    "flops": rng.uniform(0, 1e9),
                    "hbm_bytes": rng.uniform(0, 1e9),
                    "kv_page_seconds": rng.uniform(0, 3),
                }
            )
        bill.note_plan(rng.uniform(0, 50), rng.uniform(0, 10))
        bill.finalize(status="ok", total_ms=rng.uniform(1, 400))
        led.observe(bill)
        bills.append(bill)
    snap = led.snapshot()
    assert len(snap["recent"]) == 300  # ring big enough: every bill audited
    for tenant in set(b.tenant for b in bills):
        member = [b for b in bills if b.tenant == tenant]
        acct = led.tenant_totals(tenant)
        assert acct["requests"] == len(member)
        for key in ("decode_tokens", "prefill_tokens", "decode_forwards"):
            assert acct[key] == sum(getattr(b, key) for b in member), (
                tenant, key,
            )
        # Float items: the ledger folds += in completion order, the exact
        # order this loop replays — raw equality is EXACT, bit for bit.
        # (Not builtin sum(): Python 3.12 compensates float sums, so it no
        # longer equals a plain += fold in the last bits.)
        for key in ("flops", "hbm_bytes", "decode_ms", "kv_page_seconds"):
            folded = 0.0
            for b in member:
                folded += getattr(b, key)
            assert acct[key] == folded, (tenant, key)
    # Grand totals equal the tenant sums.
    for key in ("requests", "decode_tokens"):
        assert snap["totals"][key] == sum(
            a[key] for a in snap["tenants"].values()
        )


# ------------------------------------------------------------- engine side
def _engine_cfg(ledger_on: bool, **engine_overrides):
    return MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 4,
                "max_decode_len": 24,
                "kv_page_size": 16,
                "max_pages_per_seq": 16,
                "temperature": 0.0,
                **engine_overrides,
            },
            "telemetry": {"ledger": {"enabled": ledger_on}},
        }
    )


def test_engine_bills_conserve_flops_and_off_is_pass_through():
    """Engine acceptance: concurrent mixed-tenant generates produce bills
    whose FLOPs/HBM bytes sum EXACTLY to the engine's apportioned totals
    (which mirror the cost observatory's harvested per-call costs, split
    per executable); with the ledger off, outputs are byte-identical,
    GenerateResult.bill is None, and queue_stats is untouched."""
    from mcpx.engine.engine import InferenceEngine

    async def run(ledger_on: bool):
        eng = InferenceEngine(_engine_cfg(ledger_on))
        await eng.start()
        try:
            prompts = [
                eng.tokenizer.encode(f"plan request number {i}")
                for i in range(6)
            ]
            results = await asyncio.gather(
                *(
                    eng.generate(
                        p, max_new_tokens=16, constrained=False,
                        tenant=f"t{i % 3}",
                    )
                    for i, p in enumerate(prompts)
                )
            )
            return results, eng.ledger_totals(), dict(eng.queue_stats())
        finally:
            await eng.aclose()

    async def go():
        res_on, totals_on, qs_on = await run(True)
        res_off, totals_off, qs_off = await run(False)
        # Pass-through parity: byte-identical tokens, same queue_stats
        # surface, no bill, nothing apportioned.
        assert [r.token_ids for r in res_on] == [r.token_ids for r in res_off]
        assert all(r.bill is None for r in res_off)
        assert totals_off == {"flops": 0.0, "bytes": 0.0, "by_executable": {}}
        assert qs_on.keys() == qs_off.keys()
        # Every billed request carries the itemized engine bill.
        bills = [r.bill for r in res_on]
        assert all(b is not None for b in bills)
        for r, b in zip(res_on, bills):
            assert b["decode_tokens"] == r.generated_tokens
            assert b["prefill_tokens"] > 0
            assert b["decode_forwards"] > 0
            assert b["kv_pages"] > 0 and b["kv_page_seconds"] > 0
            assert b["engine_queue_ms"] == pytest.approx(r.queue_ms)
            assert b["decode_ms"] == pytest.approx(r.decode_ms)
        # FLOP/HBM conservation: sum of bills == the apportioned totals ==
        # the per-executable split (within float rounding).
        assert totals_on["flops"] > 0
        assert math.isclose(
            sum(b["flops"] for b in bills), totals_on["flops"],
            rel_tol=1e-9, abs_tol=1.0,
        )
        assert math.isclose(
            sum(b["hbm_bytes"] for b in bills), totals_on["bytes"],
            rel_tol=1e-9, abs_tol=1.0,
        )
        assert math.isclose(
            sum(totals_on["by_executable"].values()), totals_on["flops"],
            rel_tol=1e-9, abs_tol=1.0,
        )
        # The decode/prefill executables both contributed.
        assert any("prefill" in k for k in totals_on["by_executable"])
        assert any("segment" in k for k in totals_on["by_executable"])

    asyncio.run(go())


def test_engine_prefix_reuse_bills_saved_tokens():
    """A second request sharing a prompt head bills prefix_saved_tokens
    (tokens served from radix KV) and a smaller suffix prefill."""
    from mcpx.engine.engine import InferenceEngine

    async def go():
        eng = InferenceEngine(_engine_cfg(True))
        await eng.start()
        try:
            base = eng.tokenizer.encode(
                "shared planner header with a long common prompt prefix. "
            )
            a = await eng.generate(
                base + eng.tokenizer.encode("first suffix"),
                max_new_tokens=8, constrained=False,
            )
            b = await eng.generate(
                base + eng.tokenizer.encode("second suffix"),
                max_new_tokens=8, constrained=False,
            )
            assert a.bill["prefix_saved_tokens"] == 0
            assert b.bill["prefix_saved_tokens"] > 0
            assert b.bill["prefill_tokens"] < a.bill["prefill_tokens"]
        finally:
            await eng.aclose()

    asyncio.run(go())


# ---------------------------------------------------------- full-stack e2e
def test_traced_request_wall_conservation_full_stack():
    """ISSUE 14 acceptance: for a traced /plan through the real stack
    (LLM planner, engine, middleware), the bill's wall-time parts sum to
    >= 95% of the root span's wall, the bill rides the root span, and the
    tenant roll-up at GET /usage matches the recent bills."""
    from aiohttp.test_utils import TestClient, TestServer

    from mcpx.engine.engine import InferenceEngine
    from mcpx.planner.llm import LLMPlanner
    from mcpx.registry.base import ServiceRecord
    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane

    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 4,
                "max_decode_len": 48,
                "max_pages_per_seq": 16,
                "temperature": 0.0,
            },
            "planner": {"kind": "llm", "plan_cache_size": 0},
            "telemetry": {"ledger": {"enabled": True}},
        }
    )
    eng = InferenceEngine(cfg)
    cp = build_control_plane(cfg, planner=LLMPlanner(eng, cfg.planner))
    app = build_app(cp)

    async def go():
        for i in range(3):
            await cp.registry.put(
                ServiceRecord(
                    name=f"svc{i}",
                    endpoint=f"local://svc{i}",
                    description=f"fetch and summarize topic {i} data",
                    input_schema={"q": "str"},
                    output_schema={"data": "str"},
                )
            )
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # Warm once (grammar build, first-compile tails), then measure.
            r = await client.post(
                "/plan", json={"intent": "fetch data warmup"}
            )
            assert r.status == 200, await r.text()
            r = await client.post(
                "/plan",
                json={"intent": "fetch and summarize topic data"},
                headers={"X-MCPX-Tenant": "acme"},
            )
            assert r.status == 200, await r.text()
            trace_id = r.headers["X-Trace-Id"]
            rec = cp.tracer.get(trace_id)
            assert rec is not None
            root = rec.spans[0]
            bill = root.attrs.get("bill")
            assert bill is not None, "bill missing from root span attrs"
            assert bill["tenant"] == "acme"
            assert bill["decode_tokens"] > 0
            # Conservation: >= 95% of the root span's wall is itemized.
            parts = (
                bill["sched_queue_ms"] + bill["engine_queue_ms"]
                + bill["prefill_ms"] + bill["decode_ms"]
                + bill["plan_other_ms"] + bill["tool_ms"]
            )
            assert bill["total_ms"] == pytest.approx(rec.total_ms, rel=0.05)
            assert parts >= 0.95 * rec.total_ms, (
                f"attributed {parts:.1f}ms of {rec.total_ms:.1f}ms "
                f"({parts / rec.total_ms:.2%}): {bill}"
            )
            # The tenant roll-up equals the member bills at GET /usage.
            usage = await (await client.get("/usage")).json()
            acme = usage["tenants"]["acme"]
            member = [
                b for b in usage["recent"] if b["tenant"] == "acme"
            ]
            assert acme["requests"] == len(member) == 1
            assert acme["decode_tokens"] == sum(
                b["decode_tokens"] for b in member
            )
            assert acme["flops"] == pytest.approx(
                sum(b["flops"] for b in member), rel=1e-9
            )
        finally:
            await client.close()

    asyncio.run(go())


def test_server_ledger_off_is_pass_through():
    """Default config: cp.ledger is None, /usage answers enabled:false,
    responses carry no billing artifacts."""
    from aiohttp.test_utils import TestClient, TestServer

    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane

    cp = build_control_plane(MCPXConfig())
    assert cp.ledger is None and cp.slo is None
    app = build_app(cp)

    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/usage")
            assert resp.status == 200
            assert await resp.json() == {"enabled": False}
            resp = await client.get("/slo")
            assert resp.status == 200
            assert await resp.json() == {"enabled": False}
        finally:
            await client.close()

    asyncio.run(go())
