#!/usr/bin/env python
"""Baseline config ladder — one run per BASELINE.json scenario.

The reference publishes no numbers (SURVEY.md §6); the operative baseline is
the driver-defined config ladder. Each scenario drives the REAL server stack
(aiohttp app, retrieval shortlist, grammar-constrained batched decode,
concurrent orchestrator over in-process fake microservices) and prints one
JSON line:

    {"config": N, "desc": ..., "value": ..., "unit": ..., ...}

Configs (BASELINE.json "configs"):
  1. single-intent /plan -> linear DAG          (3-service registry)
  2. /plan_and_execute, per-node retry+fallback (10-service registry)
  3. batched /plan bs=32, top-k retrieval       (100-service registry)
  4. telemetry-adaptive replanning loop
  5. 256-concurrent /plan_and_execute fan-out   (1k-service registry)

Model: "2b" on TPU, "test" on CPU (MCPX_BENCH_MODEL overrides).
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time

# Runnable as `python benchmarks/ladder.py` from the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _pallas_on, _serving_announced

if int(os.environ.get("MCPX_LADDER_CPU", "0")) > 0:
    # Arm an N-device virtual CPU platform through the shared recipe. Off
    # this path the parent (_main_isolated) imports only stdlib bench
    # symbols and never touches jax: a chip belongs to one process at a
    # time, and each config's child is that process.
    from __graft_entry__ import _force_virtual_cpu

    _force_virtual_cpu(int(os.environ["MCPX_LADDER_CPU"]))


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() not in ("cpu",)


def _config(model_size: str, max_batch: int = 32, checkpoint: str = "",
            shortlist_top_k: int = 8):
    from mcpx.core.config import MCPXConfig

    _serving_announced(max_batch, "ladder _config", tag="ladder")
    return MCPXConfig.from_dict(
        {
            # Same serving vocab as bench.py: in-tree BPE (models/bpe.py).
            "model": {"size": model_size, "max_seq_len": 2048, "vocab": "bpe",
                      "checkpoint_path": checkpoint},
            "engine": {
                "max_batch_size": max_batch,
                # SAME geometry as bench.py's BPE config (decode budget 64,
                # 4 x 64-token pages): every (batch, len) bucket executable
                # then comes out of the persistent XLA compilation cache the
                # headline bench already filled — a divergent geometry cost
                # config 3 of the r5 TPU ladder ~13 min of recompiles.
                "max_decode_len": 64,
                "kv_page_size": 64,
                "max_pages_per_seq": 4,
                "temperature": 0.0,
                # bench._pallas_on: the MCPX_BENCH_PALLAS gate — one
                # definition of the knob, not a re-parse per script;
                # announced via the shared bench._serving_announced above.
                "use_pallas": _pallas_on(),
                "warmup_compile": _on_tpu(),
            },
            "planner": {"kind": "llm", "max_plan_retries": 0,
                        "shortlist_top_k": shortlist_top_k},
        }
    )


class _Stack:
    """Server + registry + fake local microservices for one scenario."""

    def __init__(self, n_services: int, model: str, *, fail: dict | None = None,
                 checkpoint: str = "", registry_seed: int = 7,
                 shortlist_top_k: int = 8):
        self.n_services = n_services
        self.model = model
        self.fail = fail or {}  # name -> "once" | "always"
        self.checkpoint = checkpoint
        self.registry_seed = registry_seed
        self.shortlist_top_k = shortlist_top_k

    async def __aenter__(self):
        from aiohttp.test_utils import TestServer

        from mcpx.orchestrator.transport import TransportError
        from mcpx.server.app import build_app
        from mcpx.server.factory import build_control_plane
        from mcpx.utils.synth import synth_registry

        self.cp = build_control_plane(
            _config(self.model, checkpoint=self.checkpoint,
                    shortlist_top_k=self.shortlist_top_k))
        self.records = synth_registry(self.n_services, seed=self.registry_seed)
        calls: dict[str, int] = {}

        def handler_for(name: str, mode: str | None):
            async def handler(payload):
                calls[name] = calls.get(name, 0) + 1
                if mode == "always" or (mode == "once" and calls[name] == 1):
                    raise TransportError(f"{name} injected failure")
                return {"service": name, "ok": True}

            return handler

        local = self.cp.orchestrator._transport.local
        for rec in self.records:
            await self.cp.registry.put(rec)
            local.register(rec.name, handler_for(rec.name, self.fail.get(rec.name)))
            for fb in rec.fallbacks:
                fb_name = fb.removeprefix("local://")
                # Fallbacks honour the fail map too — otherwise a "downed"
                # service recovers at the orchestrator level (its fallback
                # succeeds) and the retry/replan machinery is never reached.
                local.register(fb_name, handler_for(fb_name, self.fail.get(fb_name)))
        self.server = TestServer(build_app(self.cp))
        await self.server.start_server()
        self.base = f"http://{self.server.host}:{self.server.port}"

        import aiohttp

        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=512)
        )
        try:
            # Wait for background engine bring-up (bounded — a wedged
            # startup must fail the scenario, not hang the ladder), then one
            # warmup round so no XLA compile lands in the timed region.
            deadline = time.monotonic() + 1200
            while True:
                async with self.session.get(f"{self.base}/healthz") as r:
                    h = await r.json()
                if h.get("engine") in ("ready", "n/a"):
                    break
                if h.get("engine") == "failed":
                    raise RuntimeError("engine failed during startup")
                if time.monotonic() > deadline:
                    raise RuntimeError("engine startup timed out")
                await asyncio.sleep(0.5)
            bs = self.cp.config.engine.max_batch_size
            await asyncio.gather(*(self.plan(f"warmup {i}") for i in range(bs)))
        except BaseException:
            await self.__aexit__()
            raise
        return self

    async def __aexit__(self, *exc):
        await self.session.close()
        await self.server.close()
        engine = getattr(self.cp.planner, "engine", None)
        if engine is not None and engine.state == "ready":
            await engine.aclose()

    async def plan(self, intent: str) -> dict:
        async with self.session.post(f"{self.base}/plan", json={"intent": intent}) as r:
            return {"status": r.status, **(await r.json())}

    def counter(self, name: str) -> float:
        c = getattr(self.cp.metrics, name)
        return c._value.get()

    async def plan_and_execute(self, intent: str, payload: dict) -> dict:
        async with self.session.post(
            f"{self.base}/plan_and_execute", json={"intent": intent, "payload": payload}
        ) as r:
            return {"http": r.status, **(await r.json())}


async def _seed_plan(cp, intent: str, names: list[str]) -> None:
    """Pre-seed the plan cache with a crafted linear plan over ``names`` so
    plan_and_execute(intent) deterministically executes those services —
    random-weight LLM decodes cannot be steered onto a specific service, and
    the retry/fallback/replan machinery only engages when the injected
    service is actually in the executed plan."""
    from mcpx.core.dag import Plan

    wire = {
        "nodes": [
            {"name": n, "service": n, "endpoint": f"local://{n}", "inputs": {}}
            for n in names
        ],
        "edges": [
            {"from": a, "to": b} for a, b in zip(names, names[1:])
        ],
    }
    plan = Plan.from_wire(wire)
    plan.intent = intent
    plan.origin = "seeded"
    cp._cache_put((intent, await cp.registry.version()), plan)


def _emit(config: int, desc: str, value, unit: str, **extra):
    print(
        json.dumps(
            {"config": config, "desc": desc, "value": round(value, 2), "unit": unit, **extra}
        ),
        flush=True,
    )


async def config1(model: str) -> None:
    """Single-intent /plan over a 3-service registry: p50 latency."""
    async with _Stack(3, model) as st:
        lat = []
        nodes = llm = 0
        for i in range(24):
            t0 = time.monotonic()
            res = await st.plan(f"fetch auth data then enrich the user record [{i}]")
            lat.append((time.monotonic() - t0) * 1e3)
            assert res["status"] == 200, res
            nodes = max(nodes, len(res["graph"]["nodes"]))
            llm += res.get("origin") == "llm"
        _emit(1, "single /plan p50 (3 services)", statistics.median(lat), "ms",
              max_plan_nodes=nodes, llm_share=llm / 24)


async def config2(model: str) -> None:
    """/plan_and_execute with retry + ordered fallback on a 10-service registry."""
    from mcpx.utils.synth import synth_registry

    records = synth_registry(10, seed=7)
    # One flaky service (first call fails -> retry) and one hard-down service
    # that has a declared fallback endpoint.
    flaky = records[0].name
    downed = next((r.name for r in records if r.fallbacks), records[1].name)
    async with _Stack(10, model, fail={flaky: "once", downed: "always"}) as st:
        # Mentioning the injected services steers retrieval's shortlist so
        # plans actually include them (random-weight decodes pick among the
        # shortlisted names).
        ok = retries = fallbacks = 0
        lat = []
        healthy = next(r.name for r in records
                       if r.name not in (flaky, downed) and not r.fallbacks)
        payload = {k: "x" for k in
                   ("query", "user_id", "order_id", "document", "text", "items", "amount",
                    "address", "score", "status", "report", "features", "vector", "summary")}
        for i in range(12):
            t0 = time.monotonic()
            intent = f"use {flaky} then {downed} then report [{i}]"
            await _seed_plan(st.cp, intent, [flaky, downed, healthy])
            res = await st.plan_and_execute(intent, payload)
            lat.append((time.monotonic() - t0) * 1e3)
            ok += res.get("status") in ("ok", "partial")
            for node in (res.get("trace") or {}).get("nodes", []):
                kinds = [a["kind"] for a in node.get("attempts", [])]
                retries += "retry" in kinds
                fallbacks += "fallback" in kinds
        _emit(2, "plan_and_execute p50 w/ retry+fallback (10 services)",
              statistics.median(lat), "ms", ok=ok, total=12, ok_rate=ok / 12,
              plan_source="seeded-cache (deterministic injection coverage)",
              retries_exercised=retries, fallbacks_exercised=fallbacks)


async def config3(model: str) -> None:
    """Batched /plan bs=32 with top-k retrieval over 100 services."""
    import random

    from mcpx.utils.synth import intent_for

    async with _Stack(100, model) as st:
        rng = random.Random(3)
        intents = [f"{intent_for(st.records, rng)} [{i}]" for i in range(96)]
        fwd0, tok0 = st.counter("decode_forwards"), st.counter("decode_tokens")
        t0 = time.monotonic()
        results = await asyncio.gather(*(st.plan(i) for i in intents))
        dt = time.monotonic() - t0
        assert all(r["status"] == 200 for r in results)
        llm = sum(r.get("origin") == "llm" for r in results)
        fwd = st.counter("decode_forwards") - fwd0
        tok = st.counter("decode_tokens") - tok0
        # Batching proof: with a shared slab + speculation, model forwards
        # must be far fewer than requests (96 serial unbatched plans would
        # need >= 96 * min-plan-length forwards). A regression to serial
        # decoding fails here rather than shipping a slow-but-green number.
        assert fwd < len(intents) * 4, (
            f"batching regressed: {fwd} forwards for {len(intents)} plans")
        # Quality of the served plans vs their intents (suffix stripped:
        # the cache-busting " [i]" tag is not intent content).
        from mcpx.planner.quality import mean_quality, plan_quality

        by_name = {r.name: r for r in st.records}
        q = mean_quality(
            plan_quality(r.get("graph") or {}, intent.rsplit(" [", 1)[0], by_name)
            for intent, r in zip(intents, results)
        )
        _emit(3, "batched /plan throughput, top-k retrieval (100 services)",
              len(intents) / dt, "plans/s", concurrency=96,
              engine_batch=st.cp.config.engine.max_batch_size,
              llm_share=llm / len(intents), decode_forwards=int(fwd),
              tok_per_forward=round(tok / max(1.0, fwd), 2),
              quality=round(q["score"], 3),
              quality_coverage=round(q["coverage"], 3))


async def config4(model: str) -> None:
    """Telemetry-adaptive replanning: a degraded service gets planned around."""
    from mcpx.utils.synth import synth_registry

    records = synth_registry(10, seed=7)
    # A service that is hard-down INCLUDING its declared fallback: only the
    # telemetry-driven replan can route around it (baseline config 4).
    bad_rec = next((r for r in records if r.fallbacks), records[2])
    bad = bad_rec.name
    fails = {bad: "always"}
    for fb in bad_rec.fallbacks:
        fails[fb.removeprefix("local://")] = "always"
    async with _Stack(10, model, fail=fails) as st:
        payload = {"query": "q", "user_id": "u", "items": "i", "document": "d",
                   "amount": "1", "report": "r", "score": "s", "text": "t"}
        recovered = replans = 0
        n = 10
        healthy = next(r.name for r in records if r.name not in fails)
        for i in range(n):
            intent = f"use {bad} to enrich order data then report it [{i}]"
            # Seeded plan includes the hard-down service (fallback also down):
            # only a telemetry-driven replan around it can succeed.
            await _seed_plan(st.cp, intent, [bad, healthy])
            res = await st.plan_and_execute(intent, payload)
            replans += res.get("replans", 0)
            recovered += res.get("status") == "ok" and res.get("replans", 0) > 0
        _emit(4, "telemetry-adaptive replanning (degraded service)",
              replans, "replans", recovered_requests=recovered, requests=n)


async def config5(model: str) -> None:
    """256 concurrent /plan_and_execute fan-out/fan-in over 1k services."""
    import random

    from mcpx.utils.synth import intent_for

    async with _Stack(1000, model) as st:
        rng = random.Random(5)
        payload = {k: "x" for k in
                   ("query", "user_id", "order_id", "document", "text", "items", "amount",
                    "address", "score", "status", "report", "features", "vector", "summary")}
        intents = [f"{intent_for(st.records, rng, 4)} fan out and merge [{i}]"
                   for i in range(256)]
        t0 = time.monotonic()
        results = await asyncio.gather(
            *(st.plan_and_execute(i, payload) for i in intents)
        )
        dt = time.monotonic() - t0
        ok = sum(r.get("status") in ("ok", "partial") for r in results)
        llm = sum(r.get("origin") == "llm" for r in results)
        http_ok = sum(r.get("http") == 200 for r in results)
        # llm_share over ANSWERED requests: a closed-loop tail that trips the
        # server's request timeout (CPU-speed artifact) has no origin at all
        # and must not masquerade as a heuristic fallback.
        _emit(5, "256-concurrent plan_and_execute (1k services)",
              len(intents) / dt, "req/s", ok=ok, total=len(intents),
              http_ok=http_ok, ok_rate=ok / max(1, http_ok),
              llm_share=llm / max(1, http_ok))


async def config6(model: str) -> None:
    """Beyond the BASELINE set: plan quality of the committed TRAINED
    planner checkpoint through the served stack (random weights score the
    registry base rate here — VERDICT r3 next #3). Skips with a stub line
    when no artifact is committed. Always serves the tiny trained model
    (the checkpoint is size 'test'), whatever the ladder's headline model."""
    import random

    from mcpx.planner.quality import mean_quality, plan_quality
    from mcpx.utils.synth import intent_for

    # One source of truth for the artifact path + override (bench.py's).
    from bench import _TRAINED_CKPT

    ckpt = os.environ.get("MCPX_BENCH_QUALITY_CHECKPOINT", _TRAINED_CKPT)
    if not os.path.exists(ckpt):
        _emit(6, "trained-checkpoint plan quality (extra)", 0, "score",
              skipped="no committed checkpoint")
        return
    # registry_seed=0 and shortlist_top_k=6: the registry and prompt
    # geometry this checkpoint was trained to serve (models/corpus.py — a
    # deployment artifact, like the grammar); intents are fresh draws.
    async with _Stack(
        1000, "test", checkpoint=ckpt, registry_seed=0, shortlist_top_k=6
    ) as st:
        rng = random.Random(99)
        by_name = {r.name: r for r in st.records}
        rows, llm = [], 0
        for i in range(32):
            intent = intent_for(st.records, rng, rng.randint(2, 4))
            r = await st.plan(f"{intent} [{i}]")
            assert r["status"] == 200
            llm += r.get("origin") == "llm"
            rows.append(plan_quality(r.get("graph") or {}, intent, by_name))
        # Honesty gate: the heuristic fallback IS the training teacher, so
        # a broken checkpoint load would otherwise emit the teacher's high
        # score while never exercising the model.
        assert llm / 32 >= 0.95, (
            f"trained-quality degenerate: llm_share={llm / 32:.2f} — plans came "
            "from the heuristic fallback (the teacher), not the checkpoint")
        q = mean_quality(rows)
        _emit(6, "trained-checkpoint plan quality (extra)", q["score"], "score",
              coverage=round(q["coverage"], 3), relevance=round(q["relevance"], 3),
              coherence=round(q["coherence"], 3), n=q["n"], llm_share=llm / 32)


CONFIGS = [config1, config2, config3, config4, config5, config6]


async def main() -> None:
    model = os.environ.get("MCPX_BENCH_MODEL") or ("2b" if _on_tpu() else "test")
    only = os.environ.get("MCPX_LADDER_ONLY")
    for i, cfg in enumerate(CONFIGS, start=1):
        if only and str(i) not in only.split(","):
            continue
        await cfg(model)


def _main_isolated() -> None:
    """Run each config in its own subprocess: every scenario boots a fresh
    multi-GB engine, and per-process isolation is what guarantees HBM comes
    back between scenarios."""
    import subprocess

    only = os.environ.get("MCPX_LADDER_ONLY")
    ids = only.split(",") if only else [str(i) for i in range(1, len(CONFIGS) + 1)]
    failures = 0
    for i in ids:
        env = dict(os.environ, MCPX_LADDER_ONLY=i, MCPX_LADDER_CHILD="1")
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env)
        failures += proc.returncode != 0
    if failures:
        raise SystemExit(f"{failures}/{len(ids)} ladder configs failed")


if __name__ == "__main__":
    if os.environ.get("MCPX_LADDER_CHILD"):
        asyncio.run(main())
    else:
        _main_isolated()
