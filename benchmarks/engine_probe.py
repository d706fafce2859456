#!/usr/bin/env python
"""Direct-engine probe: drive InferenceEngine with concurrent constrained
requests (no HTTP server, no retrieval) and print occupancy/cohort stats —
the tool for attributing serving throughput between the engine proper and
the control-plane layers above it.

Env knobs: PROBE_MODEL (2b|test), PROBE_REQUESTS, PROBE_BATCH, PROBE_TICK,
PROBE_SPEC, PROBE_DEPTH (worker pipeline depth), PROBE_KEYS (1 = trie the
"in" keys), PROBE_CPU=N (arm an N-device virtual CPU platform).

PROBE_SWEEP runs several configs in ONE process — a chip belongs to one
process at a time — with XLA compiles shared through the persistent
compilation cache; each entry still builds a fresh engine (weights re-init
+ trace per config):

    PROBE_SWEEP="tick=2;tick=8;batch=128,tick=2;spec=16" python benchmarks/engine_probe.py

Each ';'-separated entry is a comma list of overrides (tick, spec, batch,
keys, requests); unset fields fall back to the env/default values.
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _pallas_on, _serving_announced

if int(os.environ.get("PROBE_CPU", "0")) > 0:
    from __graft_entry__ import _force_virtual_cpu

    _force_virtual_cpu(int(os.environ["PROBE_CPU"]))


_COUNTERS = (
    ("fwd", "decode_forwards"),
    ("tok", "decode_tokens"),
    ("adm", "admissions"),
    ("rows", "admitted_rows"),
    ("segrows", "segment_active_rows"),
    ("seg", "segments"),
    ("pft", "prefill_tokens"),
)


def _snap(eng):
    return {k: getattr(eng.metrics, attr)._value.get() for k, attr in _COUNTERS}


async def run_one(*, model: str, n_req: int, batch: int, tick: int, spec: int,
                  with_keys: bool, depth: int, vocab: str, minfree: int,
                  wait: float, budget: int, draft: str = "prompt") -> dict:
    from mcpx.core.config import MCPXConfig
    from mcpx.engine.engine import InferenceEngine
    from mcpx.planner.grammar import build_plan_grammar

    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": model, "max_seq_len": 2048, "vocab": vocab},
            "engine": {
                "max_batch_size": batch,
                "max_decode_len": budget,
                # SAME KV geometry as bench.py's BPE config: pages=16 made
                # every (batch, len) bucket a fresh executable instead of a
                # persistent-cache hit from the headline run. 4 x 64-token
                # pages hold the probe's 128-token prompt + up to a
                # 96-token budget + spec slack.
                "kv_page_size": 64,
                "max_pages_per_seq": 4,
                "temperature": 0.0,
                # One definition of the session-wide Pallas gate (tpu AND
                # MCPX_BENCH_PALLAS != "0"); the cpu-backend clear below
                # stays for PROBE_CPU virtual-device runs.
                "use_pallas": _pallas_on(),
                # The explicit warm rounds below compile exactly the buckets
                # the probe exercises; full warmup would compile all of them.
                "warmup_compile": False,
                "decode_steps_per_tick": tick,
                "speculate_k": spec,
                "pipeline_depth": depth,
                "admit_min_free": minfree,
                "admit_max_wait_s": wait,
                "draft_mode": draft,
            },
        }
    )
    import jax

    if jax.default_backend() == "cpu":
        cfg.engine.use_pallas = False
    _serving_announced(batch, "probe config", tag="probe")
    eng = InferenceEngine(cfg)
    t0 = time.monotonic()
    await eng.start()
    t_start = time.monotonic() - t0

    names = [f"svc-{kind}-{i:04d}" for kind in ("fetch", "rank", "notify", "merge")
             for i in range(250)]
    keys = ["query", "user_id", "order_id", "document", "text", "items", "amount",
            "address", "score", "status", "report", "features", "vector", "summary"]
    grammar = build_plan_grammar(eng.tokenizer, names,
                                 input_keys=keys if with_keys else None)
    prompt = ("Compose a service DAG. JSON\nServices:\n"
              + "\n".join(f"{n} in:a,b out:c" for n in names[:6])
              + "\nIntent: fetch and rank the things\nJSON:")
    ids = eng.tokenizer.encode(prompt)

    # Warm every admission-cohort bucket the timed phase could hit, so no
    # XLA compile lands inside the measured window.
    for a in eng._batch_buckets:
        await asyncio.gather(*(eng.generate(ids, max_new_tokens=budget, grammar=grammar)
                               for _ in range(a)))
    m0 = _snap(eng)
    t1 = time.monotonic()
    results = await asyncio.gather(*(eng.generate(ids, max_new_tokens=budget, grammar=grammar)
                                     for _ in range(n_req)))
    dt = time.monotonic() - t1
    m1 = _snap(eng)
    d = {k: m1[k] - m0[k] for k in m0}
    gen = sum(r.generated_tokens for r in results)
    out = {
        "model": model, "batch": batch, "tick": tick, "spec": spec,
        "depth": depth, "vocab": vocab, "minfree": minfree, "wait": wait,
        "budget": budget, "draft": draft,
        "keys": int(with_keys), "requests": n_req,
        "plans_per_sec": round(n_req / dt, 2),
        "elapsed_s": round(dt, 2),
        "startup_s": round(t_start, 1),
        "gen_tokens": gen,
        "decode_forwards": int(d["fwd"]),
        "tok_per_forward": round(d["tok"] / max(1, d["fwd"]), 1),
        "avg_cohort": round(d["rows"] / max(1, d["adm"]), 1),
        "admissions": int(d["adm"]),
        "avg_occupancy": round(d["segrows"] / max(1, d["seg"]), 1),
        "segments": int(d["seg"]),
        "prefill_tokens": int(d["pft"]),
        "prompt_len": len(ids),
        "p50_decode_ms": round(sorted(r.decode_ms for r in results)[n_req // 2], 1),
        "p50_prefill_ms": round(sorted(r.prefill_ms for r in results)[n_req // 2], 1),
        "p50_queue_ms": round(sorted(r.queue_ms for r in results)[n_req // 2], 1),
    }
    await eng.aclose()
    return out


def _base() -> dict:
    return {
        "model": os.environ.get("PROBE_MODEL", "2b"),
        "n_req": int(os.environ.get("PROBE_REQUESTS", "256")),
        "batch": int(os.environ.get("PROBE_BATCH", "64")),
        "tick": int(os.environ.get("PROBE_TICK", "2")),
        "spec": int(os.environ.get("PROBE_SPEC", "8")),
        "with_keys": os.environ.get("PROBE_KEYS", "1") == "1",
        "depth": int(os.environ.get("PROBE_DEPTH", "2")),
        "vocab": os.environ.get("PROBE_VOCAB", "bpe"),
        "minfree": int(os.environ.get("PROBE_MINFREE", "0")),
        "wait": float(os.environ.get("PROBE_WAIT", "0.15")),
        "budget": int(os.environ.get("PROBE_BUDGET", "96")),
        "draft": os.environ.get("PROBE_DRAFT", "prompt"),
    }


async def main() -> None:
    sweep = os.environ.get("PROBE_SWEEP", "")
    configs = []
    if sweep:
        for entry in filter(None, (e.strip() for e in sweep.split(";"))):
            c = _base()
            for kv in filter(None, entry.split(",")):
                k, _, v = kv.partition("=")
                k, v = k.strip(), v.strip()
                if k == "keys":
                    c["with_keys"] = v == "1"
                elif k == "requests":
                    c["n_req"] = int(v)
                elif k in ("tick", "spec", "batch", "depth", "minfree", "budget"):
                    c[k] = int(v)
                elif k == "wait":
                    c["wait"] = float(v)
                elif k == "model":
                    c["model"] = v
                elif k == "vocab":
                    c["vocab"] = v
                elif k == "draft":
                    c["draft"] = v
                else:
                    raise SystemExit(f"unknown sweep key {k!r}")
            configs.append(c)
    else:
        configs.append(_base())
    for c in configs:
        print(json.dumps(await run_one(**c)), flush=True)


if __name__ == "__main__":
    asyncio.run(main())
