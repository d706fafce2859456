#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mcpx still serves on the chip.

Starts ``mcpx serve`` with the LLM planner the way a user does (one child
process: ``python -m mcpx.cli --config … --registry-file … --planner llm
serve --port …``) at the full width of the ``2b`` preset (d_model 2048, 18
layers, 8 heads MQA, head_dim 256, d_ff 16384; BPE vocab; seeded random
weights) over a seeded 1,000-service registry, waits for the engine, sends a
few dozen distinct ``/plan`` requests — a concurrent burst, then one at a
time, then one long intent and one ``/plan_and_execute`` against echo
services this script hosts — and checks that every answer is an LLM-authored
valid plan served by the Mosaic-compiled ragged kernel on a TPU with no
compile and no pool reset after readiness.

One process touches JAX: the server. This script never imports jax (it
checks), so the child finds the chip free. The child is started with
``JAX_PLATFORMS=tpu``: with no TPU it fails at start-up and so does this
script — there is no CPU fallback. ``--rehearse-cpu`` asks, on the command
line only, for a rehearsal of the same script on the CPU backend at
``model.size=test`` with the kernel in Pallas interpret mode; it says so in
every line of its verdict and proves nothing about the chip.

The engine config is EngineConfig's defaults plus ``warmup_compile`` and
``temperature=0`` — what a user of ``mcpx serve`` gets. The default warm-up
set (cohort buckets 1/8/16/32 x prompt buckets 64..1024, 63 executables with
the registry grammar's) is NOT cut: cold, it compiled in under 300 s on one
v5e chip (CHANGES.md, PR 21), inside the 1,200 s limit. The warm-up compiles
and executes suffix prefill at every bucket up to 1,024 tokens (eight
128-query kernel blocks per row); most intents below land in the smallest
buckets and one long intent is sized to land in the 512-token one.

Last line of stdout on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.server
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

N_SERVICES = 1000  # the product's stated registry size
REGISTRY_SEED = 7
INTENT_SEED = 21
N_CONCURRENT = 24  # one burst, >= 16 in flight so the slab batches
N_SEQUENTIAL = 8
STARTUP_DEADLINE_S = 960.0  # of the contract's 1,200 s
REQUEST_TIMEOUT_S = 120.0
SCRAPE_TIMEOUT_S = 60.0  # /costs' first read AOT-compiles off the serving path


class SmokeFailure(Exception):
    """A check did not hold; the message says which."""


# ------------------------------------------------------------------ inputs
def build_config(rehearsal: bool) -> dict:
    """What a user of ``mcpx serve`` gets, plus warm-up and greedy decode.
    The rehearsal swaps the model for the CPU-sized one and the compiled
    kernel for the interpreted one; nothing else differs."""
    engine = {"warmup_compile": True, "temperature": 0.0}
    model = {"size": "2b", "vocab": "bpe"}
    if rehearsal:
        model["size"] = "test"
        engine["interpret"] = True
    return {"model": model, "engine": engine, "planner": {"kind": "llm"}}


def build_registry(echo_port: int) -> list:
    """The seeded synthetic registry (``ServiceRecord``s), every endpoint
    pointed at this script's loopback echo server."""
    import dataclasses

    from mcpx.utils.synth import synth_registry

    return [
        dataclasses.replace(r, endpoint=f"http://127.0.0.1:{echo_port}/svc/{r.name}")
        for r in synth_registry(N_SERVICES, seed=REGISTRY_SEED, local=False)
    ]


def build_intents(records: list) -> tuple[list[str], list[str]]:
    """(burst, follow-ups), all DISTINCT, naming domains/verbs of the
    registry, so the plan cache can answer none of them. Follow-up ``i``
    repeats burst intent ``i``'s wording under a new case number: a new
    intent whose prompt shares the earlier prompt's head, so the radix cache
    matches it and the unmatched tail goes through the suffix-prefill path
    of the kernel — which prompts with nothing in common never reach."""
    from mcpx.utils.synth import intent_for

    rng = random.Random(INTENT_SEED)
    wordings = [intent_for(records, rng) for _ in range(N_CONCURRENT)]
    burst = [f"{w} for case {i}" for i, w in enumerate(wordings)]
    follow = [f"{w} for case {N_CONCURRENT + i}" for i, w in enumerate(wordings[:N_SEQUENTIAL])]
    assert len(set(burst + follow)) == N_CONCURRENT + N_SEQUENTIAL
    return burst, follow


def build_long_intent(records: list) -> str:
    """One intent long enough that its prompt suffix lands in the 512-token
    prefill bucket: 300 BPE tokens of intent next to a shortlist block of
    50-120 tokens is past what the 256 bucket holds, and anything up to 512
    is warmed. (The other intents are ~20 tokens and land in 64 or 128.)"""
    from mcpx.models.tokenizer import make_tokenizer  # stdlib + numpy, no jax

    tok = make_tokenizer("bpe")
    rng = random.Random(INTENT_SEED + 1)
    clauses: list[str] = []
    while len(tok.encode("please " + " then ".join(clauses))) < 300:
        r = rng.choice(records)
        clauses.append(f"{r.tags[1]} the {r.tags[0]} {rng.choice(list(r.input_schema))}")
    return "please " + " then ".join(clauses)


# ------------------------------------------------------------------ checks
def check_health(body: dict) -> bool:
    """True once start-up is complete; raises at once on ``engine_error``
    (the engine did not start) or ``warm_error`` (the registry grammar was
    not compiled). ``/healthz`` answers 200 ``status: ok`` with a dead
    engine, so the fields are what counts, not the status."""
    for field in ("engine_error", "warm_error"):
        if body.get(field):
            raise SmokeFailure(f"{field}: {body[field]}")
    if body.get("engine") in ("failed", "closed"):
        raise SmokeFailure(f"engine state {body.get('engine')!r}")
    return body.get("engine") == "ready" and body.get("started") is True


def check_plan(status: int, body: dict, names: set[str], what: str) -> None:
    """One ``/plan`` (or ``/plan_and_execute``) answer: 200, authored by the
    LLM on the primary tier, and a valid plan over registry services."""
    from mcpx.core import Plan, PlanValidationError

    if status != 200:
        raise SmokeFailure(f"{what}: HTTP {status}: {str(body)[:300]}")
    if body.get("origin") != "llm":
        raise SmokeFailure(
            f"{what}: origin {body.get('origin')!r}, not 'llm' (a heuristic "
            "plan is a failure here)"
        )
    if body.get("planner") == "degraded":
        raise SmokeFailure(f"{what}: served by the degraded tier")
    try:
        plan = Plan.from_wire(body["graph"])  # validates
    except (KeyError, PlanValidationError) as e:
        raise SmokeFailure(f"{what}: plan does not validate: {e}") from e
    if not plan.nodes:
        raise SmokeFailure(f"{what}: empty plan")
    unknown = [n.service for n in plan.nodes if n.service not in names]
    if unknown:
        raise SmokeFailure(f"{what}: services not in the registry: {unknown}")


def check_kernel(health: dict, rehearsal: bool) -> dict:
    """The ragged kernel served: enabled, compiled (not interpreted) on the
    chip, and actually dispatched on the decode and the prefill path."""
    pallas = (health.get("engine_queue") or {}).get("pallas")
    if not isinstance(pallas, dict):
        raise SmokeFailure("/healthz carries no engine_queue.pallas block")
    if pallas.get("enabled") is not True:
        raise SmokeFailure(f"ragged kernel not enabled: {pallas.get('reason')}")
    if bool(pallas.get("interpret")) != rehearsal:
        raise SmokeFailure(
            f"pallas.interpret is {pallas.get('interpret')!r}; expected "
            f"{rehearsal!r} ({'rehearsal' if rehearsal else 'chip'} run)"
        )
    counts = {}
    for path in ("decode", "prefill"):
        p = (pallas.get("paths") or {}).get(path) or {}
        counts[path] = int(p.get("dispatches") or 0)
        if not p.get("engaged") or counts[path] <= 0:
            raise SmokeFailure(
                f"kernel path {path!r}: engaged={p.get('engaged')!r} "
                f"dispatches={counts[path]} ({p.get('reason')})"
            )
    return counts


def prom_total(text: str, name: str) -> float:
    """Sum of every sample of counter ``name`` in a Prometheus exposition."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name) : len(name) + 1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def check_metrics(before: str, after: str) -> dict:
    """No pool reset ever, and no executable compiled after readiness."""
    resets = prom_total(after, "mcpx_engine_resets_total")
    if resets != 0:
        raise SmokeFailure(f"mcpx_engine_resets_total = {resets} (pools were reset)")
    c0 = prom_total(before, "mcpx_engine_compiles_total")
    c1 = prom_total(after, "mcpx_engine_compiles_total")
    if c0 <= 0:
        raise SmokeFailure("mcpx_engine_compiles_total is 0 after warm-up")
    if c1 != c0:
        raise SmokeFailure(
            f"{c1 - c0:g} executable(s) compiled after readiness "
            f"(mcpx_engine_compiles_total {c0:g} -> {c1:g})"
        )
    return {"compiles": c0, "resets": resets}


def check_device(costs: dict, rehearsal: bool) -> dict:
    """The device the SERVER reports (``/costs`` -> ``device``): a TPU, with
    every meshed device holding real bytes. A rehearsal accepts the CPU it
    asked for and nothing else."""
    dev = costs.get("device") or {}
    peaks = dev.get("peaks") or {}
    platform = peaks.get("platform")
    want = "cpu" if rehearsal else "tpu"
    if platform != want:
        raise SmokeFailure(f"server reports platform {platform!r}, not {want!r}")
    count = int(peaks.get("n_devices") or 0)
    mesh = dev.get("mesh") or {}
    mesh_size = 1
    for n in mesh.values():
        mesh_size *= int(n)
    if count < 1 or mesh_size != count:
        raise SmokeFailure(f"engine mesh {mesh} does not cover {count} device(s)")
    hbm = dev.get("hbm") or []
    if not rehearsal:
        # 2B bf16 weights alone are ~4 GB over the mesh; a device holding
        # under 256 MiB is not taking part.
        idle = [h for h in hbm if int(h.get("bytes_in_use") or 0) < (256 << 20)]
        if len(hbm) != count or idle:
            raise SmokeFailure(f"device(s) with trivial bytes_in_use: {idle or hbm}")
    return {
        "platform": platform,
        "kind": peaks.get("device_kind"),
        "count": count,
        "mesh": mesh,
        "compilation_cache_dir": dev.get("compilation_cache_dir"),
        "bytes_in_use": [h.get("bytes_in_use") for h in hbm],
        "peak_bytes_in_use": [h.get("peak_bytes_in_use") for h in hbm],
    }


# ------------------------------------------------------------------- plumbing
def http_json(method: str, url: str, body: dict | None, timeout_s: float):
    """(status, parsed JSON body) — HTTP errors are answers, not exceptions."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method, headers={"content-type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        raw = e.read().decode(errors="replace")
        try:
            return e.code, json.loads(raw)
        except json.JSONDecodeError:
            return e.code, {"error": raw[:500]}


def http_text(url: str, timeout_s: float) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode()


class _Echo(http.server.BaseHTTPRequestHandler):
    """Loopback stand-in for every registry service: answers any POST with
    an ok result naming the service."""

    def do_POST(self):  # noqa: N802 - http.server's naming
        self.rfile.read(int(self.headers.get("content-length") or 0))
        out = json.dumps({"service": self.path.rsplit("/", 1)[-1], "ok": True}).encode()
        self.send_response(200)
        self.send_header("content-type", "application/json")
        self.send_header("content-length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *args):  # silence per-request stderr lines
        pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop_child(child: subprocess.Popen) -> None:
    """Terminate the server's whole process group; no orphan survives."""
    if child.poll() is None:
        try:
            os.killpg(child.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            child.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(child.pid, signal.SIGKILL)  # stragglers of the group, if any
    except ProcessLookupError:
        pass
    child.wait()


def tail(path: str, n_bytes: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n_bytes))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<no server log: {e}>"


# ----------------------------------------------------------------------- run
def run(rehearsal: bool) -> dict:
    tag = "REHEARSAL (cpu, model=test, interpret) " if rehearsal else ""
    os.makedirs(OUT_DIR, exist_ok=True)
    echo = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    threading.Thread(target=echo.serve_forever, daemon=True).start()
    records = build_registry(echo.server_address[1])
    names = {r.name for r in records}
    cfg_path = os.path.join(OUT_DIR, "config.json")
    reg_path = os.path.join(OUT_DIR, "registry.json")
    log_path = os.path.join(OUT_DIR, "server.log")
    with open(cfg_path, "w") as f:
        json.dump(build_config(rehearsal), f, indent=1)
    with open(reg_path, "w") as f:
        json.dump([r.to_dict() for r in records], f)

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    env = dict(os.environ, JAX_PLATFORMS="cpu" if rehearsal else "tpu")
    cmd = [
        sys.executable, "-m", "mcpx.cli", "--config", cfg_path,
        "--registry-file", reg_path, "--planner", "llm", "serve", "--port", str(port),
    ]
    print(f"chip_smoke: {tag}starting {' '.join(cmd[1:])}", flush=True)
    if "jax" in sys.modules:
        raise SmokeFailure("this process imported jax; the server would not get the chip")
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        child = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    try:
        # --- start-up: bounded, and over at once if the engine or the
        # process dies.
        health: dict = {}
        while True:
            if child.poll() is not None:
                raise SmokeFailure(f"server exited with code {child.returncode} during start-up")
            if time.monotonic() - t0 > STARTUP_DEADLINE_S:
                raise SmokeFailure(
                    f"not ready after {STARTUP_DEADLINE_S:.0f}s (last /healthz: {health})"
                )
            try:
                _, health = http_json("GET", f"{base}/healthz", None, 10.0)
            except (urllib.error.URLError, OSError, json.JSONDecodeError):
                time.sleep(1.0)  # not listening yet
                continue
            if check_health(health):
                break
            time.sleep(2.0)
        startup_s = time.monotonic() - t0
        print(f"chip_smoke: {tag}ready after {startup_s:.1f}s of set-up "
              "(weights, warm-up compiles, registry grammar)", flush=True)
        prom0 = http_text(f"{base}/metrics", SCRAPE_TIMEOUT_S)

        # --- requests: a concurrent burst, then follow-ups one at a time,
        # then the long intent, then one plan_and_execute. All distinct.
        burst_intents, follow_intents = build_intents(records)

        def plan(intent: str):
            return http_json("POST", f"{base}/plan", {"intent": intent}, REQUEST_TIMEOUT_S)

        t_req = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(N_CONCURRENT) as pool:
            burst = list(pool.map(plan, burst_intents))
        for i, (status, body) in enumerate(burst):
            check_plan(status, body, names, f"concurrent /plan {i}")
        for i, intent in enumerate(follow_intents):
            check_plan(*plan(intent), names, f"sequential /plan {i}")
        check_plan(*plan(build_long_intent(records)), names, "long-intent /plan")
        status, body = http_json(
            "POST", f"{base}/plan_and_execute",
            {"intent": burst_intents[0] + " again", "payload": {"query": "smoke"}},
            REQUEST_TIMEOUT_S,
        )
        check_plan(status, body, names, "/plan_and_execute")
        if body.get("status") != "ok":
            raise SmokeFailure(f"/plan_and_execute status {body.get('status')!r}")
        n_plans = N_CONCURRENT + N_SEQUENTIAL + 2
        print(f"chip_smoke: {tag}{n_plans} requests answered 200 origin=llm with valid "
              f"plans in {time.monotonic() - t_req:.1f}s", flush=True)

        # --- what served them. /costs last: its first read AOT-compiles
        # every recorded signature off the serving path.
        _, health = http_json("GET", f"{base}/healthz", None, 10.0)
        check_health(health)
        dispatches = check_kernel(health, rehearsal)
        counters = check_metrics(prom0, http_text(f"{base}/metrics", SCRAPE_TIMEOUT_S))
        _, costs = http_json("GET", f"{base}/costs", None, SCRAPE_TIMEOUT_S)
        device = check_device(costs, rehearsal)
        print(f"chip_smoke: {tag}platform={device['platform']} "
              f"device_kind={device['kind']!r} devices={device['count']} "
              f"mesh={device['mesh']}", flush=True)
        print(f"chip_smoke: {tag}compile cache in effect: "
              f"{device['compilation_cache_dir']}", flush=True)
        print(f"chip_smoke: {tag}bytes_in_use per device: {device['bytes_in_use']} "
              f"(peak {device['peak_bytes_in_use']})", flush=True)
        print(f"chip_smoke: {tag}kernel dispatches {dispatches}, executables compiled "
              f"in warm-up {counters['compiles']:g}, after readiness 0, pool resets 0",
              flush=True)
        return {"platform": device["platform"], "kind": device["kind"],
                "count": device["count"]}
    except BaseException:
        print(f"chip_smoke: --- tail of {log_path} ---\n{tail(log_path)}",
              file=sys.stderr, flush=True)
        raise
    finally:
        stop_child(child)
        echo.shutdown()
        echo.server_close()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="rehearse the script on the CPU backend at model.size=test with "
        "the kernel interpreted; proves nothing about the chip",
    )
    args = ap.parse_args(argv)
    # SIGTERM (a driver's time limit) must still run the finally that stops
    # the server.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        device = run(args.rehearse_cpu)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result = {"rehearsal": True, **result}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
