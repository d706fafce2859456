#!/usr/bin/env python3
"""The chip benchmark's one command.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json``: starts ONE child (``child.py``: the real
served path of mcpx at the configuration's published widths, the aiohttp app
``mcpx serve`` runs, on loopback), waits for ``/healthz`` ``started``, checks
the model step against the plain reference, drives the cell's traffic
(``loadgen.py``) through its warm plans, measures for ``--seconds``, reads
the program's counters and (``--trace 1``) its spans and a profiler trace of
the chip, stops the child, and prints one JSON object as the last line of
stdout: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``. ``--trace 0`` prints the cell's end-to-end metrics
(tracing and profiler off), ``--trace 1`` its per-layer metrics.

This process never imports jax: the chip belongs to the child. With no TPU
the child fails at start-up and so does this command, with no result line.
``--rehearse-cpu`` (tests and rehearsals only) runs the same command at
``model=test`` with the interpreted kernel on the CPU backend and says
``platform: cpu`` in its line; it proves the harness, never the chip.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import readers  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
from peaks import peaks_for  # noqa: E402
from stats import completion_rate, quantile, uncovered_edges  # noqa: E402

STARTUP_DEADLINE_S = 1100.0  # of the 1,200 s a first (compiling) run may take
WARM_DEADLINE_S = 240.0
SCRAPE_TIMEOUT_S = 120.0
TRACE_START_S = 2.0  # the profiled slice starts this far into the window


class BenchFailure(Exception):
    """The run cannot produce a result; the message says why."""


# ---------------------------------------------------------------------- http
class Client:
    """One keep-alive connection to the child (one per sender thread)."""

    def __init__(self, port: int, timeout_s: float) -> None:
        self.port, self.timeout_s = port, timeout_s
        self._conn: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: dict | None = None):
        """(status, parsed JSON, headers); status 0 on a transport error or
        client timeout. A body that is not JSON (``/metrics``) comes back as
        ``{"text": ...}``."""
        data = None if body is None else json.dumps(body).encode()
        headers = {"content-type": "application/json"} if data is not None else {}
        for attempt in (0, 1):
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=self.timeout_s
                    )
                self._conn.request(method, path, body=data, headers=headers)
                resp = self._conn.getresponse()
                raw = resp.read()
                try:
                    parsed = json.loads(raw.decode()) if raw else {}
                except json.JSONDecodeError:
                    text = raw.decode(errors="replace")
                    parsed = {"error": text[:300], "text": text}
                return resp.status, parsed, resp.headers
            except (http.client.HTTPException, OSError) as e:
                self.close()
                # A kept-alive connection the server closed fails on reuse
                # at once; retry once on a fresh one. A timeout is final.
                if attempt == 1 or isinstance(e, (socket.timeout, TimeoutError)):
                    return 0, {"error": f"{type(e).__name__}: {e}"}, {}
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            finally:
                self._conn = None


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------- validation
def plan_problem(status: int, body: dict, names: set, want_origin: str) -> str:
    """'' for a counted plan, else why it failed: non-200, wrong origin, or
    a graph that does not validate against the registry (nodes named,
    services registered, edges between its own nodes, acyclic)."""
    if status != 200:
        return f"HTTP {status}: {str(body.get('error', body))[:200]}"
    if body.get("origin") != want_origin:
        return f"origin {body.get('origin')!r}, not {want_origin!r}"
    graph = body.get("graph")
    if not isinstance(graph, dict) or not graph.get("nodes"):
        return "no plan graph, or an empty one"
    node_names = []
    for n in graph["nodes"]:
        if not isinstance(n, dict) or not n.get("name"):
            return "a node without a name"
        if n.get("service") not in names:
            return f"service {n.get('service')!r} is not in the registry"
        node_names.append(n["name"])
    if len(set(node_names)) != len(node_names):
        return "duplicate node names"
    succ: dict[str, list] = {n: [] for n in node_names}
    indeg = {n: 0 for n in node_names}
    for e in graph.get("edges", []):
        a, b = e.get("from"), e.get("to")
        if a not in succ or b not in succ:
            return f"edge {a!r}->{b!r} names no node"
        succ[a].append(b)
        indeg[b] += 1
    ready = [n for n, d in indeg.items() if d == 0]
    seen = 0
    while ready:
        n = ready.pop()
        seen += 1
        for m in succ[n]:
            indeg[m] -= 1
            if indeg[m] == 0:
                ready.append(m)
    return "" if seen == len(node_names) else "the plan has a cycle"


def prom_total(text: str, name: str) -> float:
    """Sum of every sample of counter ``name`` in a Prometheus exposition."""
    return sum(v for key, v in prom_samples(text).items() if key == name or key.startswith(name + "{"))


def prom_samples(text: str) -> dict[str, float]:
    """A Prometheus exposition as ``{sample name with its label set: value}``,
    e.g. ``'mcpx_engine_compiles_total{executable="admit"}'``: a counter
    path of a metric file can then address one labelled sample."""
    samples: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            samples[key.strip()] = float(value)
        except ValueError:
            continue  # not a sample line
    return samples


def counter_endpoints(cell: spec.Cell) -> list[str]:
    """Every ``endpoint`` a per-layer metric of the cell names in its args."""
    return sorted({str(m.args["endpoint"]) for m in cell.per_layer if "endpoint" in m.args})


def fetch_counters(ctl: "Client", endpoints: list[str]) -> dict[str, dict]:
    """The counters as they stand: each endpoint's JSON body as it is, a text
    body (``/metrics``) parsed by ``prom_samples``. An endpoint that does not
    answer 200 is left out, and its metrics then find nothing to read."""
    out: dict[str, dict] = {}
    for endpoint in endpoints:
        status, body, headers = ctl.request("GET", endpoint)
        if status == 200:
            is_json = "json" in (headers.get("Content-Type") or "")
            out[endpoint] = body if is_json else prom_samples(body.get("text", ""))
    return out


# --------------------------------------------------------------------- child
def mcpx_config(cell: spec.Cell, run_dir: str, port: int, trace: bool, rehearsal: bool) -> dict:
    """The MCPXConfig the child serves with: the configuration file's
    ``mcpx`` section (what differs from defaults) plus its ``EngineConfig``
    sizes (top-level keys, so that ``reduced`` can name them), the registry
    file, and tracing on (rate 1) only in a traced run."""
    cfg = json.loads(json.dumps(cell.config.get("mcpx", {})))
    engine = cfg.setdefault("engine", {})
    for key in spec.ENGINE_SIZES:
        engine[key] = cell.config[key]
    if rehearsal:
        engine["interpret"] = True
        engine.pop("data_axis", None)  # the rehearsal's device count differs
        engine.pop("model_axis", None)
    cfg.setdefault("planner", {})["kind"] = "llm"
    cfg["registry"] = {"backend": "file", "file_path": os.path.join(run_dir, "registry.json")}
    cfg["tracing"] = {"enabled": bool(trace), "sample_rate": 1.0, "ring_size": 65536}
    cfg["server"] = {"host": "127.0.0.1", "port": port,
                     "profile_dir": os.path.join(run_dir, "profile")}
    return cfg


def stop_child(child: subprocess.Popen) -> None:
    """Terminate the child's whole process group and wait for it."""
    if child.poll() is None:
        try:
            os.killpg(child.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            child.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(child.pid, signal.SIGKILL)
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


def check_health(body: dict) -> bool:
    for field in ("engine_error", "warm_error"):
        if body.get(field):
            raise BenchFailure(f"{field}: {body[field]}")
    if body.get("engine") in ("failed", "closed"):
        raise BenchFailure(f"engine state {body.get('engine')!r}")
    return body.get("engine") == "ready" and body.get("started") is True


def wait_started(child: subprocess.Popen, ctl: "Client", t_child: float, marks: dict) -> None:
    """Poll ``/healthz`` until ``started``; over at once if the engine or the
    process dies. Notes when the server first answered and the engine was
    first ready in ``marks``."""
    while True:
        if child.poll() is not None:
            raise BenchFailure(f"child exited with code {child.returncode} during start-up")
        if time.monotonic() - t_child > STARTUP_DEADLINE_S:
            raise BenchFailure(f"not started after {STARTUP_DEADLINE_S:.0f} s")
        status, health, _ = ctl.request("GET", "/healthz")
        if status == 200:
            marks.setdefault("listening", time.monotonic() - t_child)
            if health.get("engine") == "ready":
                marks.setdefault("engine_ready", time.monotonic() - t_child)
            if check_health(health):
                return
        time.sleep(0.5)


def correctness_problems(*, failed, n_good, drained, edges, ref, resets, compiles, platform,
                         pallas, kernel_paths, rehearsal) -> list[str]:
    """Every reason the run's result is not ``correct`` (empty = correct).
    ``kernel_paths`` is the block module's: kernel path -> fewest dispatches."""
    problems: list[str] = []
    if failed:
        problems.append(f"{len(failed)} failed plan(s), e.g. {failed[0].why}")
    if not drained:
        problems.append("senders still in flight after the request timeout")
    if edges is not None and edges[0] > 2.0 * edges[1]:
        problems.append(
            f"no plan completed for {edges[0]:.2f} s at the window's edges (widest gap "
            f"inside it {edges[1]:.2f} s): a stall the rate and the quantiles cannot see"
        )
    if not ref.get("ok"):
        problems.append(
            f"model step vs reference: rms {ref.get('rms_rel_err')} (tolerance "
            f"{ref.get('tol_rms')}), max {ref.get('max_rel_err')} ({ref.get('tol_max')})"
        )
    if resets != 0:
        problems.append(f"mcpx_engine_resets_total = {resets:g}")
    if compiles[0] <= 0 or compiles[1] != compiles[0]:
        problems.append(
            f"mcpx_engine_compiles_total {compiles[0]:g} -> {compiles[1]:g} after 'started'"
        )
    if platform != ("cpu" if rehearsal else "tpu"):
        problems.append(f"server reports platform {platform!r}")
    paths = pallas.get("paths") or {}
    if pallas.get("enabled") is not True or bool(pallas.get("interpret")) != rehearsal:
        problems.append(f"ragged kernel: enabled={pallas.get('enabled')!r} "
                        f"interpret={pallas.get('interpret')!r}")
    for path, fewest in kernel_paths.items():
        seen = paths.get(path) or {}
        if not seen.get("engaged"):
            problems.append(f"kernel path {path!r} not engaged: {seen.get('reason')}")
        if int(seen.get("dispatches") or 0) < fewest:
            problems.append(
                f"kernel path {path!r}: {int(seen.get('dispatches') or 0)} dispatch(es) went "
                f"through the kernel, fewer than {fewest}"
            )
    if n_good < 2:
        problems.append(f"only {n_good} plan(s) completed in the window")
    return problems


# ----------------------------------------------------------------------- run
def run(args: argparse.Namespace) -> dict:
    rehearsal = bool(args.rehearse_cpu)
    trace = bool(args.trace)
    cell = spec.load_cell(args.workload)
    found = readers.vocabulary()
    try:  # an unknown reader fails before the child starts, not after the window
        for m in cell.per_layer:
            readers.reader_named(m.reader, found)
    except KeyError as e:
        raise BenchFailure(e.args[0]) from e
    endpoints = counter_endpoints(cell)
    traffic = loadgen.load_traffic(cell.traffic)
    gen = loadgen.Generator(cell.traffic, args.seed)
    names = {r["name"] for r in gen.registry}
    clients = (
        int(cell.config["slab_rows"]) if traffic.get("clients") == "slab_rows"
        else int(traffic.get("clients") or 0)
    )

    run_dir = os.path.join(spec.ROOT, ".chip", "bench", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    port = free_port()
    with open(os.path.join(run_dir, "registry.json"), "w") as f:
        json.dump(gen.registry, f)
    cfg_path = os.path.join(run_dir, "mcpx_config.json")
    with open(cfg_path, "w") as f:
        json.dump(mcpx_config(cell, run_dir, port, trace, rehearsal), f, indent=1)
    log_path = os.path.join(run_dir, "server.log")

    env = dict(os.environ, JAX_PLATFORMS="cpu" if rehearsal else "tpu")
    if rehearsal:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={cell.chips}")
        env["XLA_FLAGS"] = " ".join(flags)
    cmd = [sys.executable, os.path.join(HERE, "child.py"),
           "--config-file", cell.config_file,
           "--mcpx-config", cfg_path, "--port", str(port)]
    if rehearsal:
        cmd.append("--rehearse-cpu")
    if "jax" in sys.modules:
        raise BenchFailure("this process imported jax; the child would not get the chip")
    marks: dict[str, float] = {}
    t_child = time.monotonic()
    wall_child = time.time()
    with open(log_path, "wb") as log:
        child = subprocess.Popen(cmd, cwd=spec.ROOT, env=env, stdout=log,
                                 stderr=subprocess.STDOUT, start_new_session=True)
    ctl = Client(port, SCRAPE_TIMEOUT_S)
    loop = None
    try:
        wait_started(child, ctl, t_child, marks)
        marks["started"] = time.monotonic() - t_child

        # --- the model step against the plain reference (set-up, not window).
        status, ref, _ = ctl.request(
            "POST", "/bench/reference", {"seed": args.seed, "control": args.control}
        )
        if status != 200:
            raise BenchFailure(f"/bench/reference: HTTP {status}: {ref}")
        marks["reference_done"] = time.monotonic() - t_child
        status, m0, _ = ctl.request("GET", "/bench/marks")
        if status != 200:
            raise BenchFailure(f"/bench/marks: HTTP {status}")
        compiles0 = prom_total(m0["engine_metrics"], "mcpx_engine_compiles_total")
        marks["child_imported"] = m0["t_imported"] - wall_child
        marks["child_app_built"] = m0["t_app_built"] - wall_child

        # --- traffic: warm plans first, so the window starts in steady state.
        def post_factory():
            c = Client(port, float(traffic["request_timeout_s"]))

            def post(intent: str):
                status, body, headers = c.request("POST", "/plan", {"intent": intent})
                why = plan_problem(status, body, names, traffic["origin"])
                return (not why), why, headers.get("X-Trace-Id", "") if headers else ""

            return post

        loop = loadgen.Loop(gen, post_factory, clients)
        loop.start()
        t_warm = time.monotonic()
        while loop.fresh_done < int(traffic["warm_plans"]):
            if child.poll() is not None:
                raise BenchFailure(f"child exited with code {child.returncode} while warming")
            if time.monotonic() - t_warm > WARM_DEADLINE_S:
                bad = [s.why for s in loop.snapshot() if not s.ok][:3]
                raise BenchFailure(
                    f"{loop.fresh_done} of {traffic['warm_plans']} warm plans after "
                    f"{WARM_DEADLINE_S:.0f} s (failures: {bad})"
                )
            time.sleep(0.05)
        t0 = time.monotonic()
        setup_s = t0 - t_child
        marks["warm_plans_done"] = setup_s
        counters0 = fetch_counters(ctl, endpoints)

        # --- the measured window; traced runs profile a slice of it.
        profile_dir = os.path.join(run_dir, "profile")
        profiled = False
        if trace:
            slice_s = min(float(traffic["trace_seconds"]), max(0.5, args.seconds - TRACE_START_S - 1))
            time.sleep(max(0.0, t0 + min(TRACE_START_S, args.seconds / 4) - time.monotonic()))
            status, body, _ = ctl.request("POST", "/profile/start", {"dir": profile_dir})
            if status != 200:
                raise BenchFailure(f"/profile/start: HTTP {status}: {body}")
            marks["profile_started"] = time.monotonic() - t_child
            time.sleep(slice_s)
            marks["profile_stopped"] = time.monotonic() - t_child
            status, body, _ = ctl.request("POST", "/profile/stop")
            if status != 200:
                raise BenchFailure(f"/profile/stop: HTTP {status}: {body}")
            marks["profile_flushed"] = time.monotonic() - t_child
            profiled = True
        # The window is [t0, t0 + seconds] whatever the profiler's flush took.
        t1 = t0 + args.seconds
        time.sleep(max(0.0, t1 - time.monotonic()))
        drained = loop.stop(float(traffic["request_timeout_s"]) + 5.0)
        marks["drained"] = time.monotonic() - t_child

        # --- what served it.
        counters1 = fetch_counters(ctl, endpoints)
        _, health, _ = ctl.request("GET", "/healthz")
        status, m1, _ = ctl.request("GET", "/bench/marks")
        if status != 200:
            raise BenchFailure(f"/bench/marks: HTTP {status}")
        status, costs, _ = ctl.request("GET", "/costs")
        if status != 200:
            raise BenchFailure(f"/costs: HTTP {status}")

        samples = [s for s in loop.snapshot() if t0 <= s.t_done <= t1]
        good = [s for s in samples if s.ok]
        failed = [s for s in samples if not s.ok]

        # --- correctness, and the device the server reports.
        dev = costs.get("device") or {}
        dev_peaks = dev.get("peaks") or {}
        platform = dev_peaks.get("platform")
        count = int(dev_peaks.get("n_devices") or 0)
        if not rehearsal:
            peaks_for(str(dev_peaks.get("device_kind")))  # unknown chip = error
            if count != cell.chips:
                raise BenchFailure(f"{count} device(s) for a {cell.chips}-chip cell")
        pallas = (health.get("engine_queue") or {}).get("pallas") or {}
        paths = pallas.get("paths") or {}
        resets = prom_total(m1["engine_metrics"], "mcpx_engine_resets_total")
        compiles1 = prom_total(m1["engine_metrics"], "mcpx_engine_compiles_total")
        edges = uncovered_edges((s.t_done for s in samples), t0, t1)
        problems = correctness_problems(
            failed=failed, n_good=len(good), drained=drained, edges=edges, ref=ref, resets=resets,
            compiles=(compiles0, compiles1), platform=platform, pallas=pallas,
            kernel_paths=m1["kernel_paths"], rehearsal=rehearsal,
        )

        hbm = dev.get("hbm") or []
        peak_bytes = max((int(h.get("peak_bytes_in_use") or 0) for h in hbm), default=0)
        in_use_bytes = max((int(h.get("bytes_in_use") or 0) for h in hbm), default=0)
        device = {"platform": platform, "kind": dev_peaks.get("device_kind"),
                  "count": count, "memory_peak_bytes": peak_bytes}

        # --- metrics.
        lat = [s.latency_ms for s in samples]
        values = {
            "plans_per_s": completion_rate(s.t_done for s in good),
            "plan_p50_ms": quantile(lat, 0.5),
            "plan_p80_ms": quantile(lat, 0.8),
            "setup_s": setup_s,
        }
        info = {
            "cell": cell.name, "seed": args.seed, "seconds": args.seconds, "trace": int(trace),
            "platform": platform, "device_kind": device["kind"], "devices": count,
            "setup_marks_s": {k: round(v, 3) for k, v in sorted(marks.items(), key=lambda kv: kv[1])},
            "reference": ref, "compiles": compiles1, "resets": resets,
            "kernel_dispatches": {p: (paths.get(p) or {}).get("dispatches") for p in paths},
            "window": {"completed": len(samples), "fresh": sum(s.fresh for s in samples),
                       "hits": sum(not s.fresh for s in samples),
                       "all_requests": len(loop.snapshot()),
                       "completed_per_window_s": len(good) / args.seconds,
                       "uncovered_edges_s": edges and edges[0],
                       "widest_gap_s": edges and edges[1]},
            "candidates": {k: values[k] for k in ("plans_per_s", "plan_p50_ms", "plan_p80_ms")},
            "problems": problems,
        }
        out_metrics: dict[str, dict] = {}
        breakdown = None
        if not trace:
            for m in cell.end_to_end:
                if values.get(m.name) is not None:
                    out_metrics[m.name] = {"value": values[m.name], "unit": m.unit}
        else:
            traces = []
            for s in samples:
                if s.trace_id:
                    status, body, _ = ctl.request("GET", f"/traces/{s.trace_id}")
                    if status == 200:
                        traces.append(body)
            reduced = None
            if profiled:
                raw_path = os.path.join(run_dir, "xplane_events.json")
                r = subprocess.run(
                    [sys.executable, os.path.join(HERE, "xplane.py"), profile_dir, raw_path],
                    env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=spec.ROOT,
                    capture_output=True, text=True, timeout=300,
                )
                if r.returncode != 0:
                    raise BenchFailure(f"trace reduction failed: {r.stderr[-800:]}")
                with open(raw_path) as f:
                    raw = json.load(f)
                reduced = xplane.reduce_device(
                    raw["planes"], wall_s=marks["profile_stopped"] - marks["profile_started"]
                )
                if not args.keep_trace:  # hundreds of MB a run otherwise
                    shutil.rmtree(profile_dir, ignore_errors=True)
                    os.remove(raw_path)
                info["trace_files"] = raw["files"]
                info["trace_planes"] = raw["plane_names"]
            if not rehearsal:
                if not reduced or reduced["busy_s"] <= 0:
                    raise BenchFailure("the traced slice shows no operation on the device")
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            with open(os.path.join(run_dir, "traces.json"), "w") as f:
                json.dump(traces, f)
            ev = readers.Evidence(
                gen_late_ms=[s.gen_late_ms for s in samples],
                traces=traces,
                counters_before=counters0,
                counters_after=counters1,
                # A CPU rehearsal has no device trace: nothing is ever
                # written under a device metric's name from it.
                device=None if rehearsal else reduced,
                memory_in_use_bytes=None if rehearsal else in_use_bytes,
                config=cell.config,
                device_kind=device["kind"],
            )
            info["traces_read"] = len(traces)
            info["plan_decode_tokens"] = readers.histogram(ev, "engine.decode", "tokens")
            for m in cell.per_layer:
                v = readers.read_metric(ev, m.reader, m.args, found)
                if v is not None:
                    out_metrics[m.name] = {"value": v, "unit": m.unit}
        print("bench-info " + json.dumps(info), flush=True)
        result = {"correct": not problems, "attempted": len(samples), "failed": len(failed),
                  "metrics": out_metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        return result
    except BaseException:
        print(f"bench: --- tail of {log_path} ---\n{tail(log_path)}", file=sys.stderr, flush=True)
        raise
    finally:
        if loop is not None:
            loop.stop(0.0)
        ctl.close()
        stop_child(child)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the raw profile and its event dump in the run directory")
    ap.add_argument("--control", choices=("", "int8-weights"), default="",
                    help="negative control of the reference check: the program's step runs on "
                         "int8-rounded weights (a second copy: only where two fit the chip), and the "
                         "run must come out not correct")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tests only: model=test, interpreted kernel, CPU backend")
    args = ap.parse_args(argv)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        if args.seconds is None:
            args.seconds = float(spec.load_benchmark()["run_seconds"])
        result = run(args)
    except (BenchFailure, spec.SpecError) as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if args.rehearse_cpu:
        result = {"rehearsal": True, **result}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
