"""What the rehearsal children of tier-1 share (PERF.md Open question 12).

A helper module, not collected. ``serve`` starts one rehearsal child of the
chip harness (``benchmarks/chip/child.py --rehearse-cpu``) for a cell of
``BENCHMARK.json`` and returns what ``run.py`` would have fetched from it; the
``FED*`` lists say which metric files each child's tests read. The dense and
the sparse child are ``tests/test_chip_harness.py``'s; every other
configuration has a ``tests/test_<block>_rehearsal.py`` of its own beside its
``tests/test_<block>_block.py``, and a new configuration adds those two files
and edits none. Each ``FED*`` list is a pure function of the JSON files, and
some subtract another cell's: they are computed here, in one place.
"""

import dataclasses
import glob
import json
import os
import subprocess
import sys
import time

from mcpx.core.config import PlannerConfig
from tests.helpers import by_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")


def _by_path(name):
    return by_path("chip_harness_" + name, os.path.join(CHIP_DIR, name + ".py"))


CELL = "olmo2-1b.distinct-closed"
# Readers that need a device profile, allocator statistics or the load
# generator's own clock: nothing a served program on the CPU can feed.
NOT_FED_HERE = {"device_op_share", "device_idle_share", "memory_in_use", "endpoint_spread",
                "client_quantile", "mla_roofline", "index_roofline", "selected_roofline",
                "ssm_state_roofline", "routed_experts_roofline", "linear_window_roofline",
                "block_score_roofline", "attn_gathered_roofline", "selective_scan_roofline"}
# The one metric a rehearsal leaves out by its NAME: the CPU backend gets no
# persistent compilation cache (``utils/backend.py::enable_compilation_cache``),
# so no compile asks it and the gauge is absent, not 0 (ISSUE 54).
NOT_FED_ON_THE_CPU = {"startup.cache_hit_share"}
# What a cell whose file names no shortlist is served with: ``serve`` writes the
# shortlist beside every ``warmup_max_len`` it is given.
PLANNER_SHORTLIST = PlannerConfig().shortlist_top_k


# The cell whose block has sparse experts and windowed layers: the
# engine.segment attributes that only such a block writes (PR 33).
SPARSE_CELL = "mellum2-12b-a2.5b.distinct-closed"
METRICS = [json.load(open(f)) for f in sorted(glob.glob(os.path.join(CHIP_DIR, "metrics", "*.json")))]
_CELLS_OF = {m["name"]: m.get("workloads")
             for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["per_layer"]}


def fed_in(cell):
    """The metrics of ``cell`` that a served program on the CPU can feed."""
    return [m for m in METRICS if m["reader"] not in NOT_FED_HERE and m["name"] not in NOT_FED_ON_THE_CPU
            and (_CELLS_OF[m["name"]] is None or cell in _CELLS_OF[m["name"]])]


# The cell whose sparse layers follow leading dense ones, beside a shared
# expert: the attributes and the per-expert counter its metrics read (PR 36).
MIXED_CELL = "trinity-mini.distinct-closed"

# The cell whose cache is latent and whose sparse layers hold a share of the
# router's experts: the attributes its metrics read (PR 42).
LATENT_CELL = "a.x-k1.wide-shortlist-closed"

# The cell whose latent cache is read through a learned index, behind a
# catalogue head longer than a prefill bucket (PR 44).
INDEX_CELL = "deepseek-v3.2-exp.catalogue-closed"

# The cell whose layers are a mixer OR a feed-forward alone and whose rows keep
# a recurrent state beside the pages (PR 48).
STATE_CELL = "nemotron-3-super.distinct-closed"

# The cell whose layers are a mixer + feed-forward, the mixer linear attention
# or attention that reads chosen key blocks, behind a catalogue head whose END
# STATE every row starts from (PR 51).
BLOCK_CELL = "minicpm-sala.catalogue-2k-closed"

# The cell whose layers are a mixer + feed-forward, the mixer a gated short
# convolution whose tail is kept a slot AND a page, or attention on heads of
# 64, the feed-forward dense then routed: its rows take radix hits (PR 56).
CONV_CELL = "lfm2-24b-a2b.distinct-closed"

# The cell whose layers are a Mamba-1 selective scan or one-KV-head attention,
# each followed by the dense feed-forward, the walk scanned over runs of like
# layers, every row prefilled whole (PR 58).
SCAN_CELL = "jamba2-3b.wide-shortlist-closed"

FED = fed_in(CELL)
FED_SPARSE = [m for m in fed_in(SPARSE_CELL) if m not in FED]
FED_MIXED = [m for m in fed_in(MIXED_CELL) if m not in FED]
FED_LATENT = [m for m in fed_in(LATENT_CELL) if m not in FED + FED_MIXED]
FED_INDEX = [m for m in fed_in(INDEX_CELL) if m not in FED + FED_MIXED + FED_LATENT]
FED_STATE = [m for m in fed_in(STATE_CELL) if m not in FED]
FED_BLOCK = [m for m in fed_in(BLOCK_CELL) if m not in FED]
FED_CONV = [m for m in fed_in(CONV_CELL) if m not in FED]
FED_SCAN = [m for m in fed_in(SCAN_CELL) if m not in FED]


def serve(cell_name, tmp_path_factory, warmup_max_len=None, shortlist_top_k=None):
    """One rehearsal child of the harness (``child.py --rehearse-cpu``: the
    served app at the cell's block's rehearsal size, LLM planner, interpreted
    kernel, tracing at rate 1), five fresh ``/plan`` requests and one re-send,
    and around them everything ``run.py`` fetches, through ``run.py``'s own
    functions."""
    fed = fed_in(cell_name)
    run = sys.modules.get("chip_harness_run") or _by_path("run")  # imports its siblings by bare name
    if CHIP_DIR in sys.path:
        sys.path.remove(CHIP_DIR)
    readers, spec, loadgen = (sys.modules[n] for n in ("readers", "spec", "loadgen"))
    cell = spec.load_cell(cell_name)
    if warmup_max_len is not None:
        config = json.loads(json.dumps(cell.config))
        config["warmup_max_len"] = warmup_max_len
        config["mcpx"]["planner"]["shortlist_top_k"] = shortlist_top_k
        cell = dataclasses.replace(cell, config=config)
    gen = loadgen.Generator({**cell.traffic, "registry_services": 120}, seed=30)
    names = {r["name"] for r in gen.registry}
    endpoints = sorted({m["args"]["endpoint"] for m in fed if "endpoint" in m["args"]} | {"/metrics"})

    run_dir = str(tmp_path_factory.mktemp("served"))
    with open(os.path.join(run_dir, "registry.json"), "w") as f:
        json.dump(gen.registry, f)
    port = run.free_port()
    cfg_path = os.path.join(run_dir, "mcpx_config.json")
    with open(cfg_path, "w") as f:
        json.dump(run.mcpx_config(cell, run_dir, port, trace=True, rehearsal=True), f)
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags))
    log_path = os.path.join(run_dir, "server.log")
    with open(log_path, "wb") as log:
        child = subprocess.Popen(
            [sys.executable, os.path.join(CHIP_DIR, "child.py"), "--config-file", cell.config_file,
             "--mcpx-config", cfg_path, "--port", str(port), "--rehearse-cpu"],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
    ctl = run.Client(port, run.SCRAPE_TIMEOUT_S)
    loop = None
    try:
        # The first /healthz body whose start-up timeline has a phase open,
        # taken while the engine warms: what an operator polls a cold start for.
        warming, t_child = None, time.monotonic()
        while warming is None and child.poll() is None and time.monotonic() - t_child < run.WARM_DEADLINE_S:
            status, body, _ = ctl.request("GET", "/healthz")
            if status == 200 and (body.get("started") or (body.get("startup") or {}).get("current")):
                warming = body
            else:
                time.sleep(0.2)
        run.wait_started(child, ctl, t_child, {})
        marks0 = ctl.request("GET", "/bench/marks")[1]
        counters0 = run.fetch_counters(ctl, endpoints)

        def post_factory():
            c = run.Client(port, float(cell.traffic["request_timeout_s"]))

            def post(intent):
                status, body, headers = c.request("POST", "/plan", {"intent": intent})
                why = run.plan_problem(status, body, names, cell.traffic["origin"])
                return (not why), why, headers.get("X-Trace-Id", "") if headers else ""

            return post

        loop = loadgen.Loop(gen, post_factory, clients=2)
        loop.start()
        deadline = time.monotonic() + run.WARM_DEADLINE_S
        while loop.fresh_done < 5 and time.monotonic() < deadline and child.poll() is None:
            time.sleep(0.05)
        drained = loop.stop(float(cell.traffic["request_timeout_s"]))
        samples = loop.snapshot()
        ok, why, trace_id = post_factory()(samples[0].intent)  # the re-send: a plan-cache hit
        samples.append(loadgen.Sample(0.0, 0.0, 0.0, ok, False, why, trace_id, intent=samples[0].intent))

        counters1 = run.fetch_counters(ctl, endpoints)
        health = ctl.request("GET", "/healthz")[1]
        marks1 = ctl.request("GET", "/bench/marks")[1]
        costs = ctl.request("GET", "/costs")[1]
        traces = []
        for s in samples:
            status, body, _ = ctl.request("GET", f"/traces/{s.trace_id}")
            if status == 200:
                traces.append(body)
    except BaseException:
        print(run.tail(log_path), file=sys.stderr)
        raise
    finally:
        if loop is not None:
            loop.stop(0.0)
        ctl.close()
        run.stop_child(child)
    ev = readers.Evidence(  # as run.py builds it in a rehearsal
        gen_late_ms=[s.gen_late_ms for s in samples], traces=traces,
        counters_before=counters0, counters_after=counters1, device=None,
        memory_in_use_bytes=None, config=cell.config, device_kind="cpu",
    )
    pallas = (health.get("engine_queue") or {}).get("pallas") or {}
    found = readers.vocabulary()  # made once, as run.py does
    return dict(
        run=run, ev=ev, found=found, histogram=readers.histogram,
        read=lambda reader, args: readers.read_metric(ev, reader, args, found), samples=samples, drained=drained, health=health,
        warming=warming, readers=readers,
        pallas=pallas, paths=pallas.get("paths") or {}, costs=costs,
        kernel_paths=marks1["kernel_paths"],
        engine_metrics=(marks0["engine_metrics"], marks1["engine_metrics"]),
        hits_before=(counters0.get("/metrics") or {}).get('mcpx_engine_prefix_state_total{event="hit"}'),
    )


# The engine.segment attributes that only a block with sparse experts or
# windowed layers writes; a dense block writes none of them.
LAYER_KIND_ATTRS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
                    "rows_past_window", "rows_live", "moe_prefill_assignments", "moe_prefill_rows",
                    "moe_expert_steps", "moe_kernel_steps")


def _segments(served):
    return [sp for tr in served["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.segment"]


def _segments_once(served):
    """One engine.segment span a dispatched segment (its rows' spans agree)."""
    seen = {}
    for sp in _segments(served):
        seen.setdefault(sp["attrs"].get("seq"), sp)
    return list(seen.values())
