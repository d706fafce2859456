"""The thin bridge from tier-1 to the chip harness (PERF.md Open question 12).

Two contracts between the program and ``benchmarks/chip``, each driven from
here with the harness imported by path, never copied. CPU, interpreted
kernel: correctness readings, not device numbers.

1. The comparison that decides a benchmark run's ``correct``
   (``reference.py::compare_with_engine_step``), through the ``gemma`` block
   module, at ``model=test`` size with 4 KV heads, so that on the 2 x 2 mesh KV
   heads split over ``model`` and rows over ``data``: the arm of
   ``_ragged_kernel_on_mesh`` and ``_write_kv_window`` that the four-chip cell
   ``mistral-7b.distinct-closed`` takes.
2. What the harness READS from the served program (``served``, below): the
   spans, span attributes, counters and health fields behind every per-layer
   metric and behind ``correct``. A span, an attribute or a counter renamed in
   the program leaves a ``null`` in the ledger's ``per_layer`` column, which
   only a ``benchmark`` PR can repair; here it fails a test first.
"""

import dataclasses
import glob
import importlib.util
import json
import math
import os
import re
import subprocess
import sys
import time

import jax
import pytest

from mcpx.models.gemma.params import load_or_init
from mcpx.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")


def _by_path(name):
    spec = importlib.util.spec_from_file_location(
        "chip_harness_" + name, os.path.join(CHIP_DIR, name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # its dataclasses look their module up
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def harness():
    spec = _by_path("spec")
    return _by_path("reference"), spec.load_block("gemma", CHIP_DIR)


def _compare(harness, mesh_shape, control=""):
    reference, block = harness
    data, model = mesh_shape
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    cfg = dataclasses.replace(block.rehearsal_config(3072), n_kv_heads=4)
    params, _ = load_or_init(cfg, "", mesh)
    out = reference.compare_with_engine_step(
        block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 27, interpret=True,
        page_size=16, rows=4, pages_per_row=32, prefill_len=128, n_decode=2, control=control,
    )
    assert (out["tol_rms"], out["tol_max"]) == reference.tol(16) == (0.02, 0.12)
    return out


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_paged_step_with_kv_heads_over_model_agrees_with_the_plain_reference(harness, mesh_shape):
    out = _compare(harness, mesh_shape)
    assert out["ok"] and out["positions"] == 12
    assert 0 < out["rms_rel_err"] < out["max_rel_err"] < out["tol_max"]


def test_int8_weights_control_fails_on_the_mesh(harness):
    plain, control = _compare(harness, (2, 2)), _compare(harness, (2, 2), control="int8-weights")
    assert not control["ok"] and control["rms_rel_err"] > 3 * plain["rms_rel_err"]


# ------------------------------------------- what the harness reads, served
CELL = "olmo2-1b.distinct-closed"
# Readers that need a device profile, allocator statistics or the load
# generator's own clock: nothing a served program on the CPU can feed.
NOT_FED_HERE = {"device_op_share", "device_idle_share", "memory_in_use", "endpoint_spread",
                "client_quantile", "mla_roofline", "index_roofline", "selected_roofline",
                "ssm_state_roofline", "routed_experts_roofline", "linear_window_roofline",
                "block_score_roofline", "attn_gathered_roofline", "selective_scan_roofline"}
LABELLED_SAMPLE = 'mcpx_engine_compiles_total{executable="admit"}'
# The one metric a rehearsal leaves out by its NAME: the CPU backend gets no
# persistent compilation cache (``utils/backend.py::enable_compilation_cache``),
# so no compile asks it and the gauge is absent, not 0 (ISSUE 54).
NOT_FED_ON_THE_CPU = {"startup.cache_hit_share"}


# The cell whose block has sparse experts and windowed layers: the
# engine.segment attributes that only such a block writes (PR 33).
SPARSE_CELL = "mellum2-12b-a2.5b.distinct-closed"
METRICS = [json.load(open(f)) for f in sorted(glob.glob(os.path.join(CHIP_DIR, "metrics", "*.json")))]
_CELLS_OF = {m["name"]: m.get("workloads")
             for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["per_layer"]}


def _fed_in(cell):
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

FED = _fed_in(CELL)
FED_SPARSE = [m for m in _fed_in(SPARSE_CELL) if m not in FED]
FED_MIXED = [m for m in _fed_in(MIXED_CELL) if m not in FED]
FED_LATENT = [m for m in _fed_in(LATENT_CELL) if m not in FED + FED_MIXED]
FED_INDEX = [m for m in _fed_in(INDEX_CELL) if m not in FED + FED_MIXED + FED_LATENT]
FED_STATE = [m for m in _fed_in(STATE_CELL) if m not in FED]
FED_BLOCK = [m for m in _fed_in(BLOCK_CELL) if m not in FED]
FED_CONV = [m for m in _fed_in(CONV_CELL) if m not in FED]
FED_SCAN = [m for m in _fed_in(SCAN_CELL) if m not in FED]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return _serve(CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_sparse(tmp_path_factory):
    return _serve(SPARSE_CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_mixed(tmp_path_factory):
    return _serve(MIXED_CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_latent(tmp_path_factory):
    # The cell's 128-service shortlist and its 1,024 warm-up bucket make a
    # rehearsal of minutes; the attributes' names do not depend on either.
    return _serve(LATENT_CELL, tmp_path_factory, warmup_max_len=128, shortlist_top_k=8)


@pytest.fixture(scope="module")
def served_index(tmp_path_factory):
    # The cell's own shortlist (1,000 >= the 120 services served here: a
    # catalogue of ~800 tokens, past the rehearsal block's 256-token buckets,
    # so the head is built in chunks) with a warm-up the CPU can afford.
    return _serve(INDEX_CELL, tmp_path_factory, warmup_max_len=256, shortlist_top_k=1000)


@pytest.fixture(scope="module")
def served_state(tmp_path_factory):
    return _serve(STATE_CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_block(tmp_path_factory):
    # The cell's own shortlist (1,500 >= the 120 services served here: a
    # catalogue of ~800 tokens, past the rehearsal block's 256-token buckets and
    # the 256 tokens of the 4 blocks a query keeps) with a warm-up the CPU can afford.
    return _serve(BLOCK_CELL, tmp_path_factory, warmup_max_len=256, shortlist_top_k=1500)


@pytest.fixture(scope="module")
def served_conv(tmp_path_factory):
    return _serve(CONV_CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_scan(tmp_path_factory):
    # As ``served_latent``: the attributes' names depend neither on the cell's
    # 128-service shortlist nor on its 1,024 warm-up bucket.
    return _serve(SCAN_CELL, tmp_path_factory, warmup_max_len=128, shortlist_top_k=8)


def _serve(cell_name, tmp_path_factory, warmup_max_len=None, shortlist_top_k=None):
    """One rehearsal child of the harness (``child.py --rehearse-cpu``: the
    served app at the cell's block's rehearsal size, LLM planner, interpreted
    kernel, tracing at rate 1), five fresh ``/plan`` requests and one re-send,
    and around them everything ``run.py`` fetches, through ``run.py``'s own
    functions."""
    fed = _fed_in(cell_name)
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


def test_the_requests_were_answered(served):
    assert served["drained"] and len(served["samples"]) >= 6
    assert [s.why for s in served["samples"] if not s.ok] == []
    assert len(served["ev"].traces) == len(served["samples"])
    # the re-send is the plan cache's hit, the fresh intents its misses
    hit_share = next(m for m in FED if m["name"] == "planner.cache_hit_share")
    assert 0 < served["read"](hit_share["reader"], hit_share["args"]) < 100


@pytest.mark.parametrize("metric", FED, ids=[m["name"] for m in FED])
def test_the_program_feeds_the_metric(served, metric):
    v = served["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v), (
        f"{metric['name']}: reader {metric['reader']}{metric['args']} found nothing in what the "
        "served program emits (a span, an attribute or a counter it reads was renamed?)"
    )


def test_every_metric_is_fed_here_or_left_out_by_its_readers_name(served):
    assert all(m["reader"] in served["found"] for m in METRICS)
    assert len(FED) >= 17 and NOT_FED_HERE <= set(served["found"])
    assert NOT_FED_ON_THE_CPU <= {m["name"] for m in METRICS}
    assert {m["name"] for m in FED_SPARSE} == {
        "moe.experts_touched_share", "moe.tok_per_touched_expert", "attn.rows_past_window_share",
        "moe.prefill_rows_per_assignment", "moe.kernel_step_share"}


# ------------------------------------------------ the start-up timeline (PR 54)
STARTUP_METRICS = [m for m in METRICS if m["name"].startswith("startup.")]
TOP_PHASES = ["startup.import", "startup.build", "startup.backend", "startup.weights",
              "startup.pools", "startup.warmup", "startup.registry_grammar"]


def test_eleven_start_up_metrics_read_one_sample_of_metrics_each():
    assert len(STARTUP_METRICS) == 11 and {m["name"] for m in STARTUP_METRICS} - NOT_FED_ON_THE_CPU == {
        m["name"] for m in FED if m["name"].startswith("startup.")}
    for m in STARTUP_METRICS:
        assert (m["reader"], m["layer"], m["moves"], m["args"]["endpoint"]) == (
            "endpoint_value", "start-up", "setup_s", "/metrics")
    # One label a gauge: the path is the sample as the exposition prints it.
    assert all(m["args"]["path"].count("=") <= 1 for m in STARTUP_METRICS)
    one_chip = {w["name"] for w in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]
                if w["chips"] == 1}
    assert set(_CELLS_OF["startup.weights_s"]) == one_chip and len(one_chip) >= 10
    assert all(_CELLS_OF[m["name"]] is None for m in STARTUP_METRICS if m["name"] != "startup.weights_s")


def test_the_cache_hit_share_is_absent_in_a_rehearsal_and_present_once_the_gauge_is_set(served):
    metric = next(m for m in STARTUP_METRICS if m["name"] == "startup.cache_hit_share")
    assert served["read"](metric["reader"], metric["args"]) is None
    from mcpx.telemetry.metrics import Metrics

    metrics = Metrics()
    metrics.set_startup(ready_s=35.0, executables=17, cache_hit_ratio=0.84)
    ev = dataclasses.replace(
        served["ev"], counters_after={"/metrics": served["run"].prom_samples(metrics.render().decode())})
    assert served["readers"].read_metric(ev, metric["reader"], metric["args"], served["found"]) == 0.84


def _phases(served):
    st = served["health"]["startup"]
    return st, {n: [p for p in st["phases"] if p["name"] == n] for n in {p["name"] for p in st["phases"]}}


def test_the_top_level_phases_tile_the_process_start_to_ready(served):
    st, by_name = _phases(served)
    top = [p for p in st["phases"] if p["name"].startswith("startup.")]
    assert [p["name"] for p in top] == TOP_PHASES  # in order, each once; /proc gives the first
    assert top[0]["t0_s"] == 0.0 and st["ready_s"] > 0.0
    assert sum(p["t1_s"] - p["t0_s"] for p in top) == pytest.approx(st["ready_s"], rel=0.02)
    for a, b in zip(top, top[1:]):  # each starts where the one before it ended
        assert b["t0_s"] - a["t1_s"] == pytest.approx(0.0, abs=0.02 * st["ready_s"])
    assert st["ready_s"] == pytest.approx(
        served["read"]("endpoint_value", {"endpoint": "/metrics", "path": "mcpx_startup_ready_seconds"}),
        abs=0.001)


def test_the_warm_ups_children_tile_it(served):
    st, by_name = _phases(served)
    (warmup,) = by_name["startup.warmup"]
    children = [p for p in st["phases"] if p["name"].startswith("warmup.")]
    assert {p["name"] for p in children} == {"warmup.grammar_tables", "warmup.prefill", "warmup.admit",
                                             "warmup.segment", "warmup.merge", "warmup.cost_table"}
    assert all(warmup["t0_s"] <= p["t0_s"] and p["t1_s"] <= warmup["t1_s"] for p in children)
    assert sum(p["t1_s"] - p["t0_s"] for p in children) == pytest.approx(
        warmup["t1_s"] - warmup["t0_s"], rel=0.02)
    assert all({"A", "T"} <= set(p) for p in by_name["warmup.prefill"])
    # the gauges carry the per-bucket phases summed a kind
    for kind in ("warmup.prefill", "warmup.cost_table"):
        gauge = served["read"]("endpoint_value", {
            "endpoint": "/metrics", "path": 'mcpx_startup_phase_seconds{phase="%s"}' % kind})
        assert gauge == pytest.approx(sum(p["t1_s"] - p["t0_s"] for p in by_name[kind]), abs=0.01)


def test_what_jax_did_fits_inside_every_phase(served):
    st, by_name = _phases(served)
    for p in st["phases"]:
        wall = p["t1_s"] - p["t0_s"]
        assert p["lower_s"] + p["backend_s"] <= wall + 0.002, p
        assert p["cache_load_s"] <= p["backend_s"], p
        assert p["other_s"] == pytest.approx(wall - p["lower_s"] - p["backend_s"], abs=0.003)
    (warmup,), (grammar,) = by_name["startup.warmup"], by_name["startup.registry_grammar"]
    assert warmup["backend_s"] > 0.0 and warmup["lower_s"] > 0.0  # the CPU compiles: no cache to load from
    # every executable the sentinel counted at started was one of a phase's
    executables = sum(p["executables"] for p in st["phases"] if p["name"].startswith("startup."))
    assert executables == warmup["executables"] + grammar["executables"] == _compiles(served)[0]
    assert executables == served["read"](
        "endpoint_value", {"endpoint": "/metrics", "path": "mcpx_startup_executables"})
    assert "error" not in grammar and served["health"].get("warm_error") is None


def test_healthz_names_the_open_phase_while_the_engine_warms_and_none_after(served):
    warming, after = served["warming"]["startup"], served["health"]["startup"]
    assert served["warming"]["started"] is False and served["warming"]["engine"] in ("cold", "warming", "ready")
    names = {"startup.build"} | set(TOP_PHASES[2:]) | {
        "warmup.grammar_tables", "warmup.prefill", "warmup.admit", "warmup.segment", "warmup.merge",
        "warmup.cost_table"}
    assert warming["current"] in names and warming["ready_s"] is None
    open_now = [p for p in warming["phases"] if p["t1_s"] is None]
    assert open_now and open_now[-1]["name"] == warming["current"]
    assert after["current"] is None and all(p["t1_s"] is not None for p in after["phases"])
    assert set(after["cache"]) >= {"dir", "files", "bytes", "max_bytes"} and after["cache"]["dir"] is None


# The engine.segment attributes that only a block with sparse experts or
# windowed layers writes; a dense block writes none of them.
LAYER_KIND_ATTRS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
                    "rows_past_window", "rows_live", "moe_prefill_assignments", "moe_prefill_rows",
                    "moe_expert_steps", "moe_kernel_steps")


def _segments(served):
    return [sp for tr in served["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.segment"]


def test_costs_counts_the_params_a_token_reads(served, served_sparse):
    dense, sparse = served["costs"]["model"], served_sparse["costs"]["model"]
    assert dense["params_active_per_token"] == dense["params_held"] > 0
    # rehearsal size: 2 of 8 experts a layer of 3 x 128 x 64 parameters, 4 layers
    assert sparse["params_held"] - sparse["params_active_per_token"] == 4 * 6 * 3 * 128 * 64
    assert sparse["flops_per_token"] == 2 * sparse["params_active_per_token"]


def test_a_dense_block_writes_no_layer_kind_attribute(served):
    assert _segments(served)
    absent = LAYER_KIND_ATTRS + ("attn_query_slots", "attn_key_blocks", "attn_run_blocks")  # a latent block's alone
    assert not any(a in sp["attrs"] for sp in _segments(served) for a in absent)
    assert "mcpx_engine_moe_expert_tokens_total{" not in served["engine_metrics"][1]
    profile = served["health"]["engine_queue"]["worker_profile"]
    assert not any(a in profile for a in absent)


@pytest.mark.parametrize("metric", FED_SPARSE, ids=[m["name"] for m in FED_SPARSE])
def test_the_sparse_block_feeds_its_metrics(served_sparse, metric):
    v = served_sparse["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v) and 0 <= v
    if metric["name"] == "moe.experts_touched_share":
        assert 0 < v <= 1
    if metric["name"] == "moe.tok_per_touched_expert":
        assert v >= 1  # a touched expert has at least one token


@pytest.mark.parametrize("attr", LAYER_KIND_ATTRS)
def test_the_sparse_blocks_segments_carry_the_attribute(served_sparse, attr):
    segments = _segments(served_sparse)
    assert segments and all(isinstance(sp["attrs"].get(attr), int) for sp in segments)


def test_the_layer_kind_attributes_add_up(served_sparse):
    """At the rehearsal size: 4 sparse layers, 8 experts held, 2 a token, a
    window of 8 that every prompt has passed."""
    layers, experts, k = 4, 8, 2
    for sp in _segments(served_sparse):
        a = sp["attrs"]
        assert a["moe_expert_slots"] == a["forwards"] * layers * experts
        assert 0 < a["moe_experts_touched"] <= min(a["moe_expert_slots"], a["moe_assignments"])
        # every live token of every forward chose k experts in each layer, all held here
        assert a["moe_assignments"] % (k * layers) == 0
        # ... at least one token a live row a forward (the device's own count,
        # ISSUE 40), and no fewer than this row emitted here. Its first
        # segment's ``tokens`` counts the admission's sample too, which no
        # segment forward routed: without the - 1 a lone row that decoded one
        # token a forward failed this once in a few hundred runs.
        routed = a["moe_assignments"] // (k * layers)
        assert routed >= a["row_forwards_live"] and routed >= a["tokens"] - 1
        assert 0 < a["rows_live"] <= 8 and a["rows_past_window"] == a["rows_live"]
        # the admission prefills in front of the segment: none, or k experts a
        # prompt token in each layer, multiplied in whole tiles of 64 rows
        assert a["moe_prefill_assignments"] % (k * layers) == 0
        assert a["moe_prefill_rows"] % 64 == 0 and a["moe_prefill_rows"] >= a["moe_prefill_assignments"]
        assert (a["moe_prefill_assignments"] > 0) == (a["prefill_rows"] > 0)
    # lifetime sums, and the per-expert counter beside them
    profile = served_sparse["health"]["engine_queue"]["worker_profile"]
    once = _segments_once(served_sparse)
    for attr in ("moe_prefill_assignments", "moe_prefill_rows"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in once) > 0
    # they came back in the harvest's own fetch: the worker blocked on the
    # device once a dispatched segment (the last may still be in flight)
    phases = profile["phases"]
    assert 0 <= phases["dispatch_submit"]["count"] - phases["sync"]["count"] <= 2
    per_expert = {key: v for key, v in served_sparse["ev"].counters_after["/metrics"].items()
                  if key.startswith("mcpx_engine_moe_expert_tokens_total{")}
    assert len(per_expert) == experts
    assert sum(per_expert.values()) <= profile["moe_assignments"]  # the scrape came first
    assert profile["moe_expert_slots"] >= profile["moe_experts_touched"] > 0


def test_the_segments_say_how_often_the_experts_kernel_engaged(served_sparse):
    """``moe.kernel_step_share``: the expert steps taken through
    ``kernels/routed_experts.py`` over all expert steps of a segment's window,
    read by the harness's own ratio reader off ``engine.segment``. Every step
    is a kernel's (the rehearsal interprets them): the segment's forwards take
    ``routed_experts``, the admission prefills in front ``prefill_expert_window``
    (a cohort of one's window) or ``prefill_expert_tiles`` (past the ridge)."""
    share = served_sparse["read"]("span_attr_ratio", {
        "name": "engine.segment", "num": "moe_kernel_steps", "den": "moe_expert_steps",
        "num_per": "segment", "den_per": "segment"})
    assert share == 1.0
    decode_only = with_prefill = 0
    for sp in _segments(served_sparse):
        a = sp["attrs"]
        assert 0 < a["moe_kernel_steps"] == a["moe_expert_steps"]
        # a touched (forward, layer, expert) triple is one step of the decode
        # kernel; a prefill in front adds its own steps (touched experts, or tiles)
        assert (a["moe_kernel_steps"] == a["moe_experts_touched"]) == (a["moe_prefill_rows"] == 0)
        assert a["moe_kernel_steps"] >= a["moe_experts_touched"]
        decode_only += a["moe_prefill_rows"] == 0
        with_prefill += a["moe_prefill_rows"] > 0
    assert decode_only and with_prefill
    assert served_sparse["paths"]["decode"]["engaged"]  # the engine resolved the kernels on


def test_the_experts_kernels_name_is_what_its_metric_selects():
    """``kernel.moe_busy_share`` finds the call in a device trace by its name
    (``metrics/kernel.moe_busy_share.json``); the attention kernels' metrics
    must not count it. Renaming the ``pallas_call`` fails this first."""
    import jax.numpy as jnp

    from mcpx.engine.kernels.routed_experts import routed_experts

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if m["reader"] == "device_op_share"}
    assert _CELLS_OF["kernel.moe_busy_share"] == [SPARSE_CELL, MIXED_CELL, LATENT_CELL, STATE_CELL, CONV_CELL]
    bf, S = jnp.bfloat16, jax.ShapeDtypeStruct
    shapes = (
        S((64, 256), bf), S((64, 8), jnp.float32), S((2, 8, 256, 256), bf), S((2, 8, 256, 256), bf),
        S((2, 8, 256, 256), bf), S((8,), jnp.int32), S((), jnp.int32), S((), jnp.int32),
    )
    text = jax.jit(lambda *a: routed_experts(*a, act=jax.nn.silu)).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    (name,) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert re.search(regex["kernel.moe_busy_share"], name)
    assert not re.search(regex["kernel.attn_busy_share"], name)
    assert not re.search(regex["kernel.mla_busy_share"], name)


@pytest.mark.parametrize("metric", FED_MIXED, ids=[m["name"] for m in FED_MIXED])
def test_the_mixed_block_feeds_its_metrics(served_mixed, metric):
    assert {m["name"] for m in FED_MIXED} == {
        "moe.routed_bytes_share", "moe.touched_per_sparse_layer", "moe.load_max_over_mean",
        "moe.prefill_rows_per_assignment", "moe.kernel_step_share"}
    v = served_mixed["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "moe.kernel_step_share":
        assert v == 1.0  # decode windows, cohorts of one and grouped tiles: every step a kernel's
    if metric["name"] == "moe.routed_bytes_share":
        assert 0 < v < 1
    if metric["name"] == "moe.touched_per_sparse_layer":
        assert 2 <= v <= 8  # a live token touches its 2 experts; a layer has 8
    if metric["name"] == "moe.load_max_over_mean":
        assert 1 <= v <= 8  # even routing reads 1, one expert taking all reads 8
    if metric["name"] == "moe.prefill_rows_per_assignment":
        # grouped (the rehearsal's cohort prefill is 8 x 128 slots, past the
        # ridge): whole tiles of 64 rows, so at least 1; the loop over its 8
        # experts would read 1,024 x 8 rows for a cohort's few hundred assignments
        assert 1 <= v < 64


def test_the_mixed_blocks_attributes_count_sparse_layers_and_bytes(served_mixed):
    """At the rehearsal size: 2 dense layers, then 6 sparse ones of 8 experts
    held, 2 a token, beside a shared expert. The bytes are those of the
    leaves a forward reads, reckoned here from the tree's shapes."""
    sys.path.insert(0, REPO)
    import jax
    import numpy as np

    spec = sys.modules["spec"]
    cfg = spec.load_block("afmoe", CHIP_DIR).rehearsal_config(3072)
    from mcpx.models.gemma.model import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))
    stacks = {k: shapes["layers"][k] for k in ("w_gate", "w_up", "w_down")}
    expert = nbytes(stacks) // (6 * 8)
    assert expert == 3 * cfg.d_model * cfg.d_expert * 2
    rest = nbytes(shapes) - nbytes(stacks) - nbytes(shapes["embed"])
    segments = _segments(served_mixed)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["moe_layer_forwards"] == a["forwards"] * 6
        assert a["moe_expert_slots"] == a["forwards"] * 6 * 8
        assert 0 < a["moe_experts_touched"] <= min(a["moe_expert_slots"], a["moe_assignments"])
        assert a["moe_assignments"] % (2 * 6) == 0  # 2 experts a live token in each SPARSE layer
        assert a["weight_bytes_routed"] == a["moe_experts_touched"] * expert
        assert a["weight_bytes_read"] == a["weight_bytes_routed"] + a["forwards"] * rest
    profile = served_mixed["health"]["engine_queue"]["worker_profile"]
    for attr in ("moe_layer_forwards", "moe_expert_slots", "weight_bytes_routed", "weight_bytes_read"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_mixed)) > 0
    per_expert = {key: v for key, v in served_mixed["ev"].counters_after["/metrics"].items()
                  if key.startswith("mcpx_engine_moe_expert_tokens_total{")}
    assert len(per_expert) == 8 and sum(per_expert.values()) <= profile["moe_assignments"]
    # /costs: a token reads 2 + 1 of the 8 + 1 experts of a sparse layer, and all of a dense one
    model = served_mixed["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    assert model["params_held"] - model["params_active_per_token"] == 6 * 6 * 3 * cfg.d_model * cfg.d_expert


@pytest.mark.parametrize("metric", FED_LATENT, ids=[m["name"] for m in FED_LATENT])
def test_the_latent_block_feeds_its_metrics(served_latent, metric):
    assert {m["name"] for m in FED_LATENT} == {
        "attn.ctx_tok_per_call", "attn.latent_bytes_share", "moe.held_assignment_share",
        "attn.slots_per_row_call", "attn.page_run_share"}
    v = served_latent["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "attn.page_run_share":
        assert v == 0  # a context under 256 tokens has no whole key block to be a run
    if metric["name"] == "attn.slots_per_row_call":
        assert 1 <= v < 2  # a live row decodes a token or two of its window's 8 slots a forward
    if metric["name"] == "attn.ctx_tok_per_call":
        assert 60 < v < 200  # an 8-service shortlist's prompt and what was decoded behind it
    if metric["name"] in ("attn.latent_bytes_share", "moe.held_assignment_share"):
        assert 0 < v < 1


def test_the_latent_blocks_attributes_count_context_and_this_share(served_latent):
    """At the rehearsal size: the dense lead and one sparse layer, experts
    4..7 of 16 held, 2 a token; a cache row of 64 + 16 values a token a layer."""
    segments = _segments(served_latent)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] % 2 == 0 and 0 < a["attn_row_calls"] <= 8 * a["forwards"] * 2
        assert a["attn_ctx_tokens"] > a["attn_row_calls"]
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (64 + 16) * 2
        # a live row's score tile: its rung, from one slot to the window's 8
        assert a["attn_row_calls"] <= a["attn_query_slots"] <= 8 * a["attn_row_calls"]
        assert a["attn_key_blocks"] == a["attn_row_calls"] and a["attn_run_blocks"] == 0  # one part block a call
        assert a["moe_tokens_routed"] % 2 == 0  # 2 experts a live token in the one sparse layer
        assert 0 <= a["moe_assignments"] <= a["moe_tokens_routed"]
        assert a["moe_expert_slots"] == a["forwards"] * 1 * 4  # the 4 experts held
    profile = served_latent["health"]["engine_queue"]["worker_profile"]
    for attr in ("attn_ctx_tokens", "attn_row_calls", "attn_query_slots", "kv_bytes_read", "moe_tokens_routed"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_latent)) > 0
    per_expert = {key for key in served_latent["ev"].counters_after["/metrics"]
                  if key.startswith("mcpx_engine_moe_expert_tokens_total{")}
    assert per_expert == {f'mcpx_engine_moe_expert_tokens_total{{expert="{e}"}}' for e in range(4, 8)}
    # /costs counts the latent attention's leaves: the tree's own count
    spec = sys.modules["spec"]
    cfg = spec.load_block("mla", CHIP_DIR).rehearsal_config(3072)
    assert served_latent["costs"]["model"]["params_held"] == cfg.n_params
    # the latent kernel served the decode path
    assert served_latent["paths"]["decode"]["engaged"] and served_latent["paths"]["decode"]["dispatches"] > 0


@pytest.mark.parametrize("metric", FED_INDEX, ids=[m["name"] for m in FED_INDEX])
def test_the_index_block_feeds_its_metrics(served_index, metric):
    assert {m["name"] for m in FED_INDEX} == {
        "attn.selected_share", "attn.index_tok_per_call", "attn.index_bytes_share"}
    v = served_index["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "attn.selected_share":
        assert 0.02 < v < 0.06  # the 32 best of a ~800-token catalogue's keys
    if metric["name"] == "attn.index_tok_per_call":
        assert 600 < v < 1200  # every live row decodes behind the whole catalogue
    if metric["name"] == "attn.index_bytes_share":
        assert v == pytest.approx(32 / (32 + 64 + 16))  # an index key beside the latent and the rotated key


def test_the_index_blocks_attributes_count_the_selection_and_the_heads_chunks(served_index):
    """At the rehearsal size: an index of 4 heads x 32 over the 32 best keys,
    two layers; the catalogue of 120 services a head of ~800 tokens, built in
    chunks of the block's largest bucket (256) on the first plan."""
    segments = _segments(served_index)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] > 0 and a["attn_sel_tokens"] == a["attn_row_calls"] * 32
        assert a["index_ctx_tokens"] == a["attn_ctx_tokens"] > a["attn_sel_tokens"]  # every row is past the 32nd key
        assert a["index_bytes_read"] == a["index_ctx_tokens"] * 32 * 2
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (64 + 16) * 2  # the masked form streams every page
        # ~50 pages a row: three whole key blocks and a part of a fourth a call
        assert 3 * a["attn_row_calls"] <= a["attn_key_blocks"] <= 5 * a["attn_row_calls"]
        assert a["attn_run_blocks"] <= a["attn_key_blocks"]
    # the head, built in chunks after the warm-up's rows were freed, lies side by
    # side in the pools: its whole key blocks are fetched as runs
    by_name = {m["name"]: m for m in METRICS}
    run_share = served_index["read"](by_name["attn.page_run_share"]["reader"], by_name["attn.page_run_share"]["args"])
    assert 0.6 <= run_share < 1
    profile = served_index["health"]["engine_queue"]["worker_profile"]
    for attr in ("attn_sel_tokens", "index_ctx_tokens", "index_bytes_read"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in _segments_once(served_index)) > 0
    # the head: one dense chunk and suffix chunks over the pages before it, counted and spanned once
    chunks = served_index["ev"].counters_after["/metrics"]["mcpx_engine_prefix_build_chunks_total"]
    builds = [sp for tr in served_index["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.prefix_build"]
    assert chunks >= 3 and len(builds) == 1
    assert builds[0]["attrs"]["chunks"] == chunks and 600 < builds[0]["attrs"]["head_tokens"] < 1200
    assert builds[0]["attrs"]["head_tokens"] % 16 == 0 and builds[0]["attrs"]["head_tokens"] > 256 * (chunks - 1)
    # every plan's own prefill is its intent behind the shared head
    per_plan = served_index["read"](by_name["engine.prefill_tok_per_plan"]["reader"],
                                    by_name["engine.prefill_tok_per_plan"]["args"])
    assert 0 < per_plan < 80
    # both kernel paths engaged: the suffix route carries every plan's prompt
    assert served_index["kernel_paths"] == {"decode": 1, "prefill": 1}
    for path in ("decode", "prefill"):
        assert served_index["paths"][path]["engaged"] and served_index["paths"][path]["dispatches"] > 0
    spec = sys.modules["spec"]
    cfg = spec.load_block("dsa", CHIP_DIR).rehearsal_config(3072)
    assert served_index["costs"]["model"]["params_held"] == cfg.n_params


@pytest.mark.parametrize("metric", FED_STATE, ids=[m["name"] for m in FED_STATE])
def test_the_state_block_feeds_its_metrics(served_state, metric):
    """Its own metric, and the sparse cells' that list it too: its expert
    layers write what every sparse block's do."""
    assert {m["name"] for m in FED_STATE} == {
        "ssm.state_bytes_share", "engine.prefix_state_miss_share", "moe.experts_touched_share", "moe.tok_per_touched_expert",
        "moe.held_assignment_share", "moe.load_max_over_mean", "moe.touched_per_sparse_layer",
        "moe.prefill_rows_per_assignment", "moe.routed_bytes_share", "moe.kernel_step_share"}
    v = served_state["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_miss_share":
        assert v == 0.0  # the cell's prompts differ from their first page on: no row finds pages resident
    elif metric["name"] == "moe.kernel_step_share":
        assert v == 1.0  # the two-matrix experts in the latent, prefill and decode
    elif metric["unit"] == "ratio" and metric["name"] != "moe.load_max_over_mean":
        assert 0 < v < 1


def test_the_state_blocks_attributes_count_calls_slots_and_what_was_kept(served_state):
    """At the rehearsal size: 5 Mamba layers among 11, a state of 16 heads x
    32 x 32 float32 a row a layer. Every new span attribute, counter and
    ``pallas.paths`` entry the cell's five new metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("nemotron_h", CHIP_DIR).rehearsal_config(3072)
    assert (cfg.n_mamba_layers, cfg.n_sparse_layers, cfg.n_attn_layers) == (5, 5, 1)
    segments = _segments(served_state)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["ssm_row_calls"] % 5 == 0 and 0 < a["ssm_row_calls"] <= a["forwards"] * 8 * 5
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
        assert a["ssm_row_calls"] <= a["ssm_tokens"] <= a["ssm_slots"] <= a["ssm_row_calls"] * 8
        assert a["attn_row_calls"] * 5 == a["ssm_row_calls"]  # ONE attention layer
        assert a["moe_layer_forwards"] == a["forwards"] * 5
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * cfg.kv_bytes_per_token
        assert "ssm_prefill_tokens" in a
    once = _segments_once(served_state)
    profile = served_state["health"]["engine_queue"]["worker_profile"]
    for attr in ("ssm_row_calls", "ssm_state_bytes", "ssm_slots", "ssm_tokens", "ssm_prefill_tokens"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in once) > 0, attr
    # every admitted prompt's tokens went through each Mamba layer once
    prefills = [sp for tr in served_state["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills and all(sp["attrs"]["ssm_prefill_tokens"] % 5 == 0 and sp["attrs"]["ssm_prefill_tokens"] > 0
                            for sp in prefills)
    # pages found resident by a row that prefilled whole all the same (no radix node
    # holds a state): the lifetime sum and the counter agree, and the suffix route never ran
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_miss"}
    metrics = served_state["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] >= 0
    assert served_state["paths"]["prefill"]["dispatches"] == 0
    # the kernel paths the cell's ``correct`` asks for
    assert served_state["kernel_paths"] == {"decode": 1, "prefill": 0, "ssm": 1}
    ssm = served_state["paths"]["ssm"]
    assert ssm["engaged"] is True and ssm["dispatches"] == served_state["paths"]["decode"]["dispatches"] > 0
    model = served_state["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    # a token reads 3 of the 8 experts held, of two matrices in the latent, in 5 layers
    assert model["params_held"] - model["params_active_per_token"] == 5 * 5 * 2 * cfg.moe_latent_size * cfg.d_expert


def test_the_state_kernels_name_is_what_its_metrics_select():
    """``kernel.ssm_busy_share`` and ``kernel.ssm_window_roofline`` find the
    state pool's kernel by the name Mosaic gives its op, and no other kernel's
    metric does."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.ssm import ssm_window

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    text = jax.jit(ssm_window, static_argnums=1).trace(
        sd((2, 8, 128, 1024), f32), 1, sd((4,), i32), sd((4,), i32), sd((4, 1024), f32), sd((4, 8, 1024), f32),
        sd((4, 2, 128, 8), f32), sd((4, 2, 8, 128), f32)).lower(lowering_platforms=("tpu",)).as_text()
    (name,) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert re.search(regex["kernel.ssm_busy_share"], name) and re.search(regex["kernel.ssm_window_roofline"], name)
    for other in ("kernel.attn_busy_share", "kernel.moe_busy_share", "kernel.mla_busy_share",
                  "kernel.routed_experts_roofline"):
        assert not re.search(regex[other], name)
    assert regex["kernel.routed_experts_roofline"] == regex["kernel.moe_busy_share"]


@pytest.mark.parametrize("metric", FED_BLOCK, ids=[m["name"] for m in FED_BLOCK])
def test_the_block_selecting_cell_feeds_its_metrics(served_block, metric):
    """Its own metrics, and the index cells' that list it too: its sparse
    layers write what a selecting block's do."""
    assert {m["name"] for m in FED_BLOCK} == {
        "attn.selected_share", "attn.index_bytes_share", "attn.slots_per_row_call", "linear.state_bytes_share",
        "attn.gathered_pages_share", "engine.prefix_state_hit_share"}
    v = served_block["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_hit_share":
        assert v == 1.0  # every plan's prompt starts with the declared head
    elif metric["name"] == "attn.slots_per_row_call":
        assert 1 <= v <= 8
    elif metric["name"] in ("attn.selected_share", "attn.gathered_pages_share"):
        assert 0.2 < v < 0.45  # 4 blocks of 64 of a ~850-token context
    else:
        assert 0 < v < 1


def test_the_block_selecting_cells_attributes_count_pages_fetched_and_the_head_state(served_block):
    """At the rehearsal size: 6 linear layers of 4 heads x 32 x 32 float32 a
    row, 2 sparse layers on 2 KV heads that keep 4 blocks of 64; the catalogue
    of 120 services a head of ~800 tokens built in chunks of 256, its end state
    handed to every plan. Every span attribute, counter and ``pallas.paths``
    entry the cell's metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("sala", CHIP_DIR).rehearsal_config(3072)
    segments = _segments(served_block)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["attn_row_calls"] > 0 and a["attn_sel_tokens"] == a["attn_row_calls"] * 256
        assert a["attn_query_slots"] >= a["attn_row_calls"]
        assert 0 < a["attn_gathered_pages"] < a["attn_ctx_pages"]
        assert a["kv_bytes_read"] == a["attn_gathered_pages"] * 16 * 32 * 2 * 2  # pages fetched: keys and values
        assert a["index_bytes_read"] > 0 and a["index_bytes_read"] % (32 * 4) == 0  # float32 rows of a page's key sum
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2 and a["ssm_row_calls"] % 6 == 0
        assert a["weight_bytes_read"] > 0 and a["weight_bytes_routed"] == 0
    counters = served_block["ev"].counters_after["/metrics"]
    assert counters['mcpx_engine_prefix_state_total{event="hit"}'] >= 5
    assert counters['mcpx_engine_prefix_state_total{event="miss"}'] == 0
    builds = [sp for tr in served_block["ev"].traces for sp in tr["tree"] if sp["name"] == "engine.prefix_build"]
    assert len(builds) == 1 and builds[0]["attrs"]["chunks"] >= 3 and 600 < builds[0]["attrs"]["head_tokens"] < 1200
    by_name = {m["name"]: m for m in METRICS}
    per_plan = served_block["read"](by_name["engine.prefill_tok_per_plan"]["reader"],
                                    by_name["engine.prefill_tok_per_plan"]["args"])
    assert 0 < per_plan < 80  # a plan's own prefill is its intent behind the head's state
    assert served_block["kernel_paths"] == {"decode": 1, "prefill": 1, "ssm": 1, "gather": 1}
    for path in served_block["kernel_paths"]:
        assert served_block["paths"][path]["engaged"] and served_block["paths"][path]["dispatches"] > 0
    profile = served_block["health"]["engine_queue"]["worker_profile"]
    assert profile["prefix_state_hit"] >= 5 and profile["prefix_state_miss"] == 0
    assert served_block["costs"]["model"]["params_held"] == cfg.n_params


def test_the_block_selecting_cells_kernels_names_are_what_its_metrics_select():
    """``kernel.block_score_roofline``, ``kernel.attn_gathered_roofline`` and
    the two linear-attention metrics find their kernels by the names Mosaic
    gives the ops; the gathered call is an attention call to
    ``kernel.attn_busy_share`` too, and no state metric reads an attention op."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.block_score import block_score
    from mcpx.engine.kernels.paged_attention import ragged_paged_attention

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct

    def kernel_name(fn, *shapes):
        text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        (name,) = set(re.findall(r'kernel_name = "([^"]+)"', text))
        return name

    score = kernel_name(lambda q, kc, p, l: block_score(q, kc, p, l, stride=16),
                        sd((2, 8, 2, 16, 128), bf), sd((2, 2, 63, 128), f32), sd((2,), i32), sd((2,), i32))
    gathered = kernel_name(
        lambda q, k, v, t, p, l: ragged_paged_attention(q, k, v, t, p, l, 0, name="ragged_paged_attention_gathered"),
        sd((4, 1, 1, 16, 128), bf), sd((1, 1, 64, 16, 128), bf), sd((1, 1, 64, 16, 128), bf), sd((4, 8), i32),
        sd((4,), i32), sd((4,), i32))
    assert re.search(regex["kernel.block_score_roofline"], score)
    assert re.search(regex["kernel.attn_gathered_roofline"], gathered) and re.search(regex["kernel.attn_busy_share"], gathered)
    assert regex["kernel.linear_attn_busy_share"] == regex["kernel.linear_attn_window_roofline"] == regex["kernel.ssm_busy_share"]
    for name in (score, gathered):
        for other in ("kernel.ssm_busy_share", "kernel.moe_busy_share", "kernel.mla_busy_share", "kernel.dsa_busy_share"):
            assert not re.search(regex[other], name)
    assert not re.search(regex["kernel.attn_busy_share"], score)
    assert _CELLS_OF["attn.selected_share"] == [INDEX_CELL, BLOCK_CELL]
    assert _CELLS_OF["attn.slots_per_row_call"] == [LATENT_CELL, INDEX_CELL, BLOCK_CELL]


def _segments_once(served):
    """One engine.segment span a dispatched segment (its rows' spans agree)."""
    seen = {}
    for sp in _segments(served):
        seen.setdefault(sp["attrs"].get("seq"), sp)
    return list(seen.values())


def _compiles(served):
    total = served["run"].prom_total
    return tuple(total(text, "mcpx_engine_compiles_total") for text in served["engine_metrics"])


# What run.py reads from the server to decide ``correct`` (run.py, "what
# served it" and ``correctness_problems``): field -> what a sound rehearsal shows.
CORRECT_READS = {
    "healthz.engine": lambda s: s["health"]["engine"] == "ready",
    "healthz.started": lambda s: s["health"]["started"] is True,
    "pallas.enabled": lambda s: s["pallas"]["enabled"] is True,
    "pallas.interpret": lambda s: s["pallas"]["interpret"] is True,  # False on the chip
    "pallas.paths.decode": lambda s: s["paths"]["decode"]["engaged"] is True
    and s["paths"]["decode"]["dispatches"] > 0,
    "pallas.paths.prefill": lambda s: s["paths"]["prefill"]["engaged"] is True
    and s["paths"]["prefill"]["dispatches"] >= 0,
    "kernel_paths": lambda s: set(s["kernel_paths"]) <= set(s["paths"]),
    "engine_queue.mesh": lambda s: s["health"]["engine_queue"]["mesh"] == {"data": 1, "model": 1},
    "mcpx_engine_resets_total": lambda s: "mcpx_engine_resets_total" in s["engine_metrics"][1]
    and s["run"].prom_total(s["engine_metrics"][1], "mcpx_engine_resets_total") == 0,
    "mcpx_engine_compiles_total": lambda s: _compiles(s)[0] > 0,
    "no_compile_after_started": lambda s: _compiles(s)[1] == _compiles(s)[0],
    "costs.device.peaks.platform": lambda s: s["costs"]["device"]["peaks"]["platform"] == "cpu",
    "costs.device.peaks.n_devices": lambda s: s["costs"]["device"]["peaks"]["n_devices"] == 1
    and isinstance(s["costs"]["device"]["peaks"]["device_kind"], str),
    "costs.device.hbm": lambda s: isinstance(s["costs"]["device"]["hbm"], list),
}


@pytest.mark.parametrize("field", list(CORRECT_READS))
def test_the_program_reports_what_correct_reads(served, field):
    assert CORRECT_READS[field](served)


def test_the_harness_finds_the_rehearsal_correct(served):
    problems = served["run"].correctness_problems(
        failed=[s for s in served["samples"] if not s.ok], n_good=len(served["samples"]),
        drained=served["drained"], edges=None, ref={"ok": True},
        resets=served["run"].prom_total(served["engine_metrics"][1], "mcpx_engine_resets_total"),
        compiles=_compiles(served), platform=served["costs"]["device"]["peaks"]["platform"],
        pallas=served["pallas"], kernel_paths=served["kernel_paths"], rehearsal=True,
    )
    assert problems == []


def test_a_trace_body_has_the_keys_the_readers_index(served):
    for tr in served["ev"].traces:
        assert isinstance(tr["started_at"], float) and tr["tree"]
        assert sum(sp["parent_id"] is None for sp in tr["tree"]) == 1
        for sp in tr["tree"]:
            assert {"name", "parent_id", "start_ms", "duration_ms"} <= set(sp)
            assert isinstance(sp.get("attrs", {}), dict)  # left out of a span that has none
        assert any(sp.get("attrs") for sp in tr["tree"])


def test_the_metrics_parser_addresses_a_labelled_sample_of_the_live_server(served):
    after = served["ev"].counters_after["/metrics"]
    assert after[LABELLED_SAMPLE] >= 1
    assert served["read"](
        "endpoint_value", {"endpoint": "/metrics", "path": LABELLED_SAMPLE}
    ) == after[LABELLED_SAMPLE]


def test_the_info_lines_histogram_of_plan_lengths_is_not_empty(served):
    hist = served["histogram"](served["ev"], "engine.decode", "tokens")
    fresh = sum(s.fresh for s in served["samples"])  # the re-send decodes nothing
    assert sum(hist.values()) == fresh >= 5 and all(0 < tokens <= 48 for tokens in hist)


# ------------------------------------------------ what the documents name
_DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
_TOP = set(os.listdir(REPO))
_BASENAMES = {"control_plane.py"}  # the reference system's file, cited as such
for _d, _dirs, _files in os.walk(REPO):
    _dirs[:] = [d for d in _dirs if not d.startswith(".") and d != "chiprun_out"]
    _BASENAMES.update(_files)


def _named_paths(text):
    """Repository paths and ``python <script>`` / ``python -m <module>`` targets
    that a document names in backticks or code blocks."""
    out = set()
    for token in re.findall(r"`([^`\n]+)`", text) + re.findall(r"^\s*(python3? .+)$", text, re.M):
        words = token.split()
        if words[0] in ("python", "python3") and len(words) > 1:
            if words[1] == "-m" and len(words) > 2:
                out.add(words[2].replace(".", "/"))
            elif words[1].endswith(".py"):
                out.add(words[1])
            continue
        word = re.split(r"::|:\d|#", words[0])[0].rstrip("/.,")
        if re.search(r"[*<>{}$|()\[\]]", word) or word.startswith(("/", "http", ".")):
            continue
        if "/" in word and word.split("/")[0] in _TOP:
            out.add(word)
        elif re.fullmatch(r"\w+\.(py|md|jsonl)|[A-Z]\w*\.json", word) and word not in _BASENAMES:
            out.add(word)  # a bare file name that no file of the tree has
    return out


@pytest.mark.parametrize("doc", _DOCS)
def test_every_path_and_command_a_document_names_exists(doc):
    named = _named_paths(open(os.path.join(REPO, doc)).read())
    missing = sorted(
        p for p in named
        if not (os.path.exists(os.path.join(REPO, p)) or os.path.exists(os.path.join(REPO, p + ".py")))
    )
    assert not missing, f"{doc} names {missing}, which the repository does not have"


# ------------------------------------------- short convolutions, tails a page
@pytest.mark.parametrize("metric", FED_CONV, ids=[m["name"] for m in FED_CONV])
def test_the_conv_block_feeds_its_metrics(served_conv, metric):
    """Its three own metrics, and the sparse and state cells' that list it too:
    its routed layers write what every sparse block's do."""
    assert {m["name"] for m in FED_CONV} == {
        "conv.mixer_bytes_share", "conv.tail_bytes_share", "engine.prefix_hit_row_share",
        "engine.prefix_state_hit_share", "engine.prefix_state_miss_share", "moe.experts_touched_share",
        "moe.tok_per_touched_expert", "moe.load_max_over_mean", "moe.touched_per_sparse_layer",
        "moe.prefill_rows_per_assignment", "moe.routed_bytes_share", "moe.kernel_step_share"}
    v = served_conv["read"](metric["reader"], metric["args"])
    counters = served_conv["ev"].counters_after["/metrics"]
    hits = counters['mcpx_engine_prefix_state_total{event="hit"}']
    if metric["name"] == "engine.prefix_state_hit_share":
        # a share of hits + misses: this model has no miss, so 1.0 wherever a row hit in the window
        # (five distinct prompts may share no page: then there is nothing to divide)
        assert v == (1.0 if v is not None else None) and (v is not None or hits == served_conv["hits_before"])
        return
    assert v is not None and math.isfinite(v)
    if metric["name"] == "engine.prefix_state_miss_share":
        assert v == 0.0  # a page's tail is always there: no row that found pages prefilled whole
    elif metric["name"] == "engine.prefix_hit_row_share":
        assert 0.0 <= v <= 1.0
    elif metric["name"] == "moe.kernel_step_share":
        assert v == 1.0
    elif metric["unit"] == "ratio" and metric["name"] != "moe.load_max_over_mean":
        assert 0 < v < 1


def test_the_conv_blocks_attributes_count_calls_tails_and_weights(served_conv):
    """At the rehearsal size: 8 short convolutions and 2 attention layers among
    10, 8 routed layers, a tail of 2 x 256 float32 a row a layer beside a
    pending window of 8. Every new span attribute, counter, ``pallas.paths``
    entry and /healthz field the cell's metrics read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("lfm2", CHIP_DIR).rehearsal_config(3072)
    assert (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_sparse_layers, cfg.kv_pack) == (8, 2, 8, 2)
    segments = _segments(served_conv)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["conv_row_calls"] % 8 == 0 and 0 < a["conv_row_calls"] <= a["forwards"] * 8 * 8
        assert a["conv_tail_bytes"] == a["conv_row_calls"] * (2 + 8) * 256 * 4 * 2  # float32, read and written
        assert a["conv_row_calls"] <= a["conv_tokens"] <= a["conv_slots"] <= a["conv_row_calls"] * 8
        assert a["attn_row_calls"] * 4 == a["conv_row_calls"]  # TWO attention layers
        assert a["moe_layer_forwards"] == a["forwards"] * 8
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (cfg.kv_bytes_per_token // 2)
        assert 0 < a["conv_weight_bytes"] < a["weight_bytes_read"] and a["conv_weight_bytes"] % a["forwards"] == 0
        assert "conv_prefill_tokens" in a and "ssm_row_calls" not in a and "ssm_state_bytes" not in a
    once = _segments_once(served_conv)
    profile = served_conv["health"]["engine_queue"]["worker_profile"]
    for attr in ("conv_row_calls", "conv_tail_bytes", "conv_slots", "conv_tokens", "conv_weight_bytes", "conv_prefill_tokens"):
        assert profile[attr] >= sum(sp["attrs"][attr] for sp in once) > 0, attr
    # every admitted prompt's own tokens went through each convolution once, and each page it
    # filled to its last slot got its tail: the rest were a matched page's
    prefills = [sp for tr in served_conv["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills
    for sp in prefills:
        a = sp["attrs"]
        assert a["conv_prefill_tokens"] % 8 == 0 and a["conv_prefill_tokens"] > 0
        own = a["conv_prefill_tokens"] // 8
        assert a["tail_pages_written"] == (a["prefix_matched_tokens"] + own) // 16 - a["prefix_matched_tokens"] // 16
    # hits and no miss: the lifetime sums and the counters agree
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_hit", "prefix_state_miss"}
    metrics = served_conv["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="hit"}'] == profile["prefix_state_hit"] >= 0
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] == 0
    assert profile["prefix_state_hit"] == metrics["mcpx_kv_prefix_hits_total"]  # every matched row is a state hit
    # the kernel paths the cell's ``correct`` asks for; no state kernel exists
    assert served_conv["kernel_paths"] == {"decode": 1, "prefill": 0}
    assert "ssm" not in served_conv["paths"] and served_conv["paths"]["prefill"]["engaged"]
    assert served_conv["paths"]["prefill"]["reason"] is None
    # the state pool's bytes where the weights' are
    pool = served_conv["health"]["engine_queue"]["state_pool"]
    n_pages = pool["page_tails_bytes"] // (8 * 2 * 256 * 4)
    assert pool["slots"] == 8 and n_pages > 8 * 16 and pool["bytes"] == pool["page_tails_bytes"] + 8 * 8 * 10 * 256 * 4 + 8 * 4
    model = served_conv["costs"]["model"]
    assert model["params_held"] == cfg.n_params
    assert model["params_held"] - model["params_active_per_token"] == 8 * 6 * 3 * 256 * 128


# --------------------------------------------- the selective-scan cell (PR 58)
@pytest.mark.parametrize("metric", FED_SCAN, ids=[m["name"] for m in FED_SCAN])
def test_the_scan_block_feeds_its_metrics(served_scan, metric):
    """The Mamba-2 cell's two metrics that list this cell too: the names are
    the same, so the metric files read here unedited."""
    assert {m["name"] for m in FED_SCAN} == {"ssm.state_bytes_share", "engine.prefix_state_miss_share"}
    v = served_scan["read"](metric["reader"], metric["args"])
    assert v is not None and math.isfinite(v)
    if metric["name"] == "ssm.state_bytes_share":
        assert 0 < v < 1


def test_the_scan_blocks_attributes_count_calls_slots_and_what_was_walked(served_scan):
    """At the rehearsal size: 6 selective-scan layers among 8, a state of 16 x
    512 float32 a row a layer. Every span attribute, counter, ``pallas.paths``
    entry and ``/healthz`` field the cell's metric files and its roofline
    reader's two forms read."""
    spec = sys.modules["spec"]
    cfg = spec.load_block("jamba", CHIP_DIR).rehearsal_config(3072)
    Lj = cfg.n_scan_layers
    assert (Lj, cfg.n_attn_layers, cfg.q_per_kv, cfg.ssm_slot_bytes) == (6, 2, 5, 16 * 512 * 4)
    segments = _segments(served_scan)
    assert segments
    for sp in segments:
        a = sp["attrs"]
        assert a["ssm_row_calls"] % Lj == 0 and 0 < a["ssm_row_calls"] <= a["forwards"] * 8 * Lj
        assert a["ssm_state_bytes"] == a["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
        assert a["ssm_row_calls"] <= a["ssm_tokens"] <= a["ssm_slots"] <= a["ssm_row_calls"] * 8
        assert a["attn_row_calls"] * Lj == a["ssm_row_calls"] * 2  # TWO attention layers
        assert a["kv_bytes_read"] == a["attn_ctx_tokens"] * (cfg.kv_bytes_per_token // 2)
        # every leaf read whole a forward, the tied embedding among them
        assert a["weight_bytes_read"] == a["forwards"] * (cfg.n_params * 2 + Lj * (16 * 512 + 2 * 512) * 2)
        assert a["moe_tokens_routed"] == 0 and "conv_row_calls" not in a
    # an admission's prefill WALKS its cohort: live tokens and A x T slots a J layer
    prefills = [sp for tr in served_scan["ev"].traces for sp in tr.get("tree", []) if sp["name"] == "engine.prefill"]
    assert prefills
    for sp in prefills:
        a = sp["attrs"]
        assert a["scan_slots"] == a["cohort_bucket"] * 128 * Lj  # the prompts fit the 128 bucket
        assert 0 < a["ssm_prefill_tokens"] <= a["scan_tokens"] <= a["scan_slots"] and a["scan_tokens"] % Lj == 0
        assert a["ssm_state_bytes"] == a["cohort_bucket"] * Lj * cfg.ssm_slot_bytes
    # what the roofline reader's two forms take from the spans (a device trace apart)
    mod = spec.import_file(os.path.join(CHIP_DIR, "reader_files", "selective_scan_roofline.py"), "chip_reader_t_")
    assert mod._segments(served_scan["ev"], "engine.prefill", ("scan_slots", "ssm_state_bytes"))
    assert mod._segments(served_scan["ev"], "engine.segment", ("ssm_state_bytes",))
    assert served_scan["read"]("selective_scan_roofline", {"regex": "selective_scan_window"}) is None  # no device trace here
    profile = served_scan["health"]["engine_queue"]["worker_profile"]
    assert {k for k in profile if k.startswith("prefix_state_")} == {"prefix_state_miss"}
    metrics = served_scan["ev"].counters_after["/metrics"]
    assert metrics['mcpx_engine_prefix_state_total{event="miss"}'] == profile["prefix_state_miss"] >= 0
    assert served_scan["paths"]["prefill"]["dispatches"] == 0  # no suffix route: every row prefills whole
    assert served_scan["kernel_paths"] == {"decode": 1, "prefill": 0, "ssm": 1}
    ssm = served_scan["paths"]["ssm"]
    assert ssm["engaged"] is True and ssm["dispatches"] == served_scan["paths"]["decode"]["dispatches"] > 0
    pool = served_scan["health"]["engine_queue"]["state_pool"]
    assert pool["slots"] == 8 and pool["state_bytes"] == Lj * 8 * cfg.ssm_slot_bytes < pool["bytes"]
    model = served_scan["costs"]["model"]
    assert model["params_held"] == model["params_active_per_token"] == cfg.n_params


def test_the_scan_kernels_names_are_what_their_metrics_select():
    """The four new metrics find the scan's two call forms by the names Mosaic
    gives their ops, each its own form alone, and no other kernel's metric
    finds either."""
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from mcpx.engine.kernels.selective_scan import selective_scan_prefill, selective_scan_window

    regex = {m["name"]: m["args"]["regex"] for m in METRICS if "regex" in m["args"]}
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    B, T, I, N = 2, 256, 512, 16
    names = {}
    text = jax.jit(selective_scan_prefill).trace(
        sd((B, T, I), f32), sd((B, T, I), f32), sd((B, T, N), f32), sd((B, T, N), f32), sd((N, I), f32),
        sd((B,), i32)).lower(lowering_platforms=("tpu",)).as_text()
    (names["prefill"],) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    text = jax.jit(selective_scan_window).trace(
        sd((3, 4, N, I), f32), sd((), i32), sd((B,), i32), sd((B,), i32), sd((B, 8, I), f32), sd((B, 8, I), f32),
        sd((B, 8, N), f32), sd((B, 8, I), f32), sd((B, 8, I), f32), sd((B, 8, N), f32), sd((B, 8, N), f32),
        sd((N, I), f32)).lower(lowering_platforms=("tpu",)).as_text()
    (names["window"],) = set(re.findall(r'kernel_name = "([^"]+)"', text))
    for form, other in (("prefill", "window"), ("window", "prefill")):
        for kind in ("busy_share", "roofline"):
            pat = regex[f"kernel.selective_scan_{form}_{kind}"]
            assert re.search(pat, names[form]) and not re.search(pat, names[other])
    for metric, pat in regex.items():
        if "selective_scan" not in metric:
            assert not any(re.search(pat, name) for name in names.values()), metric
