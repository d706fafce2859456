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

This file keeps what is about the harness itself and the dense block (the
``served`` child) and the sparse one (``served_sparse``). Every other
configuration's child and the tests that take it are
``tests/test_<block>_rehearsal.py``, beside its ``tests/test_<block>_block.py``;
what the children share is ``tests/chip_rehearsal.py`` (ROADMAP D24: a file is
one xdist worker's, so a child a file spreads them over the run).
"""

import dataclasses
import glob
import json
import math
import os
import re

import jax
import pytest

from mcpx.models.gemma.params import load_or_init
from mcpx.parallel.mesh import make_mesh

from tests.chip_rehearsal import (
    CELL,
    CHIP_DIR,
    CONV_CELL,
    FED,
    FED_SPARSE,
    LATENT_CELL,
    LAYER_KIND_ATTRS,
    METRICS,
    MIXED_CELL,
    NOT_FED_HERE,
    NOT_FED_ON_THE_CPU,
    REPO,
    SPARSE_CELL,
    STATE_CELL,
    _by_path,
    _CELLS_OF,
    _segments,
    _segments_once,
    serve,
)


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
LABELLED_SAMPLE = 'mcpx_engine_compiles_total{executable="admit"}'


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    return serve(CELL, tmp_path_factory)


@pytest.fixture(scope="module")
def served_sparse(tmp_path_factory):
    return serve(SPARSE_CELL, tmp_path_factory)


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
