"""mcpxlint (mcpx/analysis/): per-rule fixture coverage, suppression and
baseline semantics, CLI behavior, and the tier-1 gate that runs the full
analyzer over mcpx/ + benchmarks/ against the committed baseline."""

import io
import json
import pathlib

import pytest

from mcpx.analysis import (
    all_rules,
    apply_baseline,
    load_baseline,
    save_baseline,
    scan_paths,
)
from mcpx.analysis.cli import run_lint

REPO = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "lint"
BASELINE = REPO / "mcpxlint.baseline.json"

RULE_IDS = {
    "async-blocking",
    "async-shared-mutation",
    "jit-host-sync",
    "traced-control-flow",
    "jit-static-branch",
    "per-token-host-loop",
    "hardcoded-kernel-fallback",
    "broad-except",
    "blank-lines",
    "unbounded-retry-loop",
    "blocking-io-on-request-path",
    "metric-label-churn",
    "unbounded-cache-growth",
    "thread-ownership",
    "jit-contract",
    "loop-confinement",
    "blocking-transfer-on-loop",
    "sharding-contract",
}


def hits(fixture: str, rule: str) -> list[int]:
    """Sorted finding lines for one rule over one fixture file."""
    res = scan_paths([FIXTURES / fixture], root=REPO, rules=[rule])
    return sorted(f.line for f in res.findings if f.rule == rule)


# ------------------------------------------------------------------ registry
def test_registry_has_all_rules():
    assert RULE_IDS <= set(all_rules())


def test_unknown_rule_id_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        scan_paths([FIXTURES], rules=["no-such-rule"])


# ------------------------------------------------------------------ fixtures
def test_evict_without_refcount_positive():
    # An inline pop-and-free evict and a helper-class host-tier reclaim,
    # both in refcount-aware classes, neither consulting refs.
    assert hits(
        "evict_refcount_pos.py", "evict-without-refcount-consult"
    ) == [23, 40]


def test_evict_without_refcount_negative():
    # Inline refs consult, one-hop same-class helper consult, and a plain
    # refcount-free LRU all stay silent.
    assert hits("evict_refcount_neg.py", "evict-without-refcount-consult") == []


def test_async_blocking_positive():
    assert hits("async_blocking_pos.py", "async-blocking") == [10, 14, 19, 23, 27]


def test_async_blocking_negative():
    assert hits("async_blocking_neg.py", "async-blocking") == []


def test_jit_host_sync_positive():
    lines = hits("jit_host_sync_pos.py", "jit-host-sync")
    assert set(lines) == {13, 14, 19, 24, 39}
    # line 14 carries TWO syncs (float() and .item())
    assert lines.count(14) == 2


def test_jit_host_sync_negative():
    assert hits("jit_host_sync_neg.py", "jit-host-sync") == []


def test_traced_control_flow_positive():
    assert hits("traced_control_flow_pos.py", "traced-control-flow") == [9, 16]


def test_traced_control_flow_negative():
    assert hits("traced_control_flow_neg.py", "traced-control-flow") == []


def test_jit_static_branch_positive():
    # if on a non-static param, while on a non-static param, bare-@jax.jit
    # flag, and a traced name mixed into an otherwise-static test.
    assert hits("jit_static_branch_pos.py", "jit-static-branch") == [11, 13, 24, 33]


def test_jit_static_branch_negative():
    # static_argnames branches, `is not None` presence checks, nested-def
    # shadowing and never-jitted helpers all stay silent.
    assert hits("jit_static_branch_neg.py", "jit-static-branch") == []


def test_per_token_host_loop_positive():
    # while + int(), for + .item(), for + device_get — each a per-iteration
    # sync whose result feeds the next jitted dispatch (device_get IS
    # flagged here, unlike jit-host-sync's loop mode: the feedback edge,
    # not the fetch, is the serialization).
    assert hits("per_token_host_loop_pos.py", "per-token-host-loop") == [17, 26, 38]


def test_per_token_host_loop_negative():
    # Device-chained loops with one post-loop fetch, metrics-only syncs
    # (jit-host-sync's business) and feedback through plain-Python helpers
    # stay silent.
    assert hits("per_token_host_loop_neg.py", "per-token-host-loop") == []


def test_hardcoded_kernel_fallback_positive():
    # A class that resolves self._use_pallas pinning one call site to
    # use_pallas=False, another to a literal interpret=, and a function
    # that receives the resolved flag but overrides it with a literal —
    # the suffix-prefill bug class (ISSUE 15).
    assert hits("kernel_fallback_pos.py", "hardcoded-kernel-fallback") == [
        20, 23, 28,
    ]


def test_hardcoded_kernel_fallback_negative():
    # Resolved flags passed through, literals in classes WITHOUT a
    # resolved route (reference harnesses), signature defaults, and
    # standalone functions stay silent — those literals are the
    # configuration, not an override.
    assert hits("kernel_fallback_neg.py", "hardcoded-kernel-fallback") == []


def test_metric_label_churn_positive():
    # Two per-request metric constructions, then five label values
    # synthesised in the request path: f-string, concat, request.path,
    # %-format, .format().
    assert hits("metric_label_churn_pos.py", "metric-label-churn") == [
        6, 8, 13, 14, 15, 16, 17,
    ]


def test_metric_label_churn_negative():
    # Init-time construction, bounded Name/literal/conditional labels, and
    # collections.Counter stay silent.
    assert hits("metric_label_churn_neg.py", "metric-label-churn") == []


def test_unbounded_cache_growth_positive():
    # Subscript insert, list append, and setdefault on cache-named
    # containers inside async request-path functions, no bound in scope.
    assert hits(
        "unbounded_cache_growth_pos.py", "unbounded-cache-growth"
    ) == [7, 12, 17]


def test_unbounded_cache_growth_negative():
    # LRU popitem loops, eviction-helper consults, del-under-len, literal
    # key counters, non-cache names and sync helpers all stay silent.
    assert hits("unbounded_cache_growth_neg.py", "unbounded-cache-growth") == []


# ------------------------------------------------- interprocedural passes
def test_blocking_io_positive():
    # Writes in a handler, in a directly-called sync helper, and two call
    # hops deep — flagged at the WRITE site in every case.
    assert hits("blocking_io_pos.py", "blocking-io-on-request-path") == [
        13, 14, 15, 19, 33,
    ]


def test_blocking_io_negative():
    # to_thread'd method reference, nested-def + to_thread (the
    # FileRegistry pattern), read-mode open, json.dumps, and shutdown
    # async code no request reaches — all silent.
    assert hits("blocking_io_neg.py", "blocking-io-on-request-path") == []


def test_thread_ownership_positive():
    # write / two reads / owned-mutator call, all from an async handler
    # whose call-graph roots never touch the worker's thread entry.
    assert hits("ownership_pos.py", "thread-ownership") == [33, 34, 35, 36]


def test_thread_ownership_negative():
    # worker-only mutation paths, atomic cross-thread reads, __init__
    # construction writes and unowned boundary state all stay silent.
    assert hits("ownership_neg.py", "thread-ownership") == []


def test_jit_contract_static_taint_crosses_modules():
    # The PR 7 retrace-storm shape: req.max_tokens flows handler -> helper
    # -> static arg `width` across a module boundary the per-function
    # jit-static-branch rule cannot see. The finding lands at the dispatch.
    res = scan_paths(
        [FIXTURES / "jitflow" / "engine_mod.py", FIXTURES / "jitflow" / "handler_pos.py"],
        root=REPO,
        rules=["jit-contract"],
    )
    assert [(f.path.rsplit("/", 1)[-1], f.line) for f in res.findings] == [
        ("engine_mod.py", 18)
    ]
    assert "max_tokens" in res.findings[0].message
    # the old per-function rule is blind to it, by construction
    res_old = scan_paths(
        [FIXTURES / "jitflow"], root=REPO, rules=["jit-static-branch"]
    )
    assert res_old.findings == []


def test_jit_contract_bucketed_flow_is_clean():
    # size_bucket() quantizes the request value onto a fixed grid — the
    # sanctioned idiom launders the taint.
    res = scan_paths(
        [FIXTURES / "jitflow" / "engine_mod.py", FIXTURES / "jitflow" / "handler_neg.py"],
        root=REPO,
        rules=["jit-contract"],
    )
    assert res.findings == []


def test_jit_contract_engine_alone_is_clean():
    # Without the tainted caller in context there is no request provenance:
    # the finding is genuinely interprocedural.
    res = scan_paths(
        [FIXTURES / "jitflow" / "engine_mod.py"], root=REPO, rules=["jit-contract"]
    )
    assert res.findings == []


def test_use_after_donation_positive():
    assert hits("donation_pos.py", "jit-contract") == [17]


def test_use_after_donation_negative():
    # `pool = consume(pool)` rebinds in the dispatch statement itself, and
    # a sibling `else` arm is not after the dispatch (the engine's
    # `_ensure_prefix` branch shape that once false-positived).
    assert hits("donation_neg.py", "jit-contract") == []


def test_cache_rule_sees_bound_consults_through_helpers():
    # Bound consult in an imported helper (container passed as arg) or a
    # same-class trim method: the migrated rule's killed false positives.
    res = scan_paths([FIXTURES / "xmodcache"], root=REPO, rules=["unbounded-cache-growth"])
    assert [(f.path.rsplit("/", 1)[-1], f.line) for f in res.findings] == [
        ("svc_pos.py", 13)
    ]


def test_retry_rule_sees_bound_consults_through_helpers():
    # An innocuously-named imported helper that raises on an expired
    # deadline bounds the loop; a log-only helper does not.
    res = scan_paths([FIXTURES / "xmodretry"], root=REPO, rules=["unbounded-retry-loop"])
    assert [(f.path.rsplit("/", 1)[-1], f.line) for f in res.findings] == [
        ("client_pos.py", 13)
    ]


def test_loop_confinement_positive():
    # A method write reached through a thread-spawned body, the spawned
    # body's own write, an unmarked sync entry nobody spawns, and a call
    # into an @owned_by("event_loop") mutator from such an entry.
    assert hits("loop_confinement_pos.py", "loop-confinement") == [16, 20, 32, 42]


def test_loop_confinement_negative():
    # Coroutine writers, helpers only async code calls, call_soon'd
    # callbacks, marked mutators, ctor writes and cross-thread READS
    # (the sanctioned GIL-atomic snapshot contract) all stay silent.
    assert hits("loop_confinement_neg.py", "loop-confinement") == []


def test_blocking_transfer_positive():
    # float() over a queue_stats() field and np.asarray over a jitted
    # result in the handler, comprehension-generator taint, and a sync
    # helper one hop below an async request handler.
    assert hits(
        "blocking_transfer_pos.py", "blocking-transfer-on-loop"
    ) == [16, 18, 19, 25]


def test_blocking_transfer_negative():
    # Offline sync readbacks, the to_thread'd nested-def fix shape
    # (PR 7 /costs), host-native float() on the loop, and async code no
    # request reaches all stay silent.
    assert hits("blocking_transfer_neg.py", "blocking-transfer-on-loop") == []


def test_blocking_transfer_two_hops_across_modules():
    # handler -> render -> summarize, with the device source (a helper
    # returning queue_stats() raw) defined in ANOTHER module: the
    # readback is flagged at the float() two call hops below the root.
    res = scan_paths(
        [FIXTURES / "xmodtransfer"], root=REPO,
        rules=["blocking-transfer-on-loop"],
    )
    assert [(f.path.rsplit("/", 1)[-1], f.line) for f in res.findings] == [
        ("web.py", 8)
    ]
    assert "device_stats" in res.findings[0].message


def test_sharding_contract_positive():
    # An undeclared axis in a jit binding, a producer/consumer pair
    # disagreeing on the boundary buffer, and a live alias of a donated
    # sharded buffer.
    assert hits("sharding_pos.py", "sharding-contract") == [24, 30, 37]


def test_sharding_contract_negative():
    # Axes resolved through module constants, agreeing pairs, dynamic
    # (unparseable) specs and donations with no surviving alias are all
    # silent — unknowns never flag.
    assert hits("sharding_neg.py", "sharding-contract") == []


def test_sharding_contract_two_executable_mismatch():
    # The two-executable pair lives in one module, the chain in another:
    # the registry is project-global, so the mismatch is flagged at the
    # consumer dispatch; the agreeing driver stays silent.
    res = scan_paths(
        [FIXTURES / "shardflow"], root=REPO, rules=["sharding-contract"]
    )
    assert [(f.path.rsplit("/", 1)[-1], f.line) for f in res.findings] == [
        ("driver_pos.py", 8)
    ]
    assert "all-to-all" in res.findings[0].message


def test_engine_ownership_annotations_are_live():
    """The acceptance check behind the clean tree: the real engine files
    carry the declarations the pass runs on — worker entry, owned fields
    (atomic where queue_stats reads them), decorated mutators."""
    from mcpx.analysis.core import FileContext, _relpath, iter_py_files
    from mcpx.analysis.project import ProjectContext
    from mcpx.analysis.rules.ownership_rules import _Ownership

    files = iter_py_files([REPO / "mcpx" / "engine", REPO / "mcpx" / "utils"])
    ctxs = [FileContext(p, _relpath(p, REPO), p.read_text()) for p in files]
    proj = ProjectContext(ctxs, REPO)
    own = _Ownership(proj)
    eng = "mcpx.engine.engine.InferenceEngine"
    assert (eng, "_inflight") in own.fields
    assert not own.fields[(eng, "_inflight")][1]  # owner-only, not atomic
    assert own.fields[(eng, "_ewma_service_s")][1]  # GIL-atomic, cross-read
    assert proj.index.functions[f"{eng}._worker"].entry_of == "engine-worker"
    pc = "mcpx.engine.prefix_cache.RadixPrefixCache"
    assert proj.index.functions[f"{pc}.insert"].owner == "engine-worker"
    assert proj.index.classes["mcpx.engine.engine._Slab"].owner == "engine-worker"
    assert (
        proj.index.functions["mcpx.engine.kv_cache.PageAllocator.free"].owner
        == "engine-worker"
    )


def test_ownership_pass_guards_real_engine_fields(tmp_path):
    # A foreign module mutating worker-owned engine state IS flagged — the
    # annotated tree is clean because nothing violates, not because the
    # pass is inert.
    rogue = tmp_path / "rogue.py"
    rogue.write_text(
        "from mcpx.engine.engine import InferenceEngine\n\n\n"
        "async def rogue(engine: InferenceEngine):\n"
        "    engine._inflight.clear()\n"
    )
    res = scan_paths(
        [REPO / "mcpx" / "engine", REPO / "mcpx" / "utils", rogue],
        root=REPO,
        rules=["thread-ownership"],
    )
    assert any("rogue" in f.path and "_inflight" in f.message for f in res.findings)


def test_cluster_loop_annotations_are_live():
    """The loop-confinement acceptance check: the real cluster/telemetry
    classes carry the event_loop declarations the pass runs on."""
    from mcpx.analysis.core import FileContext, _relpath, iter_py_files
    from mcpx.analysis.project import ProjectContext
    from mcpx.analysis.rules.ownership_rules import LOOP_DOMAIN, _Ownership

    files = iter_py_files(
        [REPO / "mcpx" / "cluster", REPO / "mcpx" / "telemetry"]
    )
    ctxs = [FileContext(p, _relpath(p, REPO), p.read_text()) for p in files]
    proj = ProjectContext(ctxs, REPO)
    own = _Ownership(proj)
    pool = "mcpx.cluster.pool.EnginePool"
    assert proj.index.classes[pool].owner == LOOP_DOMAIN
    assert (pool, "_closed") in own.fields
    assert own.fields[(pool, "_closed")][0] == LOOP_DOMAIN
    rep = "mcpx.cluster.replica.ReplicaHandle"
    assert proj.index.classes[rep].owner == LOOP_DOMAIN
    assert proj.index.functions[f"{rep}.note_result"].owner == LOOP_DOMAIN
    rp = "mcpx.cluster.routing.RoutingPipeline"
    assert proj.index.classes[rp].owner == LOOP_DOMAIN
    assert proj.index.functions[f"{rp}.route"].owner == LOOP_DOMAIN
    led = "mcpx.telemetry.ledger.UsageLedger"
    assert proj.index.classes[led].owner == LOOP_DOMAIN
    assert proj.index.functions[f"{led}.observe"].owner == LOOP_DOMAIN
    slo = "mcpx.telemetry.slo.SLOTracker"
    assert proj.index.classes[slo].owner == LOOP_DOMAIN
    fr = "mcpx.telemetry.flight.FlightRecorder"
    assert proj.index.classes[fr].owner == LOOP_DOMAIN


def test_loop_pass_guards_real_cluster_state(tmp_path):
    # A foreign sync entry mutating loop-owned pool state IS flagged —
    # the annotated tree is clean because nothing violates, not because
    # the pass is inert. Removing EnginePool's annotation breaks this.
    rogue = tmp_path / "rogue.py"
    rogue.write_text(
        "from mcpx.cluster.pool import EnginePool\n\n\n"
        "def rogue(pool: EnginePool):\n"
        "    pool.resteers += 1\n"
    )
    res = scan_paths(
        [REPO / "mcpx" / "cluster", REPO / "mcpx" / "utils", rogue],
        root=REPO,
        rules=["loop-confinement"],
    )
    assert any(
        "rogue" in f.path and "resteers" in f.message for f in res.findings
    )
    # ...and the cluster package alone stays clean in the same scan.
    assert not [f for f in res.findings if "rogue" not in f.path]


def test_every_mutable_cluster_class_declares_ownership():
    """The opt-out gate: any mcpx/cluster/ class whose methods mutate
    instance state outside the ctor must declare an ownership domain
    (class decorator, method mark, or per-field owner comment) — new
    cluster code can't silently skip the concurrency contract."""
    import ast as _ast

    from mcpx.analysis.core import FileContext, _relpath, iter_py_files
    from mcpx.analysis.project import ProjectContext
    from mcpx.analysis.rules.ownership_rules import _Ownership

    files = iter_py_files([REPO / "mcpx" / "cluster"])
    ctxs = [FileContext(p, _relpath(p, REPO), p.read_text()) for p in files]
    proj = ProjectContext(ctxs, REPO)
    own = _Ownership(proj)
    field_marked = {cq for (cq, _attr) in own.fields}
    ctors = {"__init__", "__post_init__", "__new__"}
    offenders = []
    for cq, ci in proj.index.classes.items():
        if not cq.startswith("mcpx.cluster.") or ci.owner:
            continue
        mutating = []
        for fq, fi in proj.index.functions.items():
            if not fq.startswith(cq + ".") or fi.name in ctors or fi.owner:
                continue
            for node in _ast.walk(fi.node):
                targets = []
                if isinstance(node, _ast.Assign):
                    targets = node.targets
                elif isinstance(node, (_ast.AugAssign, _ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    while isinstance(t, _ast.Subscript):
                        t = t.value
                    if (
                        isinstance(t, _ast.Attribute)
                        and isinstance(t.value, _ast.Name)
                        and t.value.id == "self"
                    ):
                        mutating.append(f"{fi.name}:{node.lineno}")
        if mutating and cq not in field_marked:
            offenders.append((cq, mutating))
    assert offenders == [], (
        "cluster classes with post-ctor mutable state but no ownership "
        f"annotation: {offenders}"
    )


def test_committed_baseline_is_empty():
    """ISSUE 3 burn-down: the grandfathered engine.start() state-machine
    findings are fixed for real (guarded transitions), so the baseline is
    an EMPTY list — and stays one (new debt needs a better home)."""
    assert load_baseline(BASELINE) == []


def test_broad_except_positive():
    assert hits("broad_except_pos.py", "broad-except") == [7, 14, 21, 28]


def test_broad_except_negative():
    assert hits("broad_except_neg.py", "broad-except") == []


def test_shared_mutation_positive():
    assert hits("shared_mutation_pos.py", "async-shared-mutation") == [14, 23]


def test_shared_mutation_negative():
    assert hits("shared_mutation_neg.py", "async-shared-mutation") == []


def test_blank_lines_positive():
    assert hits("blank_lines_pos.py", "blank-lines") == [4]


def test_blank_lines_negative():
    assert hits("blank_lines_neg.py", "blank-lines") == []


def test_span_across_await_positive():
    # time.time / time.monotonic / asyncio loop-clock deltas, each spanning
    # a yield point (await or async with).
    assert hits("span_across_await_pos.py", "span-across-await-blocking") == [11, 17, 26]


def test_span_across_await_negative():
    assert hits("span_across_await_neg.py", "span-across-await-blocking") == []


def test_wall_clock_duration_positive():
    # Wall-clock PAIRS differenced into durations in async code: a direct
    # call minus a tracked assignment, a datetime.now() pair, and two
    # tracked names (ISSUE 14 satellite — SLO windows and ledger bills
    # are monotonic-clock contracts).
    assert hits("wall_clock_duration_pos.py", "wall-clock-duration") == [
        11, 18, 25,
    ]


def test_wall_clock_duration_negative():
    # Monotonic deltas, lone timestamps, one-sided cross-host timestamp
    # comparisons (mirror TTL idiom) and sync offline code all pass.
    assert hits("wall_clock_duration_neg.py", "wall-clock-duration") == []


def test_unbounded_retry_positive():
    # while True + for-range retry loops that await a transport call and
    # swallow its failure with no deadline or attempt bound (the aiohttp
    # `async with session.get(...)` idiom counts as the awaited call). The
    # loop in the nested async def reports ONCE, under its own function —
    # never once per enclosing scope.
    assert hits("unbounded_retry_pos.py", "unbounded-retry-loop") == [7, 15, 23, 34]


def test_unbounded_retry_negative():
    # deadline consults, give-up raises, bound-shaped branch conditions,
    # non-transport awaits and sync loops must not match.
    assert hits("unbounded_retry_neg.py", "unbounded-retry-loop") == []


def test_span_across_await_exempts_benchmarks_by_path(tmp_path):
    # Offline measurement harnesses time awaits as their PRODUCT: any
    # 'benchmarks' path segment is exempt from the request-path rule.
    bench_dir = tmp_path / "benchmarks"
    bench_dir.mkdir()
    src = (FIXTURES / "span_across_await_pos.py").read_text()
    (bench_dir / "probe.py").write_text(src)
    res = scan_paths([bench_dir], root=tmp_path, rules=["span-across-await-blocking"])
    assert res.findings == []


# -------------------------------------------------------------- suppressions
def test_suppression_consumes_finding_and_dead_one_is_reported():
    res = scan_paths([FIXTURES / "suppressed.py"], root=REPO)
    assert res.suppressed == 1  # the justified time.sleep
    assert [f.rule for f in res.findings] == ["unused-suppression"]
    assert res.findings[0].line == 11


def test_suppression_only_judged_against_selected_rules():
    # A blank-lines-only pass must not call the async-blocking suppression
    # unused — that rule never ran.
    res = scan_paths([FIXTURES / "suppressed.py"], root=REPO, rules=["blank-lines"])
    assert res.findings == []


def test_multi_rule_suppression_reports_unfired_known_id(tmp_path):
    # ignore[a,b] with only `a` firing: `a` is consumed, KNOWN-but-idle `b`
    # is reported unused — never silently passed.
    p = tmp_path / "t.py"
    p.write_text(
        "import time\n\n\nasync def f():\n"
        "    time.sleep(1)  # mcpx: ignore[async-blocking,jit-host-sync] - only one fires\n"
    )
    res = scan_paths([p], root=tmp_path)
    assert res.suppressed == 1
    assert [f.rule for f in res.findings] == ["unused-suppression"]
    assert "jit-host-sync" in res.findings[0].message


def test_unknown_suppression_id_always_reported(tmp_path):
    # A typo'd id guards nothing; it is reported even when the run's rule
    # selection wouldn't have judged that rule (unknown ids belong to no
    # rule, so selection can't exempt them).
    p = tmp_path / "t.py"
    p.write_text(
        "import time\n\n\nasync def f():\n"
        "    time.sleep(1)  # mcpx: ignore[async-blocking,asnyc-blocking] - typo\n"
    )
    res = scan_paths([p], root=tmp_path)
    assert res.suppressed == 1
    assert [f.rule for f in res.findings] == ["unused-suppression"]
    assert "asnyc-blocking" in res.findings[0].message
    res2 = scan_paths([p], root=tmp_path, rules=["blank-lines"])
    assert ["asnyc-blocking" in f.message for f in res2.findings] == [True]


def test_suppression_groups_merge_and_duplicates_dedupe(tmp_path):
    # Two ignore[...] groups on one line merge; a duplicated id within a
    # group dedupes to one suppression, with no spurious unused report.
    p = tmp_path / "t.py"
    p.write_text(
        "import time\n\n\nasync def f():\n"
        "    time.sleep(1)  "
        "# mcpx: ignore[async-blocking] - x # mcpx: ignore[async-blocking,async-blocking] - dupe\n"
    )
    res = scan_paths([p], root=tmp_path)
    assert res.suppressed == 1
    assert res.findings == []


# ------------------------------------------------------------------ baseline
def test_baseline_roundtrip_match_and_stale(tmp_path):
    res = scan_paths([FIXTURES / "broad_except_pos.py"], root=REPO)
    findings = [f for f in res.findings if f.rule == "broad-except"]
    assert findings
    path = tmp_path / "base.json"
    save_baseline(path, findings)
    entries = load_baseline(path)
    new, baselined, stale = apply_baseline(findings, entries)
    assert (new, baselined, stale) == ([], len(findings), [])
    # Deleting one entry resurfaces exactly that finding...
    new, _, stale = apply_baseline(findings, entries[1:])
    assert len(new) == 1 and not stale
    assert new[0].key == (entries[0]["path"], entries[0]["rule"], entries[0]["line"])
    # ...and an entry with no matching finding is stale.
    extra = dict(entries[0], line=9999)
    _, _, stale = apply_baseline(findings, entries + [extra])
    assert stale == [extra]


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "absent.json") == []


# ----------------------------------------------------------------------- cli
def test_cli_exit_codes_and_update(tmp_path):
    target = FIXTURES / "broad_except_pos.py"
    base = tmp_path / "b.json"
    out = io.StringIO()
    # Dirty tree, empty baseline -> 1, findings printed as path:line rule msg
    assert run_lint([str(target)], baseline=str(base), root=str(REPO), out=out) == 1
    line = out.getvalue().splitlines()[0]
    assert line.startswith("tests/fixtures/lint/broad_except_pos.py:7 broad-except ")
    # Update, then the same scan is clean
    assert run_lint(
        [str(target)], baseline=str(base), update_baseline=True,
        root=str(REPO), out=io.StringIO(),
    ) == 0
    assert run_lint([str(target)], baseline=str(base), root=str(REPO),
                    out=io.StringIO()) == 0
    # Deleting one baseline entry -> non-zero again
    data = json.loads(base.read_text())
    data["entries"] = data["entries"][1:]
    base.write_text(json.dumps(data))
    assert run_lint([str(target)], baseline=str(base), root=str(REPO),
                    out=io.StringIO()) == 1
    # A stale entry alone -> non-zero too
    data = json.loads(base.read_text())
    data["entries"] = [dict(data["entries"][0], line=9999)] + data["entries"]
    base.write_text(json.dumps(data))
    assert run_lint([str(target)], baseline=str(base), root=str(REPO),
                    out=io.StringIO()) == 1


def test_cli_json_format(tmp_path):
    out = io.StringIO()
    code = run_lint(
        [str(FIXTURES / "async_blocking_pos.py")],
        baseline=str(tmp_path / "none.json"),
        fmt="json",
        root=str(REPO),
        out=out,
    )
    payload = json.loads(out.getvalue())
    assert code == 1 and payload["exit"] == 1
    assert payload["counts_by_rule"]["async-blocking"] == 5
    assert payload["files_scanned"] == 1
    assert {f["rule"] for f in payload["new"]} == {"async-blocking"}
    assert all({"path", "line", "rule", "message"} <= set(f) for f in payload["new"])


def test_cli_unknown_rule_is_a_usage_error_not_a_crash(tmp_path):
    out = io.StringIO()
    code = run_lint(
        [str(FIXTURES / "blank_lines_neg.py")],
        baseline=str(tmp_path / "b.json"),
        rules=["no-such-rule"],
        root=str(REPO),
        out=out,
    )
    assert code == 2
    assert "unknown rule" in out.getvalue()


def test_cli_malformed_baseline_is_a_usage_error_not_a_crash(tmp_path):
    base = tmp_path / "b.json"
    for bad in ('{"entries": [{"path": "x"}]}', "{truncated"):
        base.write_text(bad)
        out = io.StringIO()
        code = run_lint(
            [str(FIXTURES / "blank_lines_neg.py")],
            baseline=str(base), root=str(REPO), out=out,
        )
        assert code == 2
        assert "cannot read baseline" in out.getvalue()


def test_cli_filtered_update_preserves_other_rules_entries(tmp_path):
    base = tmp_path / "b.json"
    target = FIXTURES / "suppressed.py"  # has 1 async-blocking (suppressed)
    # Seed the baseline with a foreign rule's entry...
    save_baseline(
        base,
        scan_paths([FIXTURES / "broad_except_pos.py"], root=REPO).findings,
    )
    before = load_baseline(base)
    assert {e["rule"] for e in before} == {"broad-except"}
    # ...then a --rule blank-lines --update-baseline over another file must
    # not wipe it.
    assert run_lint(
        [str(target)], baseline=str(base), update_baseline=True,
        rules=["blank-lines"], root=str(REPO), out=io.StringIO(),
    ) == 0
    assert load_baseline(base) == before


def test_cli_subcommand_wiring():
    from mcpx.cli.main import main

    # (an absent baseline is empty — the committed one would read as stale
    # against a single-fixture scan, by design)
    assert main(["lint", str(FIXTURES / "blank_lines_neg.py"),
                 "--baseline", str(REPO / "does-not-exist.json")]) == 0
    assert main(["lint", str(FIXTURES / "blank_lines_pos.py"),
                 "--baseline", str(REPO / "does-not-exist.json")]) == 1


def test_cli_sarif_format_matches_golden(tmp_path):
    out = io.StringIO()
    code = run_lint(
        [str(FIXTURES / "broad_except_pos.py")],
        baseline=str(tmp_path / "none.json"),
        fmt="sarif",
        root=str(REPO),
        out=out,
    )
    assert code == 1
    doc = json.loads(out.getvalue())
    golden = json.loads((FIXTURES / "sarif_golden.json").read_text())
    assert doc == golden
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "mcpxlint"
    assert all(
        r["locations"][0]["physicalLocation"]["region"]["startLine"] > 0
        for r in run["results"]
    )


def test_cli_changed_scopes_report_to_diff(tmp_path):
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=tmp_path, check=True, capture_output=True,
        )

    git("init", "-q")
    # a committed violation (a.py) and a clean committed file (b.py)...
    (tmp_path / "a.py").write_text(
        "import time\n\n\nasync def f():\n    time.sleep(1)\n"
    )
    (tmp_path / "b.py").write_text("def ok():\n    return 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    # ...then only b.py changes: --changed must report b.py's new finding
    # and stay silent about a.py's pre-existing one.
    (tmp_path / "b.py").write_text(
        "import time\n\n\nasync def g():\n    time.sleep(2)\n"
    )
    out = io.StringIO()
    code = run_lint(
        [str(tmp_path)],
        baseline=str(tmp_path / "none.json"),
        root=str(tmp_path),
        changed=True,
        fmt="json",
        out=out,
    )
    payload = json.loads(out.getvalue())
    assert code == 1
    assert payload["files_scanned"] == 1
    assert {f["path"] for f in payload["new"]} == {"b.py"}
    # per-rule wall time rides the json telemetry
    assert "async-blocking" in payload["rule_wall_s"]
    # with a clean working tree (everything committed) --changed is a no-op
    git("add", ".")
    git("commit", "-qm", "fixups")
    out2 = io.StringIO()
    assert run_lint(
        [str(tmp_path)], baseline=str(tmp_path / "none.json"),
        root=str(tmp_path), changed=True, out=out2,
    ) == 0
    assert "nothing to lint" in out2.getvalue()


def test_cli_changed_works_from_a_repo_subdirectory(tmp_path):
    # `git diff --name-only` prints toplevel-relative paths; without
    # --relative a subdirectory root silently drops every tracked change
    # and reports a false clean.
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=tmp_path, check=True, capture_output=True,
        )

    sub = tmp_path / "pkg"
    sub.mkdir()
    git("init", "-q")
    (sub / "mod.py").write_text("def ok():\n    return 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    (sub / "mod.py").write_text(
        "import time\n\n\nasync def f():\n    time.sleep(1)\n"
    )
    out = io.StringIO()
    code = run_lint(
        [str(sub)], baseline=str(tmp_path / "none.json"), root=str(sub),
        changed=True, fmt="json", out=out,
    )
    payload = json.loads(out.getvalue())
    assert code == 1
    assert {f["path"] for f in payload["new"]} == {"mod.py"}


def test_cli_changed_leaves_other_files_baseline_alone(tmp_path):
    # Baseline entries for files outside the diff are neither reported
    # stale nor wiped by --changed --update-baseline.
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=tmp_path, check=True, capture_output=True,
        )

    git("init", "-q")
    viol = "import time\n\n\nasync def f():\n    time.sleep(1)\n"
    (tmp_path / "a.py").write_text(viol)
    (tmp_path / "b.py").write_text("def ok():\n    return 1\n")
    base = tmp_path / "base.json"
    save_baseline(base, scan_paths([tmp_path / "a.py"], root=tmp_path).findings)
    before = load_baseline(base)
    assert {e["path"] for e in before} == {"a.py"}
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "b.py").write_text(viol.replace("def f", "def g"))
    # check mode: a.py's untouched baselined finding must NOT read as stale
    out = io.StringIO()
    code = run_lint(
        [str(tmp_path)], baseline=str(base), root=str(tmp_path),
        changed=True, fmt="json", out=out,
    )
    payload = json.loads(out.getvalue())
    assert payload["stale_baseline"] == []
    assert {f["path"] for f in payload["new"]} == {"b.py"}
    assert code == 1
    # update mode: re-baselining the diff preserves a.py's entries
    assert run_lint(
        [str(tmp_path)], baseline=str(base), root=str(tmp_path),
        changed=True, update_baseline=True, out=io.StringIO(),
    ) == 0
    after = load_baseline(base)
    assert [e for e in after if e["path"] == "a.py"] == before
    assert {e["path"] for e in after} == {"a.py", "b.py"}


# ------------------------------------------------------------------- --fix
_FIXABLE = (
    "import time\n"
    "\n"
    "\n"
    "\n"
    "\n"
    "async def f():\n"
    "    time.sleep(1)  # mcpx: ignore[async-blocking,async-blocking] - dupe\n"
    "    x = 1  # mcpx: ignore[blank-lines] - never fires here\n"
    "    # mcpx: ignore[asnyc-blocking] - typo'd id, comment-only line\n"
    "    return x\n"
)

_FIXED = (
    "import time\n"
    "\n"
    "\n"
    "async def f():\n"
    "    time.sleep(1)  # mcpx: ignore[async-blocking] - dupe\n"
    "    x = 1\n"
    "    return x\n"
)


def test_fix_rewrites_mechanical_findings(tmp_path):
    # Duplicate ids collapse, a dead suppression vanishes with its
    # justification, a comment-only suppression line is deleted, and the
    # blank run collapses to two — then a re-scan is clean and a second
    # --fix pass is a no-op (idempotent).
    p = tmp_path / "t.py"
    p.write_text(_FIXABLE)
    out = io.StringIO()
    code = run_lint(
        [str(p)], baseline=str(tmp_path / "none.json"), root=str(tmp_path),
        fix=True, out=out,
    )
    assert code == 0
    assert p.read_text() == _FIXED
    assert "rewrote 1 file(s)" in out.getvalue()
    res = scan_paths([p], root=tmp_path)
    assert [f.rule for f in res.findings] == []
    assert res.suppressed == 1  # the real async-blocking suppression stays
    out2 = io.StringIO()
    assert run_lint(
        [str(p)], baseline=str(tmp_path / "none.json"), root=str(tmp_path),
        fix=True, out=out2,
    ) == 0
    assert p.read_text() == _FIXED
    assert "rewrote 0 file(s)" in out2.getvalue()


def test_fix_dry_run_prints_diff_and_writes_nothing(tmp_path):
    p = tmp_path / "t.py"
    p.write_text(_FIXABLE)
    out = io.StringIO()
    code = run_lint(
        [str(p)], baseline=str(tmp_path / "none.json"), root=str(tmp_path),
        fix=True, fix_dry_run=True, out=out,
    )
    assert code == 0
    assert p.read_text() == _FIXABLE  # untouched
    diff = out.getvalue()
    assert "--- a/t.py" in diff and "+++ b/t.py" in diff
    assert "-    x = 1  # mcpx: ignore[blank-lines] - never fires here" in diff
    assert "+    x = 1" in diff
    assert "would rewrite 1 file(s)" in diff


def test_fix_respects_rule_selection(tmp_path):
    # Known suppression ids are judged only against rules that ran: an
    # async-blocking-only --fix must leave the (dead) blank-lines
    # suppression alone, while a typo'd id is removed regardless.
    p = tmp_path / "t.py"
    p.write_text(_FIXABLE)
    assert run_lint(
        [str(p)], baseline=str(tmp_path / "none.json"), root=str(tmp_path),
        rules=["async-blocking"], fix=True, out=io.StringIO(),
    ) == 0
    text = p.read_text()
    assert "ignore[blank-lines] - never fires here" in text
    assert "asnyc-blocking" not in text


def test_fix_cli_flags_wired():
    from mcpx.cli.main import main
    import contextlib
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "t.py"
        p.write_text(_FIXABLE)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            code = main([
                "lint", str(p), "--fix", "--dry-run",
                "--baseline", str(pathlib.Path(d) / "none.json"),
            ])
        assert code == 0
        assert p.read_text() == _FIXABLE
        assert "would rewrite 1 file(s)" in buf.getvalue()


def test_cli_changed_sarif_smoke(tmp_path, monkeypatch):
    # The CI shape: `mcpx lint --changed --format sarif` end to end
    # through the real subcommand over a dirty worktree.
    import contextlib
    import subprocess

    def git(*args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=tmp_path, check=True, capture_output=True,
        )

    git("init", "-q")
    (tmp_path / "a.py").write_text("def ok():\n    return 1\n")
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "a.py").write_text(
        "import time\n\n\nasync def f():\n    time.sleep(1)\n"
    )
    from mcpx.cli.main import main

    monkeypatch.chdir(tmp_path)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        code = main([
            "lint", str(tmp_path), "--changed", "--format", "sarif",
            "--baseline", str(tmp_path / "none.json"),
        ])
    assert code == 1
    doc = json.loads(buf.getvalue())
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "mcpxlint"
    results = run["results"]
    assert {r["ruleId"] for r in results} == {"async-blocking"}
    assert all(
        r["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]
        == "a.py"
        for r in results
    )


# ----------------------------------------------------------- tier-1 gate
@pytest.fixture(scope="module")
def full_tree():
    """The one full scan of mcpx/ + benchmarks/ (call graph, dataflow
    fixpoint and all) that both gates below read."""
    return scan_paths([REPO / "mcpx", REPO / "benchmarks"], root=REPO)


def test_full_tree_lint_runs_every_rule_over_every_file(full_tree):
    """What a loaded CPU cannot change about the full scan: every file of
    both trees is in it, and every registered rule ran and left the
    per-rule wall-time telemetry that would show a pass blowing up. Its
    duration is no assertion of a CPU run (17-19 s alone, 27-34 s under six
    loaded workers)."""
    from mcpx.analysis.core import iter_py_files

    assert full_tree.files_scanned == len(iter_py_files([REPO / "mcpx", REPO / "benchmarks"])) > 0
    assert set(all_rules()) == set(full_tree.rule_wall_s)


def test_tree_is_clean_against_committed_baseline(full_tree):
    """THE gate: the full analyzer over mcpx/ + benchmarks/ must report
    nothing beyond the committed baseline, and every baseline entry must
    still match a live finding (no stale grandfathering)."""
    entries = load_baseline(BASELINE)
    new, _, stale = apply_baseline(full_tree.findings, entries)
    assert not new, "new findings:\n" + "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries (delete them): {stale}"


def test_committed_baseline_stays_small():
    # The baseline is a burn-down list, not a dumping ground: additions
    # need a better reason than "the analyzer complained".
    assert len(load_baseline(BASELINE)) <= 10
