"""Tier-1 schema gate for the bench output JSON (ISSUE 7 satellite) and
the `mcpx bench report` regression tracker.

The gate pins the NEW observability fields — the roofline block,
``pallas_reason``, and the embedded regression verdict — against
``bench._output_json`` so a later PR cannot silently drop them from the
one JSON line the driver persists. Host-side pure functions only: no
engine, no device, no timed phases."""

import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (stdlib-only module level; jax untouched)
from mcpx.cli.bench_report import (  # noqa: E402
    build_report,
    default_series,
    load_runs,
    run_report,
)


def _stats(**overrides):
    """A representative ``_run`` stats dict (the fields _output_json reads)."""
    base = {
        "plans_per_sec": 5.0,
        "p50_ms": 100.0,
        "p99_ms": 200.0,
        "open_loop_rate": 3.5,
        "sat_p50_ms": 150.0,
        "sat_p99_ms": 300.0,
        "llm_share": 1.0,
        "decode_tok_s": 80.0,
        "decode_forwards": 100,
        "tok_per_forward": 2.0,
        "prefill_tokens": 1000,
        "mfu": 0.001,
        "mfu_basis": "xla_cost_analysis",
        "roofline": {
            "basis": "xla_cost_analysis",
            "mfu_basis": "xla_cost_analysis",
            "peak_flops": 1e12,
            "peak_flops_basis": "measured_matmul",
            "peak_bytes_s": None,
            "phases": {
                "sat": {
                    "flops": 1e9,
                    "bytes_accessed": 1e8,
                    "wall_s": 1.0,
                    "achieved_flops_s": 1e9,
                    "achieved_bytes_s": 1e8,
                    "arithmetic_intensity": 10.0,
                    "mfu": 0.001,
                    "hbm_bw_util": None,
                    "bound": None,
                },
                "open": None,
            },
            "mfu_analytic": 0.0008,
            "xla_vs_analytic": 1.2,
        },
        "pallas_reason": "cpu backend: Mosaic TPU kernels cannot run — "
        "the fused-jnp reference attention serves",
        "phase_tok_per_forward": {"sat": 2.0, "open": 2.0},
        "phase_p50_ms": {"queue": 1.0, "prefill": 2.0, "decode": 3.0},
        "phase_p50_open_ms": {"queue": 1.0, "prefill": 2.0, "decode": 3.0},
        "plan_quality": {"score": 0.2},
        "backend": "cpu",
        "n_services": 1000,
        "n_requests": 16,
        "errors": 0,
        "overload": None,
        "mixed": None,
        "spec": None,
        "prefix": None,
        "tier": None,
        "flight": None,
        "ledger": None,
        "kernel": None,
        "cluster": None,
        "provenance": None,
        "pallas_paths": {
            "enabled": True,
            "interpret": True,
            "reason": None,
            "paths": {
                "decode": {"engaged": True, "dispatches": 40, "reason": None},
                "prefill": {"engaged": True, "dispatches": 12, "reason": None},
                "spec_verify": {
                    "engaged": True,
                    "dispatches": 0,
                    "reason": "idle: speculative decoding off",
                },
            },
        },
        "latency_attribution": None,
        "chaos": None,
        "grammar_fallback": {"shape_only": 0, "keys_free": 0, "typed_off": 0},
        "cache_hit_share": 0.0,
        "unique_intents": 0,
    }
    base.update(overrides)
    return base


# ------------------------------------------------------------- schema gate
def test_output_schema_carries_roofline_pallas_reason_and_verdict():
    out = bench._output_json(_stats(), {"score": 0.86}, "test")
    # The pre-existing contract fields stay.
    for key in (
        "metric", "value", "p50_ms", "llm_share", "mfu", "mfu_basis",
        "pallas", "spec_speedup", "chaos_success_rate", "grammar_fallback",
        # ISSUE 8: the prefix-reuse phase block and its promoted keys.
        "prefix", "prefill_tokens_per_request", "prefill_reduction",
        "prefix_hit_rate", "replan_p50_cold_ms", "replan_p50_warm_ms",
        # ISSUE 11: the tiered-KV phase block and its promoted keys.
        "tier", "tier_token_hit_rate", "tier_hit_ratio",
        "victim_token_hit_rate", "warm_restart_prefill_ratio",
        # ISSUE 13: the flight-recorder phase block, its promoted
        # overhead/profile keys, and the saturation warm-replan number.
        "flight", "flight_overhead_frac", "worker_profile",
        "replan_warm_sat_p50_ms",
        # ISSUE 14: the cost-ledger phase block, its promoted overhead
        # key, and the per-tenant usage-attribution block.
        "ledger", "ledger_overhead_frac", "attribution",
        # ISSUE 15: the ragged-kernel/fused-dispatch phase block, its
        # promoted cadence/speedup keys, and the per-path pallas block.
        "kernel", "decode_dispatches_per_token",
        "decode_dispatches_per_token_per_step", "fused_decode_speedup",
        "pallas_paths",
        # ISSUE 16: the cluster phase block, its promoted scaling /
        # failover / affinity / warm-rejoin keys, and the measurement
        # basis scenario dimension (ROADMAP item 4).
        "cluster", "cluster_scaling_linearity",
        "cluster_p99_one_down_ratio", "cluster_routed_token_hit_rate",
        "cluster_rr_token_hit_rate", "cluster_affinity_hit_margin",
        "cluster_warm_rejoin_prefill_ratio", "measurement_basis",
    ):
        assert key in out, key
    # ISSUE 7 fields: the roofline block…
    rf = out["roofline"]
    assert rf is not None
    assert rf["basis"] == "xla_cost_analysis"
    assert rf["mfu_basis"] == "xla_cost_analysis"
    sat = rf["phases"]["sat"]
    for key in (
        "achieved_flops_s", "achieved_bytes_s", "arithmetic_intensity",
        "mfu", "flops", "bytes_accessed",
    ):
        assert key in sat, key
    assert rf["mfu_analytic"] is not None
    # …pallas_reason…
    assert isinstance(out["pallas_reason"], str) and out["pallas_reason"]
    # …and the embedded regression verdict.
    assert isinstance(out["regression"], dict)
    assert "verdict" in out["regression"]
    json.dumps(out)  # the one-line artifact must stay JSON-serializable


def test_output_promotes_tier_phase_acceptance_keys():
    """ISSUE 11: when the tiered-KV phase ran, its acceptance numbers are
    promoted to the top level for TRACKED_METRICS regression tracking."""
    tier = {
        "working_set_ratio": 10.0,
        "tier_token_hit_rate": 0.61,
        "tier_hit_ratio": 4.2,
        "victim_token_hit_rate": 0.88,
        "warm_restart_prefill_ratio": 8.0,
        "spills": 120,
        "readmits": 80,
        "destructive_evictions": 0,
    }
    out = bench._output_json(_stats(tier=tier), None, "test")
    assert out["tier"]["working_set_ratio"] == 10.0
    assert out["tier_token_hit_rate"] == 0.61
    assert out["tier_hit_ratio"] == 4.2
    assert out["victim_token_hit_rate"] == 0.88
    assert out["warm_restart_prefill_ratio"] == 8.0
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["tier"] is None and out["tier_token_hit_rate"] is None


def test_output_promotes_flight_phase_acceptance_keys():
    """ISSUE 13: when the flight phase ran, the overhead fraction and the
    worker profile block are promoted to the top level (regression
    tracking + the >=95% attribution acceptance read them there)."""
    wp = {
        "phases": {
            "dispatch": {"total_s": 1.0, "share": 0.5, "count": 10,
                         "p50_us": 100.0},
            "idle": {"total_s": 1.0, "share": 0.5, "count": 10,
                     "p50_us": 100.0},
        },
        "wall_s": 2.0,
        "attributed_s": 2.0,
        "attributed_frac": 1.0,
        "iterations": 10,
    }
    flight = {
        "requests": 64,
        "plans_per_sec_off": 50.0,
        "plans_per_sec_on": 49.5,
        "flight_overhead_frac": 0.01,
        "worker_profile": wp,
        "flight_samples": 12,
        "flight_ring_len": 12,
        "detectors": ["p99_shift"],
    }
    out = bench._output_json(_stats(flight=flight), None, "test")
    assert out["flight_overhead_frac"] == 0.01
    assert out["worker_profile"]["attributed_frac"] == 1.0
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["flight"] is None and out["flight_overhead_frac"] is None
    assert out["worker_profile"] is None
    assert out["replan_warm_sat_p50_ms"] is None


def test_output_promotes_kernel_phase_acceptance_keys():
    """ISSUE 15: when the ragged-kernel/fused-dispatch phase ran, the
    dispatch cadence (fused + per-step arms) and the wall-clock guard are
    promoted to the top level for TRACKED_METRICS regression tracking,
    and the per-path pallas block rides the headline."""
    kernel = {
        "requests": 48,
        "rounds": 3,
        "steps_per_dispatch": 4,
        "per_step": {"decode_tok_s": 100.0, "dispatches_per_token": 0.26},
        "fused": {"decode_tok_s": 120.0, "dispatches_per_token": 0.06},
        "decode_dispatches_per_token": 0.06,
        "decode_dispatches_per_token_per_step": 0.26,
        "dispatch_reduction": 4.33,
        "fused_decode_speedup": 1.2,
        "interpret_parity": True,
        "cadence_parity": True,
        "pallas_paths": {"enabled": True},
    }
    out = bench._output_json(_stats(kernel=kernel), None, "test")
    assert out["kernel"]["steps_per_dispatch"] == 4
    assert out["decode_dispatches_per_token"] == 0.06
    assert out["decode_dispatches_per_token_per_step"] == 0.26
    assert out["fused_decode_speedup"] == 1.2
    assert out["pallas_paths"]["paths"]["prefill"]["engaged"] is True
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["kernel"] is None
    assert out["decode_dispatches_per_token"] is None
    assert out["fused_decode_speedup"] is None


def test_output_promotes_cluster_phase_acceptance_keys():
    """ISSUE 16: when the cluster phase ran, its scaling / failover /
    affinity / warm-rejoin acceptance numbers are promoted to the top
    level for TRACKED_METRICS regression tracking."""
    cluster = {
        "basis": {"scaling": "router-sim", "warm_rejoin": "interpret-kernel"},
        "plans_per_sec": {"1": 190.0, "2": 380.0, "4": 760.0},
        "cluster_scaling_linearity": 0.98,
        "one_down": {"p99_ms_baseline": 28.0, "p99_ms_one_down": 41.0,
                     "failures": 0, "resteered": 3, "rejoin_generation": 1},
        "cluster_p99_one_down_ratio": 1.46,
        "cluster_routed_token_hit_rate": 0.79,
        "cluster_rr_token_hit_rate": 0.31,
        "cluster_affinity_hit_margin": 0.48,
        "warm_rejoin": {"prefill_ratio": 8.0, "parity_ok": True},
        "cluster_warm_rejoin_prefill_ratio": 8.0,
    }
    out = bench._output_json(_stats(cluster=cluster), None, "test")
    assert out["cluster"]["one_down"]["failures"] == 0
    assert out["cluster_scaling_linearity"] == 0.98
    assert out["cluster_p99_one_down_ratio"] == 1.46
    assert out["cluster_routed_token_hit_rate"] == 0.79
    assert out["cluster_rr_token_hit_rate"] == 0.31
    assert out["cluster_affinity_hit_margin"] == 0.48
    assert out["cluster_warm_rejoin_prefill_ratio"] == 8.0
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["cluster"] is None
    assert out["cluster_scaling_linearity"] is None
    assert out["cluster_routed_token_hit_rate"] is None
    assert out["cluster_warm_rejoin_prefill_ratio"] is None


def test_output_promotes_provenance_phase_acceptance_keys():
    """ISSUE 19: when the decision-provenance phase ran, the recorder's
    overhead fraction and the /explain schema-coverage fraction are
    promoted to the top level for TRACKED_METRICS regression tracking."""
    provenance = {
        "requests": 96,
        "rounds": 3,
        "plans_per_sec_off": 50.0,
        "plans_per_sec_on": 49.7,
        "provenance_overhead_frac": 0.006,
        "explanation_coverage": 1.0,
        "decisions_per_request": 1.5,
        "records_emitted": 144,
    }
    out = bench._output_json(_stats(provenance=provenance), None, "test")
    assert out["provenance"]["decisions_per_request"] == 1.5
    assert out["provenance_overhead_frac"] == 0.006
    assert out["explanation_coverage"] == 1.0
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["provenance"] is None
    assert out["provenance_overhead_frac"] is None
    assert out["explanation_coverage"] is None


def test_measurement_basis_labels_the_platform(monkeypatch):
    """ROADMAP item 4: the output JSON carries an explicit measurement
    basis — real-TPU / interpret-kernel / jnp-proxy — derived from the
    platform and the kernel route."""
    monkeypatch.setattr(bench, "_on_tpu", lambda: False)
    monkeypatch.delenv("MCPX_BENCH_PALLAS", raising=False)
    assert bench._measurement_basis() == "interpret-kernel"
    monkeypatch.setenv("MCPX_BENCH_PALLAS", "0")
    assert bench._measurement_basis() == "jnp-proxy"
    monkeypatch.delenv("MCPX_BENCH_PALLAS")
    monkeypatch.setattr(bench, "_on_tpu", lambda: True)
    assert bench._measurement_basis() == "real-TPU"
    monkeypatch.setattr(bench, "_on_tpu", lambda: False)
    out = bench._output_json(_stats(), None, "test")
    assert out["measurement_basis"] == "interpret-kernel"


def test_report_scenario_splits_on_measurement_basis():
    """A measurement-basis change (e.g. r09's jnp-proxy ->
    interpret-kernel switch) reads as a NEW scenario: prior runs on the
    old basis are excluded, not compared."""
    prior = [
        (f"a{i}", _mk_run(10.0, 100.0, measurement_basis="jnp-proxy"))
        for i in range(3)
    ]
    shifted = ("z", _mk_run(30.0, 30.0, measurement_basis="interpret-kernel"))
    rep = build_report([*prior, shifted])
    assert rep["verdict"] == "no_comparable_series"
    assert set(rep["excluded_scenario_mismatch"]) == {"a0", "a1", "a2"}
    # Same basis compares as before.
    same = ("z2", _mk_run(9.9, 101.0, measurement_basis="jnp-proxy"))
    rep = build_report([*prior, same])
    assert rep["verdict"] == "ok"
    assert set(rep["compared_against"]) == {"a0", "a1", "a2"}


def test_unwrap_derives_basis_for_pre_r10_artifacts(tmp_path):
    """Artifacts predating the measurement_basis field get it derived from
    what they recorded: TPU backend -> real-TPU; pallas + pallas_paths
    (the r09 interpreter round) -> interpret-kernel; else jnp-proxy."""
    from mcpx.cli.bench_report import _derive_basis

    assert _derive_basis(_mk_run(1.0, 1.0, backend="tpu")) == "real-TPU"
    assert _derive_basis(
        _mk_run(1.0, 1.0, pallas=True, pallas_paths={"enabled": True})
    ) == "interpret-kernel"
    assert _derive_basis(_mk_run(1.0, 1.0, pallas=False)) == "jnp-proxy"
    assert _derive_basis(_mk_run(1.0, 1.0)) == "jnp-proxy"
    # load_runs backfills through _unwrap, so scenario keying never
    # wildcards across a basis change.
    p = tmp_path / "BENCH_r05.json"
    p.write_text(json.dumps(_mk_run(10.0, 100.0, pallas=False)))
    runs = load_runs([str(p)])
    assert runs[0][1]["measurement_basis"] == "jnp-proxy"


def test_output_promotes_ledger_phase_acceptance_keys():
    """ISSUE 14: when the cost-ledger phase ran, the overhead fraction
    and the attribution block are promoted to the top level (regression
    tracking reads ledger_overhead_frac and
    attribution.wall_attributed_frac there)."""
    attribution = {
        "requests": 288,
        "wall_attributed_frac": 0.97,
        "flops_per_plan": 5.0e7,
        "decode_tokens_per_plan": 9.5,
        "flops_conserved": True,
        "tenants": {
            "acme": {"requests": 72, "decode_tokens": 700,
                     "prefill_tokens": 1500, "flops": 1.2e9,
                     "decode_ms": 9000.0},
        },
    }
    ledger = {
        "requests": 96,
        "rounds": 3,
        "plans_per_sec_off": 50.0,
        "plans_per_sec_on": 49.6,
        "ledger_overhead_frac": 0.008,
        "attribution": attribution,
        "slo": {"objectives": [
            {"name": "latency_p99", "budget_remaining": 1.0,
             "fast_burn": 0.0},
        ]},
    }
    out = bench._output_json(_stats(ledger=ledger), None, "test")
    assert out["ledger_overhead_frac"] == 0.008
    assert out["attribution"]["wall_attributed_frac"] == 0.97
    assert out["attribution"]["flops_conserved"] is True
    assert out["attribution"]["tenants"]["acme"]["requests"] == 72
    # Skipped phase: block and promoted keys null, never absent.
    out = bench._output_json(_stats(), None, "test")
    assert out["ledger"] is None and out["ledger_overhead_frac"] is None
    assert out["attribution"] is None


def test_output_roofline_never_null_even_without_accounting():
    """Acceptance: the roofline block is non-null with a LABELED fallback
    when cost accounting was unavailable — never silently absent."""
    out = bench._output_json(
        _stats(roofline=None, mfu_basis="measured_matmul"), None, "test"
    )
    assert out["roofline"] is not None
    assert out["roofline"]["basis"] == "unavailable"
    assert out["roofline"]["mfu_basis"] == "unavailable"
    assert "phases" in out["roofline"]


def test_roofline_block_from_cost_snapshots():
    """_roofline_block turns /costs snapshot deltas into per-phase achieved
    rates; a missing scrape degrades to basis='unavailable'."""

    def snap(flops, byt):
        return {"engine": {"totals": {"flops_executed": flops, "bytes_executed": byt}}}

    block = bench._roofline_block(
        snap(0.0, 0.0), snap(2e9, 4e8), snap(3e9, 6e8),
        sat_wall=2.0, open_wall=1.0,
        peak_flops=1e12, peak_flops_basis="measured_matmul", peak_bytes=None,
        mfu_analytic=0.001, analytic_flops=1e9,
    )
    assert block["basis"] == "xla_cost_analysis"
    sat, opn = block["phases"]["sat"], block["phases"]["open"]
    assert sat["achieved_flops_s"] == 1e9
    assert sat["mfu"] == 0.001
    assert sat["arithmetic_intensity"] == 5.0
    assert opn["achieved_flops_s"] == 1e9
    assert block["xla_vs_analytic"] == 2.0
    degraded = bench._roofline_block(
        None, None, None, 2.0, 1.0, 1e12, "measured_matmul", None, 0.001, 1e9
    )
    assert degraded["basis"] == "unavailable"
    assert degraded["phases"]["sat"] is None


def test_pallas_reason_covers_the_off_paths(monkeypatch):
    # CPU backend (the tier-1 platform): since ISSUE 15 the kernel serves
    # through the Pallas interpreter by default — the reason says so —
    # and MCPX_BENCH_PALLAS=0 restores the jnp proxy, reasoned.
    monkeypatch.setattr(bench, "_on_tpu", lambda: False)
    monkeypatch.delenv("MCPX_BENCH_PALLAS", raising=False)
    assert "interpret" in bench._pallas_reason()
    assert bench._pallas_on() is True
    monkeypatch.setenv("MCPX_BENCH_PALLAS", "0")
    assert "MCPX_BENCH_PALLAS=0" in bench._pallas_reason()
    assert bench._pallas_on() is False
    monkeypatch.delenv("MCPX_BENCH_PALLAS")
    # Operator override on TPU.
    monkeypatch.setattr(bench, "_on_tpu", lambda: True)
    monkeypatch.setenv("MCPX_BENCH_PALLAS", "0")
    assert "MCPX_BENCH_PALLAS=0" in bench._pallas_reason()
    # Engine hardware probe rejected the kernel.
    monkeypatch.setenv("MCPX_BENCH_PALLAS", "1")
    assert "head_dim" in bench._pallas_reason(engine_use_pallas=False)
    # Nothing says off — and no artifact of an earlier run steers this one.
    monkeypatch.delenv("MCPX_BENCH_PALLAS")
    assert bench._pallas_on() is True
    assert bench._pallas_reason(engine_use_pallas=True) == "enabled"
    assert not hasattr(bench, "_smoke_artifact")


# --------------------------------------------------------- regression report
def test_bench_report_over_committed_series():
    """ISSUE 7 acceptance: `mcpx bench report` over >= 2 committed
    BENCH_r*.json files produces a regression verdict."""
    runs = load_runs(default_series(REPO))
    assert len(runs) >= 2, "committed BENCH series shrank below 2 readable runs?"
    report = build_report(runs)
    assert report["verdict"] in ("ok", "regressed", "no_comparable_series")
    assert report["metrics"], "no tracked metrics evaluated"
    # The headline metric must have been comparable across the series.
    assert report["metrics"]["value"]["verdict"] in ("ok", "improved", "regressed")
    json.dumps(report)


def _mk_run(value, p50, **extra):
    return {
        "metric": "plans_per_sec", "value": value, "p50_ms": p50,
        "model": "test", "backend": "cpu", "vocab": "bpe",
        "quantize": "none", "registry": "synthetic", "n_services": 1000,
        **extra,
    }


def test_report_verdicts_bands_and_scenario_exclusion():
    runs = [
        ("r1", _mk_run(10.0, 100.0)),
        ("r2", _mk_run(10.5, 102.0)),
        ("r3", _mk_run(9.8, 98.0)),
        # A different scenario must be excluded, not averaged in.
        ("tpu", dict(_mk_run(500.0, 5.0), backend="tpu", model="2b")),
        # Latest: throughput fine (inside band), p50 3x worse (outside).
        ("r4", _mk_run(10.1, 300.0)),
    ]
    report = build_report(runs)
    assert report["verdict"] == "regressed"
    assert report["excluded_scenario_mismatch"] == ["tpu"]
    assert set(report["compared_against"]) == {"r1", "r2", "r3"}
    assert report["metrics"]["value"]["verdict"] == "ok"
    m = report["metrics"]["p50_ms"]
    assert m["verdict"] == "regressed"
    assert m["delta_frac"] > m["band_frac"]
    assert "p50_ms" in report["regressions"]
    # Improvement in the good direction reads as improved, not regressed.
    runs[-1] = ("r4", _mk_run(20.0, 99.0))
    report = build_report(runs)
    assert report["verdict"] == "ok"
    assert report["metrics"]["value"]["verdict"] == "improved"


def test_absolute_noise_floor_for_near_zero_fractions():
    """flight/ledger overhead and deadline-overrun share are paired
    differences with a true value of ~0: when both the latest value and
    the prior median sit inside the metric's absolute floor, the verdict
    reads ok no matter how large the RELATIVE delta looks (r08..r10 kept
    flagging 0.018 -> 0.054 as a 3x regression). A value that escapes
    the floor is judged by the normal band."""
    from mcpx.cli.bench_report import NOISE_FLOORS, render_text

    prior = [
        ("a", _mk_run(10.0, 100.0, flight_overhead_frac=-0.0183)),
        ("b", _mk_run(10.1, 101.0, flight_overhead_frac=0.0026)),
        ("c", _mk_run(9.9, 99.0, flight_overhead_frac=0.0173)),
    ]
    inside = ("z", _mk_run(10.0, 100.0, flight_overhead_frac=0.0544))
    report = build_report([*prior, inside])
    m = report["metrics"]["flight_overhead_frac"]
    assert m["verdict"] == "ok"
    assert m["floor_abs"] == NOISE_FLOORS["flight_overhead_frac"]
    assert "flight_overhead_frac" not in report["regressions"]
    assert "floor=±0.06 abs" in render_text(report)
    # 12% measured overhead is NOT jitter: it escapes the floor and the
    # near-zero median makes the relative delta blow past any band.
    escaped = ("z", _mk_run(10.0, 100.0, flight_overhead_frac=0.12))
    report = build_report([*prior, escaped])
    assert report["metrics"]["flight_overhead_frac"]["verdict"] == "regressed"
    assert "flight_overhead_frac" in report["regressions"]


def test_report_missing_metric_is_flagged_when_it_vanishes():
    prior = [("a", _mk_run(10.0, 100.0, mfu=0.01)) for _ in range(3)]
    latest = ("z", _mk_run(10.0, 100.0))  # mfu dropped
    report = build_report([*prior, latest])
    assert report["metrics"]["mfu"]["verdict"] == "missing"
    assert report["metrics"]["mfu"]["previous_median"] == 0.01
    # Surfaced in the top-level missing list, but NOT a regression verdict:
    # optional phases null their metrics legitimately; dropped FIELDS are
    # the schema gate's business.
    assert "mfu" in report["missing"]
    assert report["verdict"] == "ok"


def test_mfu_compared_only_within_matching_basis():
    """A measurement-basis change (analytic -> xla_cost_analysis) must not
    read as a performance regression/improvement: mfu only compares
    against prior runs with the SAME mfu_basis."""
    prior = [
        (f"a{i}", _mk_run(10.0, 100.0, mfu=0.005, mfu_basis="measured_matmul"))
        for i in range(3)
    ]
    shifted = ("z", _mk_run(10.0, 100.0, mfu=0.02, mfu_basis="xla_cost_analysis"))
    rep = build_report([*prior, shifted])
    assert rep["metrics"]["mfu"]["verdict"] == "new"  # no cross-basis priors
    assert rep["metrics"]["mfu"]["basis"] == "xla_cost_analysis"
    same_basis = ("z2", _mk_run(10.0, 100.0, mfu=0.002, mfu_basis="measured_matmul"))
    rep = build_report([*prior, same_basis])
    assert rep["metrics"]["mfu"]["verdict"] == "regressed"


def test_run_report_cli_exit_codes(tmp_path):
    p1 = tmp_path / "BENCH_r01.json"
    p2 = tmp_path / "BENCH_r02.json"
    p1.write_text(json.dumps(_mk_run(10.0, 100.0)))
    p2.write_text(json.dumps(_mk_run(10.0, 500.0)))  # p50 regressed 5x
    out = io.StringIO()
    assert run_report([str(p1), str(p2)], fmt="json", out=out) == 0
    payload = json.loads(out.getvalue())
    assert payload["verdict"] == "regressed"
    assert run_report(
        [str(p1), str(p2)], fail_on_regression=True, out=io.StringIO()
    ) == 1
    # Fewer than two readable artifacts is a usage error, not a crash.
    assert run_report([str(p1)], out=io.StringIO()) == 2
    # Driver-wrapper artifacts ({"parsed": ...}) unwrap transparently.
    p3 = tmp_path / "BENCH_r03.json"
    p3.write_text(json.dumps({"rc": 0, "parsed": _mk_run(11.0, 101.0)}))
    out = io.StringIO()
    assert run_report([str(p1), str(p3)], fmt="json", out=out) == 0
    assert json.loads(out.getvalue())["latest"] == "BENCH_r03.json"


def test_cli_subcommand_wiring(tmp_path):
    from mcpx.cli.main import main

    p1 = tmp_path / "a.json"
    p2 = tmp_path / "b.json"
    p1.write_text(json.dumps(_mk_run(10.0, 100.0)))
    p2.write_text(json.dumps(_mk_run(10.2, 101.0)))
    assert main(["bench", "report", str(p1), str(p2)]) == 0
    assert main(["bench", "report", "--format", "json", str(p1), str(p2)]) == 0


def test_regression_block_embedded_against_repo_series():
    out = bench._output_json(_stats(), None, "test")
    reg = out["regression"]
    # The repo ships >= 2 comparable CPU-proxy rounds, so the embedded
    # verdict must have actually compared something.
    assert reg["verdict"] in ("ok", "regressed")
    assert reg["compared_against"]
