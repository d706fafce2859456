"""bench.py config plumbing: the honesty-critical knobs that steer a run
(env -> engine config), the removed fallbacks (no platform switch, no
model-size retry, no invented peak) and the fallback-kind scrape that
surfaces grammar degradations in the one JSON line the operator reads.

These are host-side pure functions — no engine, no device."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (stdlib-only module level; jax untouched)


def test_batch_default_comes_from_env_or_constant_only(monkeypatch):
    """The served batch is MCPX_BENCH_BATCH or a constant — never a value
    read back from an earlier run's artifact."""
    monkeypatch.delenv("MCPX_BENCH_BATCH", raising=False)
    assert bench._bench_batch("2b") == 32
    assert bench._bench_batch("test") == 64
    monkeypatch.setenv("MCPX_BENCH_BATCH", "16")
    assert bench._bench_batch("2b") == 16


def test_main_fails_instead_of_falling_back(monkeypatch):
    """A run that cannot start fails: main() makes ONE attempt at the model
    asked for on the device JAX gives it — no device guard that re-arms a
    virtual CPU, no retry at model=test, no measured-matmul MFU peak."""
    for gone in ("_device_guard", "_measured_peak_flops", "_peak_flops_per_chip"):
        assert not hasattr(bench, gone), gone
    seen = []

    async def boom(model, *a):
        seen.append(model)
        raise RuntimeError("engine did not start")

    monkeypatch.setattr(bench, "_run", boom)
    monkeypatch.setenv("MCPX_BENCH_MODEL", "2b")
    with pytest.raises(RuntimeError, match="engine did not start"):
        bench.main()
    assert seen == ["2b"]


def test_quality_phase_failure_fails_the_run(monkeypatch, capsys):
    async def ok_run(*a):
        return {}

    async def broken_quality(**kw):
        raise RuntimeError("quality phase broke")

    monkeypatch.setattr(bench, "_run", ok_run)
    monkeypatch.setattr(bench, "_run_quality_trained", broken_quality)
    monkeypatch.setenv("MCPX_BENCH_MODEL", "test")
    monkeypatch.delenv("MCPX_BENCH_SKIP_QUALITY", raising=False)
    with pytest.raises(RuntimeError, match="quality phase broke"):
        bench.main()
    assert capsys.readouterr().out == ""  # no result line


def test_pallas_gate_forces_fused_jnp(monkeypatch):
    monkeypatch.setenv("MCPX_BENCH_PALLAS", "0")
    cfg = bench._build_config("test")
    assert cfg.engine.use_pallas is False


def test_worker_lever_knobs(monkeypatch):
    monkeypatch.setenv("MCPX_BENCH_TICK", "2")
    monkeypatch.setenv("MCPX_BENCH_DEPTH", "3")
    monkeypatch.setenv("MCPX_BENCH_MINFREE", "16")
    monkeypatch.setenv("MCPX_BENCH_WAIT", "0.05")
    # (MCPX_BENCH_SPECULATE_K was MCPX_BENCH_SPEC until the speculative-
    # decoding phase gate claimed that name.)
    monkeypatch.setenv("MCPX_BENCH_SPECULATE_K", "4")
    monkeypatch.setenv("MCPX_BENCH_DRAFT", "off")
    cfg = bench._build_config("test")
    e = cfg.engine
    assert (
        e.decode_steps_per_tick,
        e.pipeline_depth,
        e.admit_min_free,
        e.speculate_k,
        e.draft_mode,
    ) == (2, 3, 16, 4, "off")
    assert abs(e.admit_max_wait_s - 0.05) < 1e-9


def test_worker_lever_defaults_untouched(monkeypatch):
    for env in (
        "MCPX_BENCH_TICK",
        "MCPX_BENCH_DEPTH",
        "MCPX_BENCH_MINFREE",
        "MCPX_BENCH_WAIT",
        "MCPX_BENCH_SPECULATE_K",
        "MCPX_BENCH_DRAFT",
    ):
        monkeypatch.delenv(env, raising=False)
    from mcpx.core.config import EngineConfig

    cfg = bench._build_config("test")
    assert cfg.engine.decode_steps_per_tick == EngineConfig.decode_steps_per_tick
    assert cfg.engine.pipeline_depth == EngineConfig.pipeline_depth


def test_spec_headline_flip(monkeypatch):
    """MCPX_BENCH_SPEC_HEADLINE arms speculation for the headline phases
    AND implies hetero_batch (the grammar-aware drafter only runs in the
    heterogeneous slab); unset, both stay off for round comparability."""
    monkeypatch.delenv("MCPX_BENCH_HETERO", raising=False)
    monkeypatch.setenv("MCPX_BENCH_SPEC_HEADLINE", "1")
    cfg = bench._build_config("test")
    assert cfg.engine.speculative.enabled is True
    assert cfg.engine.hetero_batch is True
    monkeypatch.delenv("MCPX_BENCH_SPEC_HEADLINE", raising=False)
    cfg = bench._build_config("test")
    assert cfg.engine.speculative.enabled is False
    assert cfg.engine.hetero_batch is False


def test_fallback_kinds_scrape_is_kind_complete():
    """A NEW degradation kind minted in the planner shows up in the bench
    honesty field without a bench change; canonical kinds are explicit 0s."""
    prom = {
        'mcpx_grammar_fallbacks_total{kind="typed_off"}': 3.0,
        'mcpx_grammar_fallbacks_total{kind="shape_only"}': 1.0,
        'mcpx_grammar_fallbacks_total{kind="some_future_kind"}': 2.0,
        "mcpx_plans_total": 9.0,
    }
    out = {
        **{k: 0 for k in ("shape_only", "keys_free", "typed_off")},
        **bench._fallback_kinds(prom),
    }
    assert out == {
        "shape_only": 1.0,
        "keys_free": 0,
        "typed_off": 3.0,
        "some_future_kind": 2.0,
    }
