import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError


def test_defaults_validate():
    MCPXConfig().validate()


def test_from_dict_and_unknown_key():
    cfg = MCPXConfig.from_dict({"engine": {"max_batch_size": 8}})
    assert cfg.engine.max_batch_size == 8
    with pytest.raises(ConfigError, match="unknown key"):
        MCPXConfig.from_dict({"engine": {"nope": 1}})


def test_env_overrides():
    cfg = MCPXConfig.from_env(
        {
            "MCPX_ENGINE_MAX_BATCH_SIZE": "16",
            "MCPX_ENGINE_USE_PALLAS": "false",
            "MCPX_ENGINE_TEMPERATURE": "0.7",
            "REDIS_URL": "redis://x:6379/0",
        }
    )
    assert cfg.engine.max_batch_size == 16
    assert cfg.engine.use_pallas is False
    assert cfg.engine.temperature == 0.7
    assert cfg.registry.redis_url == "redis://x:6379/0"


def test_invalid_page_size_rejected():
    with pytest.raises(ConfigError, match="power of two"):
        MCPXConfig.from_dict({"engine": {"kv_page_size": 13}})


def test_decode_budget_bounded_by_the_distance_table():
    """The budget mask compares the remaining budget with an int16 table
    saturated at ``DIST_SUCC_MAX``: exact only for budgets up to it."""
    from mcpx.planner.grammar import DIST_SUCC_MAX

    MCPXConfig.from_dict({"engine": {"max_decode_len": DIST_SUCC_MAX}})
    with pytest.raises(ConfigError, match=f"max_decode_len must be <= {DIST_SUCC_MAX}"):
        MCPXConfig.from_dict({"engine": {"max_decode_len": DIST_SUCC_MAX + 1}})


def test_invalid_planner_kind_rejected():
    with pytest.raises(ConfigError, match="planner.kind"):
        MCPXConfig.from_dict({"planner": {"kind": "oracle"}})


def test_steps_per_dispatch_roundtrip_and_bounds():
    """Fused multi-step dispatch knob (ISSUE 15): round-trips like every
    engine field, 1 = legacy per-tick cadence is legal, and out-of-range
    windows are rejected (not clamped silently)."""
    cfg = MCPXConfig.from_dict({"engine": {"steps_per_dispatch": 8}})
    assert cfg.engine.steps_per_dispatch == 8
    assert cfg.to_dict()["engine"]["steps_per_dispatch"] == 8
    MCPXConfig.from_dict({"engine": {"steps_per_dispatch": 1}}).validate()
    with pytest.raises(ConfigError, match="steps_per_dispatch"):
        MCPXConfig.from_dict({"engine": {"steps_per_dispatch": 0}})
    with pytest.raises(ConfigError, match="steps_per_dispatch"):
        MCPXConfig.from_dict({"engine": {"steps_per_dispatch": 65}})


def test_nested_speculative_from_dict_roundtrip():
    """engine.speculative is a NESTED dataclass: dict loading reaches one
    level deeper with the same key checking and string coercion, and
    to_dict round-trips it."""
    cfg = MCPXConfig.from_dict(
        {"engine": {"speculative": {"enabled": "true", "k": "6", "draft": "grammar"}}}
    )
    assert cfg.engine.speculative.enabled is True
    assert cfg.engine.speculative.k == 6
    assert cfg.engine.speculative.draft == "grammar"
    assert cfg.to_dict()["engine"]["speculative"] == {
        "enabled": True,
        "k": 6,
        "draft": "grammar",
    }
    with pytest.raises(ConfigError, match="engine.speculative.nope"):
        MCPXConfig.from_dict({"engine": {"speculative": {"nope": 1}}})
    # The natural YAML/JSON mistake `speculative: true` (the enable flag
    # lives INSIDE the nested object) must fail as a ConfigError at load,
    # not an AttributeError later in validate().
    with pytest.raises(ConfigError, match="engine.speculative.*object"):
        MCPXConfig.from_dict({"engine": {"speculative": True}})


def test_nested_speculative_env_overrides():
    cfg = MCPXConfig.from_env(
        {
            "MCPX_ENGINE_SPECULATIVE_ENABLED": "1",
            "MCPX_ENGINE_SPECULATIVE_K": "3",
        }
    )
    assert cfg.engine.speculative.enabled is True
    assert cfg.engine.speculative.k == 3
    assert cfg.engine.speculative.draft == "recurrent"  # untouched default


def test_invalid_speculative_rejected():
    with pytest.raises(ConfigError, match="speculative.k"):
        MCPXConfig.from_dict({"engine": {"speculative": {"k": 0}}})
    # Upper bound guards the drafter's float32 closed-form state advance
    # (2^i per window position overflows past ~127 and NaNs acceptance).
    with pytest.raises(ConfigError, match="speculative.k"):
        MCPXConfig.from_dict({"engine": {"speculative": {"k": 128}}})
    with pytest.raises(ConfigError, match="speculative.draft"):
        MCPXConfig.from_dict({"engine": {"speculative": {"draft": "oracle"}}})


def test_ledger_and_slo_config_roundtrip():
    """ISSUE 14 satellite: telemetry.ledger.* (nested) and the slo
    section load with key checking + string coercion, survive a to_dict
    round-trip, and validate their knobs."""
    cfg = MCPXConfig.from_dict(
        {
            "telemetry": {"ledger": {"enabled": "true", "max_tenants": "8"}},
            "slo": {
                "enabled": True,
                "bucket_s": "5",
                "windows_s": [10.0, 60.0, 120.0, 240.0],
                "objectives": [
                    {"name": "p99", "kind": "latency", "target": 0.95,
                     "threshold_ms": 250.0},
                ],
            },
            "scheduler": {"enabled": True, "burn_aware": True},
        }
    )
    assert cfg.telemetry.ledger.enabled is True
    assert cfg.telemetry.ledger.max_tenants == 8
    assert cfg.slo.bucket_s == 5.0
    round2 = MCPXConfig.from_dict(cfg.to_dict())
    assert round2.slo.objectives == cfg.slo.objectives
    assert round2.telemetry.ledger.max_tenants == 8
    assert round2.scheduler.burn_aware is True
    # Env override reaches the nested ledger section.
    env_cfg = MCPXConfig.from_env({"MCPX_TELEMETRY_LEDGER_ENABLED": "1"})
    assert env_cfg.telemetry.ledger.enabled is True
    # Unknown nested key fails at load.
    with pytest.raises(ConfigError, match="telemetry.ledger.nope"):
        MCPXConfig.from_dict({"telemetry": {"ledger": {"nope": 1}}})


def test_invalid_slo_rejected():
    with pytest.raises(ConfigError, match="objectives\\[0\\].kind"):
        MCPXConfig.from_dict(
            {"slo": {"objectives": [{"name": "x", "kind": "vibes",
                                     "target": 0.9}]}}
        )
    with pytest.raises(ConfigError, match="target"):
        MCPXConfig.from_dict(
            {"slo": {"objectives": [{"name": "x", "kind": "availability",
                                     "target": 1.5}]}}
        )
    with pytest.raises(ConfigError, match="threshold_ms"):
        MCPXConfig.from_dict(
            {"slo": {"objectives": [{"name": "x", "kind": "latency",
                                     "target": 0.9}]}}
        )
    with pytest.raises(ConfigError, match="windows_s"):
        MCPXConfig.from_dict({"slo": {"windows_s": [300.0]}})
    with pytest.raises(ConfigError, match="windows_s"):
        MCPXConfig.from_dict({"slo": {"windows_s": [300.0, 60.0]}})
    # burn_aware without the SLO engine is a wiring error, not a no-op.
    with pytest.raises(ConfigError, match="burn_aware"):
        MCPXConfig.from_dict(
            {"scheduler": {"enabled": True, "burn_aware": True}}
        )
