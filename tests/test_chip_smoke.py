"""Guards that keep the chip bring-up from rotting, all on CPU:
``chip_smoke.py``'s parent stays off jax, its checks refuse everything that
is not "an LLM-authored valid plan served by the compiled kernel on a TPU",
the script's CPU rehearsal runs end to end, and the persistent compilation
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to the one fixed
in-checkout directory."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (stdlib-only at module level)
from chip_smoke import SmokeFailure  # noqa: E402


# ------------------------------------------------------- one process on jax
def test_parent_never_imports_jax():
    """Building every input the parent builds (config, registry, intents —
    the long one tokenizes with the BPE vocab) and checking a plan leaves
    jax unimported: the child is the one process that touches the chip."""
    code = (
        "import sys, chip_smoke\n"
        "recs = chip_smoke.build_registry(1)\n"
        "chip_smoke.build_config(False); chip_smoke.build_intents(recs)\n"
        "chip_smoke.build_long_intent(recs)\n"
        "g = {'nodes': [{'name': 'a', 'service': recs[0].name}], 'edges': []}\n"
        "chip_smoke.check_plan(200, {'origin': 'llm', 'graph': g},"
        " {recs[0].name}, 'x')\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True, timeout=120)


def test_smoke_config_is_the_2b_default_engine():
    cfg = chip_smoke.build_config(rehearsal=False)
    assert cfg["model"] == {"size": "2b", "vocab": "bpe"}
    # Only warm-up and greedy decode are set; every other engine field —
    # the warm-up bucket set, the kernel route — is EngineConfig's default.
    assert cfg["engine"] == {"warmup_compile": True, "temperature": 0.0}
    reh = chip_smoke.build_config(rehearsal=True)
    assert reh["model"]["size"] == "test" and reh["engine"]["interpret"] is True


def test_intents_are_distinct_and_follow_ups_share_a_head():
    recs = chip_smoke.build_registry(1)
    burst, follow = chip_smoke.build_intents(recs)
    assert len(burst) >= 16 and len(set(burst + follow)) == len(burst) + len(follow)
    for a, b in zip(burst, follow):
        assert a.rsplit(" for case ", 1)[0] == b.rsplit(" for case ", 1)[0]


# ------------------------------------------------------------------ checks
def _health(**over):
    body = {
        "status": "ok",
        "engine": "ready",
        "started": True,
        "engine_queue": {
            "pallas": {
                "enabled": True,
                "interpret": False,
                "reason": None,
                "paths": {
                    "decode": {"engaged": True, "dispatches": 9, "reason": None},
                    "prefill": {"engaged": True, "dispatches": 2, "reason": None},
                },
            }
        },
    }
    body.update(over)
    return body


def test_health_fails_at_once_on_engine_error_whatever_the_status():
    assert chip_smoke.check_health(_health()) is True
    assert chip_smoke.check_health(_health(engine="warming")) is False
    # engine ready but the registry grammar not yet warm: not ready.
    assert chip_smoke.check_health(_health(started=False)) is False
    with pytest.raises(SmokeFailure, match="engine_error"):
        chip_smoke.check_health(
            _health(engine="failed", engine_error="XlaRuntimeError: RESOURCE_EXHAUSTED")
        )
    with pytest.raises(SmokeFailure, match="warm_error"):
        # a failed grammar warm on a ready, serving engine
        chip_smoke.check_health(_health(warm_error="PlannerError: warm failed"))
    with pytest.raises(SmokeFailure, match="failed"):
        chip_smoke.check_health(_health(engine="failed"))


def test_plan_check_fails_on_anything_but_a_valid_llm_plan():
    names = {"auth-fetch-0000", "user-score-0001"}
    graph = {
        "nodes": [
            {"name": "a", "service": "auth-fetch-0000"},
            {"name": "b", "service": "user-score-0001"},
        ],
        "edges": [{"from": "a", "to": "b"}],
    }
    ok = {"origin": "llm", "graph": graph}
    chip_smoke.check_plan(200, ok, names, "p")
    with pytest.raises(SmokeFailure, match="HTTP 422"):
        chip_smoke.check_plan(422, {"error": "planning failed"}, names, "p")
    with pytest.raises(SmokeFailure, match="origin 'heuristic'"):
        chip_smoke.check_plan(200, {**ok, "origin": "heuristic"}, names, "p")
    with pytest.raises(SmokeFailure, match="degraded"):
        chip_smoke.check_plan(200, {**ok, "planner": "degraded"}, names, "p")
    with pytest.raises(SmokeFailure, match="not in the registry"):
        chip_smoke.check_plan(200, ok, {"auth-fetch-0000"}, "p")
    cyclic = {**graph, "edges": [{"from": "a", "to": "b"}, {"from": "b", "to": "a"}]}
    with pytest.raises(SmokeFailure, match="does not validate"):
        chip_smoke.check_plan(200, {"origin": "llm", "graph": cyclic}, names, "p")
    with pytest.raises(SmokeFailure):
        chip_smoke.check_plan(200, {"origin": "llm", "graph": {"nodes": [], "edges": []}}, names, "p")


def test_kernel_check_wants_the_compiled_kernel_dispatched_on_both_paths():
    assert chip_smoke.check_kernel(_health(), rehearsal=False) == {
        "decode": 9, "prefill": 2,
    }

    def with_pallas(**over):
        h = _health()
        h["engine_queue"]["pallas"].update(over)
        return h

    with pytest.raises(SmokeFailure, match="not enabled"):
        chip_smoke.check_kernel(
            with_pallas(enabled=False, reason="engine.use_pallas=false"), False
        )
    with pytest.raises(SmokeFailure, match="interpret"):
        chip_smoke.check_kernel(with_pallas(interpret=True), False)
    with pytest.raises(SmokeFailure, match="interpret"):
        # ...and a rehearsal that is NOT interpreting is not a rehearsal.
        chip_smoke.check_kernel(_health(), rehearsal=True)
    for path in ("decode", "prefill"):
        h = _health()
        h["engine_queue"]["pallas"]["paths"][path]["dispatches"] = 0
        with pytest.raises(SmokeFailure, match=path):
            chip_smoke.check_kernel(h, False)
    with pytest.raises(SmokeFailure, match="no engine_queue.pallas"):
        chip_smoke.check_kernel({"engine": "ready"}, False)


_PROM = (
    "# HELP mcpx_engine_compiles_total x\n"
    'mcpx_engine_compiles_total{{executable="prefill"}} {a}\n'
    'mcpx_engine_compiles_total{{executable="segment"}} 2.0\n'
    "mcpx_engine_compiles_total_created 1.7e9\n"
    "mcpx_engine_resets_total {r}\n"
)


def test_metrics_check_wants_no_reset_and_no_compile_after_readiness():
    before = _PROM.format(a="12.0", r="0.0")
    assert chip_smoke.check_metrics(before, before) == {"compiles": 14.0, "resets": 0.0}
    with pytest.raises(SmokeFailure, match="compiled after readiness"):
        chip_smoke.check_metrics(before, _PROM.format(a="13.0", r="0.0"))
    with pytest.raises(SmokeFailure, match="resets_total"):
        chip_smoke.check_metrics(before, _PROM.format(a="12.0", r="1.0"))


def _costs(platform="tpu", n=1, mesh=None, bytes_in_use=5 << 30):
    return {
        "device": {
            "peaks": {"platform": platform, "device_kind": "TPU v5 lite", "n_devices": n},
            "mesh": mesh or {"data": n, "model": 1},
            "compilation_cache_dir": "/x/.jax_cache",
            "hbm": [
                {"device": f"d{i}", "bytes_in_use": bytes_in_use, "peak_bytes_in_use": bytes_in_use}
                for i in range(n)
            ],
        }
    }


def test_device_check_refuses_anything_but_a_tpu_with_every_device_in_use():
    dev = chip_smoke.check_device(_costs(), rehearsal=False)
    assert (dev["platform"], dev["kind"], dev["count"]) == ("tpu", "TPU v5 lite", 1)
    chip_smoke.check_device(_costs(n=4, mesh={"data": 2, "model": 2}), False)
    with pytest.raises(SmokeFailure, match="platform 'cpu'"):
        chip_smoke.check_device(_costs(platform="cpu"), rehearsal=False)
    with pytest.raises(SmokeFailure, match="platform 'tpu'"):
        # a rehearsal is never what ran because a chip happened to be there
        chip_smoke.check_device(_costs(), rehearsal=True)
    with pytest.raises(SmokeFailure, match="does not cover"):
        chip_smoke.check_device(_costs(n=4, mesh={"data": 1, "model": 2}), False)
    idle = _costs(n=4, mesh={"data": 2, "model": 2})
    idle["device"]["hbm"][3]["bytes_in_use"] = 1 << 20
    with pytest.raises(SmokeFailure, match="trivial bytes_in_use"):
        chip_smoke.check_device(idle, False)


@pytest.mark.slow  # ~2 min of XLA:CPU compiles and interpreted kernels: not tier-1 budget
def test_rehearsal_runs_end_to_end_and_says_it_is_one():
    """The whole script — child server, warm-up, requests, scrapes, teardown
    — on the CPU backend at model=test with the kernel interpreted. Its
    verdict line is marked a rehearsal and names the CPU. (On every PR the
    driver runs the real thing on the chip; this is the builder's dry run.)"""
    # One CPU device, not conftest's eight: the mesh is the dry run's job
    # (test_graft_entry), and one device is half the wall time.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {
        "rehearsal": True, "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert "REHEARSAL" in proc.stdout


# --------------------------------------------------------- compile cache
def test_compile_cache_env_set_means_no_directory_is_set_in_code(monkeypatch):
    import jax

    from mcpx.utils import backend

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.enable_compilation_cache() == "/somewhere/else"
    assert calls == []


def test_compile_cache_unset_means_the_fixed_in_checkout_directory(monkeypatch):
    import jax

    from mcpx.utils import backend

    calls = []
    made = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setattr(backend.os, "makedirs", lambda p, **k: made.append(p))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    # The CPU backend (these tests) gets no cache and sets nothing...
    assert backend.enable_compilation_cache() is None and calls == []
    # ...an accelerator gets the one fixed path inside the checkout.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.enable_compilation_cache() == fixed
    assert calls == [("jax_compilation_cache_dir", fixed)] and made == [fixed]
