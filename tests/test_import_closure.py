"""The serving path's import closure: what a replica imports before its
first line of work is what serving reads.

``startup.import`` is the largest phase of a warm start in the smaller
configurations, and 45% of it was one optional library: ``orbax.checkpoint``
at ``models/gemma/params.py``'s module scope brought 782 of the process's
1,818 modules (``google.cloud.logging``, ``grpc``, ``tensorstore``, ...) into
a process that draws its weights. Each test is a fresh interpreter, because
this process has long since imported everything.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The serving entries: what ``benchmarks/chip/child.py`` and ``mcpx serve``
# import before they build an engine.
ENTRIES = ("mcpx.engine.engine", "mcpx.planner.llm", "mcpx.server.app", "mcpx.server.factory")
# The checkpoint library and what only it brings.
UNSERVED = ("orbax", "tensorstore", "grpc", "google.cloud.logging")
# 1,036 modules measured (PR 61; 1,818 on its parent) + 10%: jax's and
# aiohttp's own closures wander by a few modules with the environment, a
# library back at module scope brings hundreds.
MODULES_CEILING = 1140


def _fresh(script: str) -> dict:
    """Run ``script`` in a fresh interpreter from the repo's root and return
    the JSON object its last line prints."""
    done = subprocess.run(
        [sys.executable, "-c", script],
        cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


_PRELUDE = f"""
import json, sys
import {", ".join(ENTRIES)}

def held():
    return [m for m in {UNSERVED!r} if m in sys.modules]
"""


@pytest.fixture(scope="module")
def served():
    """The serving entries imported, then a start-up timeline built."""
    return _fresh(
        _PRELUDE
        + """
out = {"held": held(), "modules": len(sys.modules)}
from mcpx.telemetry.startup import StartupTimeline
timeline = StartupTimeline()
out["at_timeline"] = len(sys.modules)
out["snapshot"] = timeline.snapshot()
out["trace"] = timeline.trace()
print(json.dumps(out))
"""
    )


def test_serving_entries_leave_the_checkpoint_library_out(served):
    assert served["held"] == []
    assert served["modules"] < MODULES_CEILING


def test_startup_import_carries_the_module_count(served):
    (phase,) = [p for p in served["snapshot"]["phases"] if p["name"] == "startup.import"]
    assert phase["modules"] == served["at_timeline"]
    assert served["modules"] <= phase["modules"] < MODULES_CEILING
    (span,) = [s for s in served["trace"]["tree"] if s["name"] == "startup.import"]
    assert span["attrs"]["modules"] == phase["modules"]


def test_checkpoint_calls_import_orbax_and_round_trip(tmp_path):
    out = _fresh(
        _PRELUDE
        + f"""
import jax, numpy as np
from mcpx.models.gemma import GemmaConfig, init_params
from mcpx.models.gemma.params import load_or_init, save_checkpoint

cfg = GemmaConfig(dtype="float32")
params = init_params(cfg, jax.random.PRNGKey(7))
out = {{"before": held()}}
path = {str(tmp_path / "ckpt")!r}
save_checkpoint(path, params)
out["after_save"] = held()
restored, source = load_or_init(cfg, path)
out["source"] = source
out["same"] = all(
    bool(np.array_equal(np.asarray(a), np.asarray(b)))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored), strict=True)
)
print(json.dumps(out))
"""
    )
    assert out["before"] == []
    assert "orbax" in out["after_save"]
    assert out["source"] == "checkpoint"
    assert out["same"]


def test_a_missing_orbax_names_the_checkpoint_and_the_package(tmp_path, monkeypatch):
    from mcpx.core.errors import EngineError
    from mcpx.models.gemma import GemmaConfig
    from mcpx.models.gemma.params import load_checkpoint, save_checkpoint

    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)  # the import raises
    path = tmp_path / "ckpt"
    path.mkdir()
    with pytest.raises(EngineError, match=re.escape(str(path)) + ".*orbax-checkpoint"):
        load_checkpoint(str(path), GemmaConfig())
    with pytest.raises(EngineError, match="orbax-checkpoint"):
        save_checkpoint(str(path), {})
