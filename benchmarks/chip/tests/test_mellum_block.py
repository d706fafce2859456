"""The Mellum block module at its rehearsal size on the CPU (interpreted
kernel): under the step's routing the program's step passes the comparison
that decides ``correct``; without the routing record it does not (the flip);
with wrong experts forced it does not; on int8-rounded weights it does not.
And the published keys reach the program's config. Not a device number."""

import dataclasses
import json
import os
import sys

import pytest

from conftest import CHIP_DIR, REPO


@pytest.fixture(scope="module")
def compare():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import reference
    import spec
    from mcpx.models.gemma.params import init_params

    block = spec.load_block("mellum")
    cfg = block.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def run(**kw):
        return reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 5, interpret=True,
            page_size=16, rows=2, pages_per_row=32, prefill_len=128, n_decode=2, **kw)

    return run, block, lambda: block.routing_readings(params, dataclasses.asdict(cfg))


def test_step_passes_under_its_own_routing_and_checks_every_position(compare):
    run, block, readings = compare
    out = run()
    assert out["ok"] and out["positions"] == 6, out
    # one reading a row; every position the step ran was checked
    read = readings()
    assert len(read) == 2
    assert sum(r["checked"] for r in read) == 4 * (sum(out["prompt_lens"]) + 2 * 2)
    assert max(r["distance"] for r in read) < block.DELTA
    assert sum(r["flipped"] for r in read) > 0  # the two sides do choose differently


@pytest.mark.parametrize("control", ["follow_step_routing", "wrong_experts"])
def test_controls_fail(compare, control):
    run, block, _ = compare
    saved = dict(block.CONTROLS)
    block.CONTROLS[control] = not saved[control]
    try:
        out = run()
    finally:
        block.CONTROLS.update(saved)
    assert not out["ok"], out


def test_lower_precision_fails(compare):
    run, _, _ = compare
    assert not run(control="int8-weights")["ok"]


def test_published_keys_reach_the_config():
    sys.path.insert(0, REPO)
    import spec

    block = spec.load_block("mellum")
    with open(os.path.join(CHIP_DIR, "configs", "mellum2-12b-a2.5b.json")) as f:
        config = json.load(f)
    cfg = block.model_config(spec.model_keys(config), 3072)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert, cfg.n_experts_held) == (64, 8, 896, 64)
    assert cfg.n_layers == 12 and cfg.layer_types == (("sliding_attention",) * 3 + ("full_attention",)) * 3
    assert cfg.sliding_window == 1024 and cfg.yarn_factor == 16 and cfg.rope_theta == 500000
    assert round(cfg.n_params / 1e9, 2) == 5.03
    with pytest.raises(ValueError, match="consumed by nothing"):
        block.model_config({**spec.model_keys(config), "qk_norm": True}, 3072)
