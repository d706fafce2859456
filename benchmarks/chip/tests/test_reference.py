"""The comparison that decides ``correct``, at model=test on the CPU
(interpreted kernel): the plain step passes, a step on int8-rounded weights
and a step that yields NaN do not. Not a device number."""

import dataclasses
import os
import sys

import pytest

from conftest import REPO


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

    block = spec.load_block("gemma")
    cfg = block.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def run(p=params, **kw):
        return reference.compare_with_engine_step(
            block, p, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 5, interpret=True,
            page_size=16, rows=2, pages_per_row=32, prefill_len=128, n_decode=2, **kw)

    return run, params


def test_plain_step_passes_and_lower_precision_fails(compare):
    run, _ = compare
    plain, control = run(), run(control="int8-weights")
    assert plain["ok"] and plain["positions"] == 6
    assert plain["rms_rel_err"] < plain["max_rel_err"] < 0.04
    assert not control["ok"] and control["rms_rel_err"] > 3 * plain["rms_rel_err"]


def test_a_nan_never_passes(compare):
    import jax

    run, params = compare
    bad = jax.tree.map(lambda w: w * float("nan") if w.ndim >= 2 else w, params)
    out = run(bad)
    assert not out["ok"] and out["max_rel_err"] > 1e6 and out["rms_rel_err"] > 1e6
