"""The thin bridge from tier-1 to the chip harness (PERF.md Open question 12).

The comparison that decides a benchmark run's ``correct``
(``benchmarks/chip/reference.py::compare_with_engine_step``) driven from
here, through the ``gemma`` block module, at ``model=test`` size with 4 KV
heads, so that on the 2 x 2 mesh KV heads split over ``model`` and rows over
``data``: the arm of ``_ragged_kernel_on_mesh`` and ``_write_kv_window`` that
the four-chip cell ``mistral-7b.distinct-closed`` takes. The harness is
imported by path, never copied. CPU, interpreted kernel: a correctness
reading, not a device number.
"""

import dataclasses
import importlib.util
import os
import sys

import jax
import pytest

from mcpx.models.gemma.params import load_or_init
from mcpx.parallel.mesh import make_mesh

CHIP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "chip")


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
