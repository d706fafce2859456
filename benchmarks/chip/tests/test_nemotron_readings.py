"""The comparison that decides ``correct`` for ``nemotron-3-super``, judged
with the limits as committed (``reference.tol``, ``nemotron_h.MARGIN``): a
sound step passes on every seed, and each control comes out NOT correct
through ``reference.compare_with_engine_step`` itself: the state moved by the
window and not by what the row kept, the recurrent state kept in bfloat16
(the precision below the float32 the configuration states for it: the logits
do not see it, ``nemotron_h.STATE_COARSE`` does), and the int8-weights
control. Each reading is appended to ``chiprun_out/nemotron_readings.jsonl``.

Where jax has a TPU this runs the cell's configuration at the slab's shape
and the timed sizes (8 rows, 32 pages a row, prefill at the 128 bucket, three
decode windows of 8 slots with uneven live widths through ``ssm_window`` and
the ragged kernel): ``chiprun -- python -m pytest
benchmarks/chip/tests/test_nemotron_readings.py -q -s``. On the CPU it runs
the block's rehearsal size through the interpreted kernels (not a device
number). ``NEMOTRON_SEEDS=a,b,...`` gives the sound step's seeds; the
controls run on the first, the int8 one with the weights rounded in place as
``test_trinity_readings.py`` does and for its reason."""

import dataclasses
import json
import os
import sys
import time

import pytest

from conftest import CHIP_DIR, REPO
from test_trinity_readings import _Replaying, _Stepping, _row

SEEDS = [int(s) for s in os.environ.get("NEMOTRON_SEEDS", str(2**31 + 48)).split(",")]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import jax

    import reference
    import spec
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("nemotron_h")
    if jax.default_backend() == "tpu":
        with open(os.path.join(CHIP_DIR, "configs", "nemotron-3-super.json")) as f:
            keys = spec.model_keys(json.load(f))
        cfg = block.model_config(keys, 3072)
        shape = dict(interpret=False, page_size=16, rows=8, pages_per_row=32, prefill_len=128, n_decode=3)
    else:
        cfg = block.rehearsal_config(3072)
        shape = dict(interpret=True, page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3)
    dims = dataclasses.asdict(cfg)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    draw = lambda: jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(0)))
    state = {"params": draw()}

    def compare(blk, seed):
        return reference.compare_with_engine_step(
            blk, state["params"], cfg, dims, mesh, seed=seed, **shape)

    def note(row):
        row = {"device": jax.devices()[0].device_kind, "experts_held": cfg.n_experts_held,
               "n_layers": cfg.n_layers, "margin": block.MARGIN, **row}
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "nemotron_readings.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")

    return dict(block=block, reference=reference, dims=dims, state=state, draw=draw,
                compare=compare, note=note)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_step_passes_under_the_limits_as_committed(bench, seed):
    block, t0 = bench["block"], time.time()
    out = bench["compare"](block, seed)
    read = block.routing_readings(bench["state"]["params"], bench["dims"])
    coarse = block.state_readings()
    bench["note"]({**_row(out, read, t0, control="", seed=seed, prompt_lens=out["prompt_lens"]),
                   "state_coarse": [min(coarse), max(coarse)]})
    assert out["ok"], out
    assert max(r["distance"] for r in read) <= block.MARGIN
    assert 0 < max(coarse) <= block.STATE_COARSE


@pytest.mark.parametrize("control", ["state_in_bfloat16", "state_moves_by_the_window"])
def test_a_control_of_the_state_comes_out_not_correct(bench, control):
    """The state rounded to bfloat16 after every forward; the state moved by
    the window's live slots and not by the one token the row kept."""
    block, t0, seed = bench["block"], time.time(), SEEDS[0]
    block.CONTROLS[control] = True
    try:
        out = bench["compare"](block, seed)
        read = block.routing_readings(bench["state"]["params"], bench["dims"])
        coarse = block.state_readings()
    finally:
        block.CONTROLS[control] = False
    failed = {"rms": out["rms_rel_err"] > out["tol_rms"], "max": out["max_rel_err"] > out["tol_max"],
              "routing": max(r["distance"] for r in read) > block.MARGIN,
              "state_coarse": max(coarse) > block.STATE_COARSE}
    bench["note"]({**_row(out, read, t0, control=control, seed=seed),
                   "state_coarse": [min(coarse), max(coarse)],
                   "fails": sorted(k for k, v in failed.items() if v)})
    assert not out["ok"], out
    assert failed["state_coarse"] == (control == "state_in_bfloat16")


def test_the_int8_control_comes_out_not_correct(bench):
    import jax

    block, reference, state, seed = bench["block"], bench["reference"], bench["state"], SEEDS[0]
    t0 = time.time()
    # round in place: a leaf at a time, each into the buffer it came from
    rounded = jax.jit(lambda w: reference.int8_rounded({"w": w})["w"], donate_argnums=0)
    leaves, tree = jax.tree.flatten(state["params"])
    state["params"] = None
    for i in range(len(leaves)):
        leaves[i] = jax.block_until_ready(rounded(leaves[i]))
    state["params"] = jax.tree.unflatten(tree, leaves)
    stepping = _Stepping(block)
    bench["compare"](stepping, seed)
    del leaves
    for leaf in jax.tree.leaves(state.pop("params")):
        leaf.delete()
    state["params"] = bench["draw"]()  # the sound weights again, from their seed
    judged = bench["compare"](_Replaying(block, stepping.kept), seed)
    # The readings behind the verdict: a row that breaks the routing limit reads NaN, so the
    # logits' own distance is read once more with that limit out of the way.
    read = block.routing_readings(state["params"], bench["dims"])
    margin, block.MARGIN = block.MARGIN, float("inf")
    try:
        out = bench["compare"](_Replaying(block, stepping.kept), seed)
    finally:
        block.MARGIN = margin
    failed = {"rms": out["rms_rel_err"] > out["tol_rms"], "max": out["max_rel_err"] > out["tol_max"],
              "routing": max(r["distance"] for r in read) > margin}
    bench["note"]({**_row(out, read, t0, control="int8-weights", seed=seed), "ok": judged["ok"],
                   "fails": sorted(k for k, v in failed.items() if v)})
    assert not judged["ok"], judged
    assert any(failed.values()), failed
