"""The comparison that decides ``correct`` for ``jamba2-3b``, judged with the
limits as committed (``reference.tol``, ``jamba.STATE_COARSE``): a sound step
passes on every seed, and each control comes out NOT correct through
``reference.compare_with_engine_step`` itself: a rejected slot's token left in
the state (the state moved by the window and not by what the row kept), the
pending commit applied twice, the recurrent state through bfloat16 (the
precision below the float32 the configuration states for it: the logits do not
see it, ``jamba.STATE_COARSE`` does), the mixer's four products on operands
rounded once to bfloat16 (the precision below the float32 the configuration
states BETWEEN the mixer's matrices; on every seed), and the int8-weights
control. Each
reading is appended to ``chiprun_out/jamba_readings.jsonl``.

Where jax has a TPU this runs the cell's configuration, all 28 layers, at the
slab's shape and the timed sizes (8 rows, 128 pages a row, prefill at the 1,024
bucket through ``selective_scan_prefill``, three decode windows of 8 slots with
uneven live widths through ``selective_scan_window`` and the ragged kernel at
ONE KV head): ``chiprun -- python -m pytest
benchmarks/chip/tests/test_jamba_readings.py -q -s``. On the CPU it runs the
block's rehearsal size through the interpreted kernels (not a device number).
``JAMBA_SEEDS=a,b,...`` gives the sound step's seeds; the controls run on the
first, the int8 one with the weights rounded in place as
``test_trinity_readings.py`` does and for its reason."""

import dataclasses
import json
import os
import sys
import time

import pytest

from conftest import CHIP_DIR, REPO
from test_trinity_readings import _Replaying, _Stepping

SEEDS = [int(s) for s in os.environ.get("JAMBA_SEEDS", str(2**31 + 58)).split(",")]
CONTROLS = [c for c in os.environ.get(
    "JAMBA_CONTROLS", "state_in_bfloat16,mixer_in_bfloat16,state_moves_by_the_window,pending_commit_twice"
).split(",") if c]
# (the mixer's precision control is read on EVERY seed: its reading is the one
# nearest the limit)
CASES = [(c, s) for c in CONTROLS for s in (SEEDS if c == "mixer_in_bfloat16" else SEEDS[:1])]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import jax

    import reference
    import spec
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("jamba")
    if jax.default_backend() == "tpu":
        with open(os.path.join(CHIP_DIR, "configs", "jamba2-3b.json")) as f:
            keys = spec.model_keys(json.load(f))
        cfg = block.model_config(keys, 3072)
        shape = dict(interpret=False, page_size=16, rows=8, pages_per_row=128, prefill_len=1024, n_decode=3)
    else:
        cfg = block.rehearsal_config(3072)
        shape = dict(interpret=True, page_size=16, rows=4, pages_per_row=4, prefill_len=48, n_decode=3)
    dims = dataclasses.asdict(cfg)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    draw = lambda: jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(0)))
    state = {"params": draw()}

    def compare(blk, seed):
        return reference.compare_with_engine_step(blk, state["params"], cfg, dims, mesh, seed=seed, **shape)

    def note(out, t0, **row):
        coarse = block.state_readings()
        row = {"device": jax.devices()[0].device_kind, "n_layers": cfg.n_layers, **row, "ok": out["ok"],
               "rms": out["rms_rel_err"], "max": out["max_rel_err"], "tol_rms": out["tol_rms"],
               "tol_max": out["tol_max"], "state_coarse": [min(coarse), max(coarse)],
               "s": round(time.time() - t0, 1)}
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "jamba_readings.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        return coarse

    return dict(block=block, reference=reference, dims=dims, state=state, draw=draw, compare=compare, note=note)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_step_passes_under_the_limits_as_committed(bench, seed):
    t0 = time.time()
    out = bench["compare"](bench["block"], seed)
    coarse = bench["note"](out, t0, control="", seed=seed, prompt_lens=out["prompt_lens"])
    assert out["ok"], out
    assert 0 < max(coarse) <= bench["block"].STATE_COARSE


@pytest.mark.parametrize("control, seed", CASES)
def test_a_control_of_the_state_comes_out_not_correct(bench, control, seed):
    block, t0 = bench["block"], time.time()
    block.CONTROLS[control] = True
    try:
        out = bench["compare"](block, seed)
    finally:
        block.CONTROLS[control] = False
    coarse = block.state_readings()
    failed = {"rms": out["rms_rel_err"] > out["tol_rms"], "max": out["max_rel_err"] > out["tol_max"],
              "state_coarse": max(coarse) > block.STATE_COARSE}
    bench["note"](out, t0, control=control, seed=seed, fails=sorted(k for k, v in failed.items() if v))
    if control == "mixer_in_bfloat16" and out["n_layers"] < 28:
        pytest.skip("judged at the cell's depth, on the chip: 8 layers read about the limit")
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
    out = bench["compare"](_Replaying(block, stepping.kept), seed)
    bench["note"](out, t0, control="int8-weights", seed=seed)
    assert not out["ok"], out
    assert out["rms_rel_err"] > out["tol_rms"] or out["max_rel_err"] > out["tol_max"]
