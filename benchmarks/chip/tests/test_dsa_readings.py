"""The comparison that decides ``correct`` for ``deepseek-v3.2-exp``, judged
with the limits as committed (``reference.tol``, ``dsa.MARGIN``,
``dsa.SELECTION_MARGIN``): a sound step passes on every seed, and BOTH
controls come out NOT correct through ``reference.compare_with_engine_step``
itself: the weights rounded to int8, and the step with its selection left out
(every key a query sees attended). Each reading is appended to
``chiprun_out/dsa_readings.jsonl``.

Where jax has a TPU this runs the cell's configuration at the slab's shape and
the timed sizes (8 rows, 512 pages a row, contexts to 7,168 prefilled a row at
a time in chunks of 1,024 through the two kernels, three decoded positions):
``chiprun -- python -m pytest benchmarks/chip/tests/test_dsa_readings.py -q
-s`` (``DSA_SEEDS=a,b,...`` gives the sound step's seeds; the controls run on
the first). On the CPU it runs the block's rehearsal size through the
interpreted kernels (not a device number). The int8 control rounds the
weights in place, as ``test_axk1_readings.py`` does and for its reason (two
copies of 10.8 GB fit no chip)."""

import dataclasses
import json
import os
import sys
import time

import pytest

from conftest import CHIP_DIR, REPO
from test_trinity_readings import _Replaying, _Stepping

SEEDS = [int(s) for s in os.environ.get("DSA_SEEDS", str(2**31 + 44)).split(",")]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import jax

    import reference
    import spec
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("dsa")
    if jax.default_backend() == "tpu":
        with open(os.path.join(CHIP_DIR, "configs", "deepseek-v3.2-exp.json")) as f:
            keys = spec.model_keys(json.load(f))
        cfg = block.model_config(keys, 3072)
        shape = dict(interpret=False, page_size=16, rows=8, pages_per_row=512, prefill_len=7168, n_decode=3)
    else:
        cfg = block.rehearsal_config(3072)
        block.CHUNK = 48  # chunk edges on both sides of the rehearsal's 32nd key
        shape = dict(interpret=True, page_size=16, rows=4, pages_per_row=8, prefill_len=96, n_decode=3)
    dims = dataclasses.asdict(cfg)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    draw = lambda: jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(0)))
    state = {"params": draw()}

    def compare(blk, seed):
        return reference.compare_with_engine_step(
            blk, state["params"], cfg, dims, mesh, seed=seed, **shape)

    def note(row):
        row = {"device": jax.devices()[0].device_kind, "experts_held": cfg.n_experts_held,
               "n_layers": cfg.n_layers, "margin": block.MARGIN,
               "selection_margin": block.SELECTION_MARGIN, **row}
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "dsa_readings.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")

    return dict(block=block, reference=reference, dims=dims, state=state, draw=draw,
                compare=compare, note=note)


def _row(out, read, t0, **kw):
    return {**kw, "ok": out["ok"], "rms": out["rms_rel_err"], "max": out["max_rel_err"],
            "tol_rms": out["tol_rms"], "tol_max": out["tol_max"],
            "distance": max(r["distance"] for r in read),
            "flipped": sum(r["flipped"] for r in read), "checked": sum(r["checked"] for r in read),
            "selection_distance": max(r["selection_distance"] for r in read),
            "selection_flipped": sum(r["selection_flipped"] for r in read),
            "selection_checked": sum(r["selection_checked"] for r in read),
            "prompt_lens": out.get("prompt_lens"), "s": round(time.time() - t0, 1)}


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_step_passes_under_the_limits_as_committed(bench, seed):
    block, t0 = bench["block"], time.time()
    out = bench["compare"](block, seed)
    compare_s = round(time.time() - t0, 1)
    read = block.routing_readings(bench["state"]["params"], bench["dims"])
    bench["note"]({**_row(out, read, t0, control="", seed=seed), "compare_s": compare_s})
    assert out["ok"], out
    assert max(r["distance"] for r in read) <= block.MARGIN
    assert max(r["selection_distance"] for r in read) <= block.SELECTION_MARGIN


def _judged_without_the_limits(bench, kept, seed):
    """The verdict with the limits as committed, then the readings behind it:
    a row that breaks a routing or a selection limit reads NaN, so the logits'
    own distance is read once more with those limits out of the way."""
    block = bench["block"]
    judged = bench["compare"](_Replaying(block, kept), seed)
    read = block.routing_readings(bench["state"]["params"], bench["dims"])
    margins = block.MARGIN, block.SELECTION_MARGIN
    block.MARGIN = block.SELECTION_MARGIN = float("inf")
    try:
        out = bench["compare"](_Replaying(block, kept), seed)
    finally:
        block.MARGIN, block.SELECTION_MARGIN = margins
    failed = {"rms": out["rms_rel_err"] > out["tol_rms"], "max": out["max_rel_err"] > out["tol_max"],
              "routing": max(r["distance"] for r in read) > margins[0],
              "selection": max(r["selection_distance"] for r in read) > margins[1]}
    return judged, out, read, failed


def test_the_step_with_its_selection_left_out_comes_out_not_correct(bench):
    """Every key a query sees attended: the sound weights, the program's own
    kernels, one part of the mathematics missing."""
    block, seed, t0 = bench["block"], SEEDS[0], time.time()
    stepping = _Stepping(block)
    block.CONTROLS["step_selects"] = False
    try:
        bench["compare"](stepping, seed)
    finally:
        block.CONTROLS["step_selects"] = True
    judged, out, read, failed = _judged_without_the_limits(bench, stepping.kept, seed)
    bench["note"]({**_row(out, read, t0, control="selection-left-out", seed=seed), "ok": judged["ok"],
                   "fails": sorted(k for k, v in failed.items() if v)})
    assert not judged["ok"], judged
    assert failed["selection"], failed


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
    judged, out, read, failed = _judged_without_the_limits(bench, stepping.kept, seed)
    bench["note"]({**_row(out, read, t0, control="int8-weights", seed=seed), "ok": judged["ok"],
                   "fails": sorted(k for k, v in failed.items() if v)})
    assert not judged["ok"], judged
    assert any(failed.values()), failed
