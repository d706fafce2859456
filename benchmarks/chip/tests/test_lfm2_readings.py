"""The comparison that decides ``correct`` for ``lfm2-24b-a2b``, judged with
the limits as committed (``reference.tol``, ``lfm2.MARGIN``): a sound step
passes on every seed; the int8-weights control, a step that starts a radix
hit's row from ZEROS and not from its page's tail, and a step that keeps a
rejected slot's convolution input each come out NOT correct through
``reference.compare_with_engine_step`` itself. Each reading is appended to
``chiprun_out/lfm2_readings.jsonl``.

Where jax has a TPU this runs the cell's configuration at the slab's shape
(8 rows, 257 pages, the whole-prompt prefill at the 128 bucket, every second
row's suffix prefill from a page's tail, three decode windows through the
kernel): ``chiprun -- python -m pytest
benchmarks/chip/tests/test_lfm2_readings.py -q -s``. On the CPU it runs
the block's rehearsal size through the interpreted kernel (not a device
number). ``LFM2_SEEDS=a,b,...`` gives the sound step's seeds; the control
runs on the first.

The int8 control at the cell's size: the rounded copy of 10.28 GB of weights does
not fit beside them on a 16 GB chip, so the step and the reference run one
after the other. The weights are rounded IN PLACE (``reference.int8_rounded``
a leaf at a time, the leaf's buffer donated), the program's step runs on
them with its outputs and its routing kept, the tree is dropped and drawn
again from its seed, and ``compare_with_engine_step`` then compares the
reference on the sound weights with the kept outputs.
"""

import dataclasses
import json
import os
import sys
import time

import pytest

from conftest import CHIP_DIR, REPO

SEEDS = [int(s) for s in os.environ.get("LFM2_SEEDS", str(2**31 + 56)).split(",")]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import jax

    import reference
    import spec
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("lfm2")
    on_chip = jax.default_backend() == "tpu"
    if on_chip:
        with open(os.path.join(CHIP_DIR, "configs", "lfm2-24b-a2b.json")) as f:
            keys = spec.model_keys(json.load(f))
        cfg = block.model_config(keys, 3072)
        shape = dict(interpret=False, page_size=16, rows=8, pages_per_row=32, prefill_len=128, n_decode=3)
    else:
        cfg = block.rehearsal_config(3072)
        shape = dict(interpret=True, page_size=16, rows=4, pages_per_row=8, prefill_len=64, n_decode=3)
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
        with open(os.path.join(REPO, "chiprun_out", "lfm2_readings.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")

    return dict(block=block, reference=reference, dims=dims, state=state, draw=draw,
                compare=compare, note=note)


def _row(out, read, t0, **kw):
    return {**kw, "ok": out["ok"], "rms": out["rms_rel_err"], "max": out["max_rel_err"],
            "tol_rms": out["tol_rms"], "tol_max": out["tol_max"],
            "distance": max(r["distance"] for r in read),
            "flipped": sum(r["flipped"] for r in read), "checked": sum(r["checked"] for r in read),
            "s": round(time.time() - t0, 1)}


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_step_passes_under_the_limits_as_committed(bench, seed):
    block, t0 = bench["block"], time.time()
    out = bench["compare"](block, seed)
    read = block.routing_readings(bench["state"]["params"], bench["dims"])
    bench["note"](_row(out, read, t0, control="", seed=seed))
    assert out["ok"], out
    assert max(r["distance"] for r in read) <= block.MARGIN


@pytest.mark.parametrize("control, switch", [("zero_tail_at_hit", ("tail_at_hit", False)),
                                             ("keeps_rejected_slots", ("state_moves_by_the_window", True))])
def test_a_step_that_breaks_the_states_rule_comes_out_not_correct(bench, control, switch):
    block, t0 = bench["block"], time.time()
    key, value = switch
    sound, block.CONTROLS[key] = block.CONTROLS[key], value
    try:
        judged = bench["compare"](block, SEEDS[0])
        read = block.routing_readings(bench["state"]["params"], bench["dims"])
        margin, block.MARGIN = block.MARGIN, float("inf")
        try:  # the logits' own distance, with the routing limit out of the way
            out = bench["compare"](block, SEEDS[0])
        finally:
            block.MARGIN = margin
    finally:
        block.CONTROLS[key] = sound
    bench["note"]({**_row(out, read, t0, control=control, seed=SEEDS[0]), "ok": judged["ok"]})
    assert not judged["ok"], judged


class _Stepping:
    """The block with its step's outputs kept and a reference that costs
    nothing: the comparison's result is thrown away, its step is what runs."""

    def __init__(self, block):
        self.block, self.kept = block, []

    def step_functions(self, *args, **kw):
        import numpy as np

        prefill, decode = self.block.step_functions(*args, **kw)

        def keep(fn):
            def run(*a):
                logits, _pools = out = fn(*a)
                self.kept.append(np.asarray(logits))
                return out
            return run

        return keep(prefill), keep(decode)

    def reference_logits(self, params, dims, tokens):
        import jax.numpy as jnp

        return jnp.broadcast_to(jnp.arange(dims["vocab_size"], dtype=jnp.float32),
                                (tokens.shape[0], dims["vocab_size"]))


class _Replaying:
    """The block's reference against a step that hands back the kept outputs
    (and does NOT run, so the routing record stays the kept step's)."""

    def __init__(self, block, kept):
        self.reference_logits, self.kept = block.reference_logits, list(kept)

    def step_functions(self, *args, **kw):
        replay = lambda *a: (self.kept.pop(0), None)
        return replay, replay


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
