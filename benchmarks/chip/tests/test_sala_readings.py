"""The comparison that decides ``correct`` for ``minicpm-sala``, judged with
the limits as committed (``reference.tol``, ``sala.SELECTION_MARGIN``,
``sala.STATE_COARSE``): a sound step passes on every seed, and each control
comes out NOT correct through ``reference.compare_with_engine_step`` itself:
a step that reads the wrong blocks (only a query's own block forced), the state
moved by the window's live slots and not by the token kept, the recurrent
state kept in bfloat16 (the precision below the float32 the configuration
states for it), and the int8-weights control. Each reading is appended to
``chiprun_out/sala_readings.jsonl``.

Where jax has a TPU this runs the cell's configuration at the slab's shape
and the timed sizes (8 rows, 1,024 pages a row, prefill to 13,312 in chunks of
1,024 through the suffix route, three decode windows of 8 slots with uneven
live widths through the state pool's kernel, the block-score kernel and the
gathering attention): ``chiprun -- python -m pytest
benchmarks/chip/tests/test_sala_readings.py -q -s``. On the CPU it runs the
block's rehearsal size through the interpreted kernels (not a device number).
``SALA_SEEDS=a,b,...`` gives the sound step's seeds; the controls run on the
first."""

import dataclasses
import json
import os
import sys
import time

import pytest

from conftest import CHIP_DIR, REPO

SEEDS = [int(s) for s in os.environ.get("SALA_SEEDS", str(2**31 + 51)).split(",")]
CONTROLS = [c for c in os.environ.get(
    "SALA_CONTROLS", "wrong_blocks,state_moves_by_the_window,state_in_bfloat16,int8-weights").split(",") if c]


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import jax

    import reference
    import spec
    from mcpx.models.gemma.model import init_params
    from mcpx.parallel.mesh import make_mesh

    block = spec.load_block("sala")
    if jax.default_backend() == "tpu":
        with open(os.path.join(CHIP_DIR, "configs", "minicpm-sala.json")) as f:
            config = json.load(f)
        cfg = block.model_config(spec.model_keys(config), 3072)
        shape = dict(interpret=False, page_size=16, rows=config["max_batch_size"],
                     pages_per_row=config["max_pages_per_seq"], prefill_len=config["warmup_max_len"])
    else:
        cfg = block.rehearsal_config(3072)
        shape = dict(interpret=True, page_size=16, rows=4, pages_per_row=40, prefill_len=512)
    dims = dataclasses.asdict(cfg)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(0)))

    def read(control, seed):
        """One comparison -> the row of its readings."""
        for k in block.CONTROLS:
            block.CONTROLS[k] = k == "follow_step_selection"
        if control and control != "int8-weights":
            block.CONTROLS[control] = True
        t0 = time.time()
        try:
            out = reference.compare_with_engine_step(
                block, params, cfg, dims, mesh, seed=seed, **shape,
                control=control if control == "int8-weights" else "")
            seconds = time.time() - t0
            coarse = block.state_readings()
            selection = block.selection_readings(params, dims)
            judged = out
            if not out["ok"] and out["rms_rel_err"] >= 1e29:
                # a row that breaks a limit of the block's reads NaN: the logits' own
                # distance is read once more with those limits out of the way
                margin, state = block.SELECTION_MARGIN, block.STATE_COARSE
                block.SELECTION_MARGIN = block.STATE_COARSE = float("inf")
                try:
                    out = reference.compare_with_engine_step(
                        block, params, cfg, dims, mesh, seed=seed, **shape,
                        control=control if control == "int8-weights" else "")
                finally:
                    block.SELECTION_MARGIN, block.STATE_COARSE = margin, state
        finally:
            for k in block.CONTROLS:
                block.CONTROLS[k] = k == "follow_step_selection"
        checked = sum(r["selection_checked"] for r in selection)
        row = {
            "device": jax.devices()[0].device_kind, "n_layers": cfg.n_layers, "control": control, "seed": seed,
            "ok": judged["ok"], "rms_rel_err": out["rms_rel_err"], "max_rel_err": out["max_rel_err"],
            "tol_rms": out["tol_rms"], "tol_max": out["tol_max"], "positions": out["positions"],
            "prompt_lens": out["prompt_lens"], "seconds": round(seconds, 1),
            "selection_distance": max(r["selection_distance"] for r in selection),
            "selection_flip_share": sum(r["selection_flipped"] for r in selection) / max(checked, 1),
            "selection_checked": checked, "selection_margin": block.SELECTION_MARGIN,
            "state_coarse": [min(coarse), max(coarse)],
        }
        row["fails"] = sorted(k for k, v in {
            "rms": out["rms_rel_err"] > out["tol_rms"], "max": out["max_rel_err"] > out["tol_max"],
            "selection": row["selection_distance"] > block.SELECTION_MARGIN,
            "state_coarse": max(coarse) > block.STATE_COARSE}.items() if v)
        print(json.dumps(row), flush=True)
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "sala_readings.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        return row

    return dict(block=block, read=read)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_step_passes_under_the_limits_as_committed(bench, seed):
    row = bench["read"]("", seed)
    assert row["ok"] and not row["fails"], row
    assert 0 < row["state_coarse"][1] <= bench["block"].STATE_COARSE


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_comes_out_not_correct(bench, control):
    row = bench["read"](control, SEEDS[0])
    assert not row["ok"] and row["fails"], row
    if control == "wrong_blocks":
        assert "selection" in row["fails"]
    if control == "state_in_bfloat16":
        assert row["fails"] == ["state_coarse"] or "state_coarse" in row["fails"]
