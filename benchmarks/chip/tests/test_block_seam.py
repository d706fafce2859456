"""The seam a configuration arrives through: its block module
(``models/<module>.py``), found by name, gives the program's model config, the
rehearsal's, the plain reference, the kernel paths ``correct`` requires and,
optionally, the program's step. CPU only; not a device number."""

import dataclasses
import json
import os
import sys
import types

import pytest

import reference
import spec
from conftest import CHIP_DIR, REPO

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

# What child.py's gemma_dims gave for the two committed configurations before
# it moved into models/gemma.py (PR 23-25), field for field.
DIMS = {
    "olmo2-1b": dict(vocab_size=3072, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=16,
                     head_dim=128, d_ff=8192, rope_theta=500000.0, norm_eps=1e-06,
                     max_seq_len=4096, dtype="bfloat16"),
    "mistral-7b-1chip": dict(vocab_size=3072, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
                             head_dim=128, d_ff=14336, rope_theta=10000.0, norm_eps=1e-05,
                             max_seq_len=32768, dtype="bfloat16"),
}


def config(name):
    with open(os.path.join(CHIP_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gemma():
    sys.path.insert(0, REPO)
    return spec.load_block("gemma")


@pytest.mark.parametrize("name", sorted(DIMS))
def test_both_configurations_map_to_the_fields_they_always_had(gemma, name):
    cfg = config(name)
    assert cfg["module"] == "gemma"
    model_cfg = gemma.model_config(spec.model_keys(cfg), 3072)
    got = dataclasses.asdict(model_cfg)
    assert {k: got[k] for k in DIMS[name]} == DIMS[name]
    assert list(gemma.gemma_dims(spec.model_keys(cfg), 3072).items()) == list(DIMS[name].items())
    assert type(got["rope_theta"]) is float and type(got["norm_eps"]) is float
    # every key of the file is the harness's or the block's: none is dropped
    assert set(spec.model_keys(cfg)) | spec.HARNESS_KEYS >= set(cfg)
    assert gemma.kernel_paths == {"decode": 1, "prefill": 0}
    assert gemma.rehearsal_config(3072).vocab_size == 3072


@pytest.mark.parametrize("extra", [
    {"num_local_experts": 64}, {"sliding_window": 4096}, {"hidden_act": "silu"},
    {"tie_word_embeddings": False}, {"kv_lora_rank": 512}, {"vocab_size": 32000},
])
def test_a_key_the_block_does_not_consume_is_an_error_not_a_silent_drop(gemma, extra):
    keys = {**spec.model_keys(config("mistral-7b-1chip")), **extra}
    with pytest.raises(ValueError):
        gemma.model_config(keys, 3072)


def block_dir(tmp_path, name, body):
    (tmp_path / "models").mkdir(exist_ok=True)
    (tmp_path / "models" / f"{name}.py").write_text(body)
    return str(tmp_path)


GOOD = ("kernel_paths = {'decode': 1}\n"
        "def model_config(c, v): return c\n"
        "def rehearsal_config(v): return v\n"
        "def reference_logits(p, d, t): return t\n")


def test_a_block_module_is_a_name_a_file_and_four_parts(tmp_path):
    d = block_dir(tmp_path, "fine", GOOD)
    assert spec.load_block("fine", d).kernel_paths == {"decode": 1}
    for bad in ("../gemma", "models/gemma", "os.path", "", None, 7, "a b"):
        with pytest.raises(spec.SpecError):
            spec.load_block(bad, d)
    with pytest.raises(spec.SpecError, match="has: \\['fine'\\]"):
        spec.load_block("absent", d)
    with pytest.raises(spec.SpecError, match="reference_logits"):
        spec.load_block("short", block_dir(tmp_path, "short", GOOD.replace("reference_logits", "other")))
    for not_paths in ("('decode',)", "{}", "{'decode': -1}"):
        with pytest.raises(spec.SpecError, match="kernel_paths"):
            spec.load_block("paths", block_dir(tmp_path, "paths", GOOD.replace("{'decode': 1}", not_paths)))


def test_a_configuration_without_a_module_is_refused(tree):
    root = tree(cell={"name": "odd.distinct-closed", "config": "odd", "traffic": "distinct-closed",
                      "chips": 1, "why": "test"},
                config=("odd", {**config("olmo2-1b"), "name": "odd"}))
    assert spec.load_cell("odd.distinct-closed", root).config["module"] == "gemma"
    for change in (lambda c: c.pop("module"), lambda c: c.update(module="no-such-block"),
                   lambda c: c.update(module="../gemma")):
        cfg = {**config("olmo2-1b"), "name": "odd"}
        change(cfg)
        with open(os.path.join(root, "benchmarks/chip/configs/odd.json"), "w") as f:
            json.dump(cfg, f)
        with pytest.raises(spec.SpecError, match="module"):
            spec.load_cell("odd.distinct-closed", root)


def test_the_tolerance_follows_the_depth_and_is_what_it_was_at_16():
    assert reference.tol(16) == (0.02, 0.12)  # exactly: both cells' verdicts do not move
    rms, mx = reference.tol(32)
    assert rms == pytest.approx(0.02 * 2 ** 0.5) and mx == pytest.approx(0.12 * 2 ** 0.5)
    assert round(rms, 4) == 0.0283 and round(mx, 3) == 0.170
    assert reference.tol(64) == pytest.approx((0.04, 0.24))
    # under 16 layers no chip reading exists and the CPU's lie above the
    # square-root law (2 layers: 0.0075 against 0.0071): 16's tolerance holds
    assert reference.tol(8) == reference.tol(2) == (0.02, 0.12)


# ------------------------------------------------------ a block through the seam
@pytest.fixture(scope="module")
def compare(gemma):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from mcpx.models.gemma.params import init_params

    cfg = gemma.rehearsal_config(3072)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def run(block, **kw):
        return reference.compare_with_engine_step(
            block, params, cfg, dataclasses.asdict(cfg), mesh, seed=2**31 + 9, interpret=True,
            page_size=16, rows=2, pages_per_row=32, prefill_len=128, n_decode=2, **kw)

    return run


def test_a_new_block_passes_through_the_seam_and_a_wrong_reference_cannot_hide(gemma, compare):
    probe = spec.load_block("probe", TESTS_DIR)  # its own reference AND its own step
    plain, through = compare(gemma), compare(probe)
    assert plain["ok"] and through["ok"] and through["positions"] == 6
    assert through["n_layers"] == gemma.rehearsal_config(3072).n_layers
    assert (through["tol_rms"], through["tol_max"]) == reference.tol(through["n_layers"])
    # the probe's step with the reference's half of the change left out ...
    wrong_ref = types.SimpleNamespace(**{**vars(probe), "reference_logits": gemma.reference_logits})
    out = compare(wrong_ref)
    assert not out["ok"] and out["rms_rel_err"] > 10 * through["tol_rms"]
    # ... and its reference against the program's unchanged step
    wrong_step = types.SimpleNamespace(**{k: v for k, v in vars(probe).items() if k != "step_functions"})
    out = compare(wrong_step)
    assert not out["ok"] and out["rms_rel_err"] > 10 * through["tol_rms"]
