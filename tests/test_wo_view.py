"""``wo`` is stored ``[n, H, dv, D]`` and scanned as ``[n, H * dv, D]``: the
view is taken of the whole stack, outside the layer scan
(``model.layer_stacks``), so the body's ``btf,fd->btd`` takes its layer's
slice with no reshape between (PR 46). The stored tree, its specs and the
arithmetic are what they were: every forward equals, bit for bit on the CPU,
the parent's (the stored slice reshaped inside the body) and, to float32
rounding, a projection written per head on the stored leaf, as the
benchmark's plain references write it."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

import mcpx.engine.paged_decode as paged
import mcpx.models.gemma.model as model
from mcpx.engine.kv_cache import init_paged_kv
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import decode_step, init_kv_cache, init_params, layer_stacks, prefill
from mcpx.models.gemma.quant import dequant_params, quant_pspecs, quantize_params
from mcpx.parallel.mesh import make_mesh, param_pspecs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")

_DENSE = dict(
    vocab_size=384, d_model=64, n_layers=3, n_heads=4, head_dim=16, d_ff=128, max_seq_len=32, dtype="float32"
)
_LATENT = dict(
    _DENSE, n_kv_heads=1, attention="latent", q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=8,
    v_head_dim=32, yarn_factor=40.0, yarn_original_max_pos=16, attn_score_factor=1.8739,
    activation="silu", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
)
KINDS = {
    "mha": dict(_DENSE, n_kv_heads=4),
    "gqa-4:1": dict(_DENSE, n_kv_heads=1),
    "gated-qk-normed": dict(
        _DENSE, n_kv_heads=2, qk_norm=True, attn_gate=True, post_norms=True, norm_plus_one=False,
        activation="silu", tie_embeddings=False,
    ),
    "latent": _LATENT,
    # a dense lead layer (a stack of its own) before two sparse ones, an index over the 8 best keys
    "latent-index-dense-lead": dict(
        _LATENT, index_n_heads=4, index_head_dim=32, index_topk=8, n_experts=8, n_experts_per_tok=2,
        d_expert=32, n_dense_layers=1, d_shared_expert=32, router_scoring="sigmoid", router_scale=2.5,
        router_bias_scale=0.1,
    ),
}


def _wo_width(cfg):
    return cfg.n_heads, cfg.attn_out_width // cfg.n_heads, cfg.d_model


def _stored_stacks(cfg, params):
    names = ("dense_layers", "layers") if cfg.n_dense_layers else ("layers",)
    return [params[name] for name in names]


def _residual_on_the_stored_leaf(per_head):
    """``attention_residual`` on the layer's STORED ``[H, dv, D]`` slice, two
    ways: the parent's (the slice reshaped to ``[F, D]`` inside the scan body,
    then ``btf,fd->btd``), or per head as the references write it
    (``the,hed->td`` with a batch in front)."""

    def residual(x, h, attn, lp, cfg):
        H, dv, D = _wo_width(cfg)
        B, T, _ = attn.shape
        if cfg.attn_gate:
            gate = jnp.einsum("btd,df->btf", h, lp["w_attn_gate"].reshape(D, H * dv))
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)
        assert lp["wo"].shape == (H, dv, D)
        f32 = jnp.float32 if cfg.branches_float32 else None
        if per_head:
            out = jnp.einsum("bthe,hed->btd", attn.reshape(B, T, H, dv), lp["wo"], preferred_element_type=f32)
        else:
            out = jnp.einsum("btf,fd->btd", attn, lp["wo"].reshape(H * dv, D), preferred_element_type=f32)
        if cfg.post_norms:
            return model._add_normed(x, out, lp["post_attn_norm"], cfg)
        return model._join(x, out)

    return residual


def _three_forwards(cfg, params):
    """Logits of the dense forward (one ``decode_step`` after a prefill), the
    prefill and a paged window over empty pages, each jitted."""
    B, T, psz = 2, 24, 8
    seq = jnp.asarray(np.random.default_rng(7).integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([T, T - 5], jnp.int32)
    whole, cache = jax.jit(lambda p: prefill(p, cfg, seq, lens, init_kv_cache(cfg, B, cfg.max_seq_len)))(params)
    step, _ = jax.jit(lambda p, c: decode_step(p, cfg, seq[:, 0], lens, c))(params, cache)
    table = 1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    window, _ = jax.jit(
        lambda p: decode_chunk_paged(
            p, cfg, seq, jnp.zeros((B,), jnp.int32), table, init_paged_kv(cfg, B * 4 + 1, psz),
            use_pallas=False, mesh=mesh, q_lens=lens,
        )
    )(params)
    return {"prefill": whole, "decode_step": step, "paged_window": window}


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_scan_gets_the_merged_view_and_the_forwards_equal_a_per_head_projection(kind, monkeypatch):
    cfg = GemmaConfig(**KINDS[kind])
    params = init_params(cfg, jax.random.PRNGKey(0))
    H, dv, D = _wo_width(cfg)
    stored = _stored_stacks(cfg, params)
    stacks, _ = layer_stacks(cfg, params)
    assert len(stacks) == len(stored) == (2 if cfg.n_dense_layers else 1)
    for tree, (scanned, lo, hi) in zip(stored, stacks):
        assert tree["wo"].shape == (hi - lo, H, dv, D)  # the tree is what it was
        assert scanned["wo"].shape == (hi - lo, H * dv, D)
        np.testing.assert_array_equal(scanned["wo"], tree["wo"].reshape(hi - lo, H * dv, D))  # H-major
        assert {k: v.shape for k, v in scanned.items() if k != "wo"} == {
            k: tree[k].shape for k in scanned if k != "wo"
        }
    got = _three_forwards(cfg, params)
    # the oracles scan the stored leaf as it is
    monkeypatch.setattr(model, "_merge_heads", lambda stack: stack)
    for per_head in (False, True):
        monkeypatch.setattr(model, "attention_residual", _residual_on_the_stored_leaf(per_head))
        monkeypatch.setattr(paged, "attention_residual", _residual_on_the_stored_leaf(per_head))
        want = _three_forwards(cfg, params)
        for name in want:
            assert np.isfinite(np.asarray(want[name])).all(), name
            if per_head:
                # another order of the same float32 sums (XLA's CPU dot over two
                # contracted dimensions is not its dot over their product)
                np.testing.assert_allclose(got[name], want[name], rtol=1e-4, atol=2e-5, err_msg=name)
            else:
                np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(want[name]), err_msg=name)


@pytest.mark.parametrize("kind", ["mha", "latent"])
def test_a_head_sharded_wo_is_merged_with_no_collective_of_its_own(kind):
    """Two CPU devices on ``model``: ``wo`` is split by head, the merged axis
    stays split (H-major), and the jitted forward's HLO moves ``wo`` through
    no all-gather, collective-permute or all-to-all: the only collective
    after the projection is the partial sums' all-reduce."""
    cfg = GemmaConfig(**KINDS[kind])
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    specs = param_pspecs(cfg, mesh)
    assert specs["layers"]["wo"][1] == "model"
    sharded = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs)
    B, T = 2, 16
    seq = jnp.asarray(np.random.default_rng(3).integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    lens = jnp.asarray([T, T - 3], jnp.int32)
    run = jax.jit(lambda p: prefill(p, cfg, seq, lens, init_kv_cache(cfg, B, cfg.max_seq_len))[0])
    text = run.lower(sharded).compile().as_text()
    H, dv, D = _wo_width(cfg)
    n = cfg.n_layers
    wo_shapes = {
        f"[{lead}{h},{dv},{D}]" for lead in ("", "1,", f"{n},") for h in (H, H // 2)
    } | {f"[{lead}{f},{D}]" for lead in ("", "1,", f"{n},") for f in (H * dv, H * dv // 2)}
    moved = [
        line.strip()[:200] for line in text.splitlines()
        if any(op in line for op in (" all-gather", " collective-permute", " all-to-all"))
        and any(s in line for s in wo_shapes)
    ]
    assert not moved, moved
    assert " all-reduce" in text  # the model axis is in use: the branch's partial sums are summed
    np.testing.assert_allclose(
        np.asarray(run(sharded), np.float32), np.asarray(run(params), np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("kind", ["mha", "gqa-4:1"])
def test_an_int8_tree_goes_through_the_view_as_its_dequantised_tree_does(kind, monkeypatch):
    """``quant.py`` keeps ``wo`` [n, H, hd, D] with a scale [n, 1, 1, D]; the
    view merges both, and the per-layer dequant inside the scan gives the
    parent's result (the stored slice dequantised, then reshaped in the body)
    bit for bit, and the whole tree's dequantised first to float32 rounding."""
    cfg = GemmaConfig(**KINDS[kind])
    q = quantize_params(init_params(cfg, jax.random.PRNGKey(0)))
    H, dv, D = _wo_width(cfg)
    assert q["layers"]["wo"]["int8"].shape == (cfg.n_layers, H, dv, D)
    assert q["layers"]["wo"]["scale"].shape == (cfg.n_layers, 1, 1, D)
    [(scanned, _, _)], _ = layer_stacks(cfg, q)
    assert scanned["wo"]["int8"].shape == (cfg.n_layers, H * dv, D)
    assert scanned["wo"]["scale"].shape == (cfg.n_layers, 1, D)
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    assert quant_pspecs(cfg, mesh)["layers"]["wo"]["int8"] == param_pspecs(cfg, mesh)["layers"]["wo"]
    got = _three_forwards(cfg, q)
    whole = _three_forwards(cfg, dequant_params(q, jnp.dtype(cfg.dtype)))
    monkeypatch.setattr(model, "_merge_heads", lambda stack: stack)
    monkeypatch.setattr(model, "attention_residual", _residual_on_the_stored_leaf(per_head=False))
    monkeypatch.setattr(paged, "attention_residual", _residual_on_the_stored_leaf(per_head=False))
    parent = _three_forwards(cfg, q)
    for name in parent:
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(parent[name]), err_msg=name)
        # the same numbers multiplied in another fusion
        np.testing.assert_allclose(got[name], whole[name], rtol=1e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize(
    "config", sorted(f[:-5] for f in os.listdir(os.path.join(CHIP_DIR, "configs")) if f.endswith(".json"))
)
def test_at_every_cells_widths_the_tree_keeps_its_heads_and_the_scan_merges_them(config):
    """Shapes alone (nothing is drawn): the benchmark's references read
    ``engine._params`` and contract ``wo`` per head, so the leaf stays 4-D."""
    import importlib.util

    harness = importlib.util.spec_from_file_location("chip_harness_spec_wo_t", os.path.join(CHIP_DIR, "spec.py"))
    spec = importlib.util.module_from_spec(harness)
    sys.modules[harness.name] = spec
    harness.loader.exec_module(spec)
    with open(os.path.join(CHIP_DIR, "configs", config + ".json")) as f:
        file = json.load(f)
    block = spec.import_file(spec.block_file(file["module"], CHIP_DIR), "chip_block_wo_t_")
    cfg = block.model_config(spec.model_keys(file), 3072)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    H, dv, D = _wo_width(cfg)
    if cfg.hybrid:
        # A layer_pattern model walks its stacks without a scan, and its
        # attention leaves are STORED with the heads merged on the matmul's own
        # axis (its reference reads them so): a row of the stack feeds its dot.
        stacks = {"attn_layers": cfg.n_attn_layers}
        if cfg.mixer_ffn:  # both mixers' leaves, heads merged alike
            stacks = {"block_layers": cfg.n_block_layers, "linear_layers": cfg.n_linear_layers}
        for name, n in stacks.items():
            assert shapes[name]["wo"].shape == (n, H * dv, D)
            assert shapes[name]["wq"].shape == (n, D, H * dv)
        return
    runs = jax.eval_shape(lambda p: [scanned for scanned, _, _ in layer_stacks(cfg, p)[0]], shapes)
    stored = _stored_stacks(cfg, shapes)
    assert len(runs) == len(stored)
    for tree, scanned in zip(stored, runs):
        n = tree["wo"].shape[0]
        assert tree["wo"].shape == (n, H, dv, D)
        assert scanned["wo"].shape == (n, H * dv, D)
