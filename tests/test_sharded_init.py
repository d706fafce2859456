"""The weights are drawn already sharded, and the bits do not know it.

``init_params`` creates every leaf with its ``param_pspecs`` sharding (one
jitted draw per leaf, ``out_shardings``, the divisor a runtime operand). A
plan's length is a constant of the weights, so the draw has to stay what it
was when it ran eagerly on one device: the oracle below is that draw, kept
here as it stood before PR 27. Fan-ins that are not powers of four (96, 192,
384) are where a divide folded into a multiply by a reciprocal shows.
"""

import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mcpx.core.config import MCPXConfig
from mcpx.engine.engine import InferenceEngine
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.params import bytes_per_device, load_or_init
from mcpx.models.gemma.quant import leaf_quantizer, quant_pspecs
from mcpx.parallel.mesh import make_mesh, param_pspecs

SHAPES = {
    "d96": GemmaConfig(vocab_size=512, d_model=96, n_layers=2, n_heads=4, n_kv_heads=4,
                       head_dim=32, d_ff=384),
    "d192": GemmaConfig(vocab_size=512, d_model=192, n_layers=3, n_heads=8, n_kv_heads=4,
                        head_dim=16, d_ff=384),
}
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}


def _mesh(name):
    data, model = MESHES[name]
    return make_mesh(data=data, model=model, devices=jax.devices()[: data * model])


def eager_draw(cfg, key, leaf_transform=None):
    """``init_params`` as it was before PR 27: eager, whole, on one device."""
    dtype = jnp.dtype(cfg.dtype)
    t = leaf_transform or (lambda _name, w: w)
    k_embed, k_q, k_k, k_v, k_o, k_gate, k_up, k_down = jax.random.split(key, 8)
    L, D, H, K, hd, F, V = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                            cfg.head_dim, cfg.d_ff, cfg.vocab_size)

    def normal(name, key, shape, fan_in):
        return t(name, (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype))

    return {
        "embed": normal("embed", k_embed, (V, D), D),
        "layers": {
            "pre_attn_norm": t("pre_attn_norm", jnp.zeros((L, D), dtype)),
            "pre_mlp_norm": t("pre_mlp_norm", jnp.zeros((L, D), dtype)),
            "wq": normal("wq", k_q, (L, D, H, hd), D),
            "wk": normal("wk", k_k, (L, D, K, hd), D),
            "wv": normal("wv", k_v, (L, D, K, hd), D),
            "wo": normal("wo", k_o, (L, H, hd, D), H * hd),
            "w_gate": normal("w_gate", k_gate, (L, D, F), D),
            "w_up": normal("w_up", k_up, (L, D, F), D),
            "w_down": normal("w_down", k_down, (L, F, D), F),
        },
        "final_norm": t("final_norm", jnp.zeros((D,), dtype)),
    }


def _bits(x):
    x = np.asarray(x)
    return x.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def assert_same_bits(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape, jax.tree_util.keystr(path)
        differing = int(np.sum(_bits(g) != _bits(w)))
        assert differing == 0, f"{jax.tree_util.keystr(path)}: {differing} of {g.size} values differ"


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_leaves_bit_identical_to_the_eager_draw(mesh_name, shape):
    cfg = SHAPES[shape]
    params, source = load_or_init(cfg, "", _mesh(mesh_name), seed=3)
    assert source == "random"
    assert_same_bits(params, eager_draw(cfg, jax.random.PRNGKey(3)))


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_int8_leaves_bit_identical_to_the_eager_draw(mesh_name):
    cfg = SHAPES["d192"]
    mesh = _mesh(mesh_name)
    params, _ = load_or_init(cfg, "", mesh, quantize="int8")
    assert_same_bits(params, eager_draw(cfg, jax.random.PRNGKey(0), leaf_quantizer))
    specs = quant_pspecs(cfg, mesh)
    for (path, leaf), spec in zip(jax.tree.leaves_with_path(params), jax.tree.leaves(specs)):
        assert leaf.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, spec), leaf.ndim
        ), jax.tree_util.keystr(path)


def test_no_mesh_draws_the_same_bits_on_the_default_device():
    cfg = SHAPES["d96"]
    params, _ = load_or_init(cfg, "")
    assert_same_bits(params, eager_draw(cfg, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("mesh_name", ["2x2", "1x4"])
def test_every_leaf_carries_its_pspec_and_no_shard_of_a_split_leaf_is_whole(mesh_name):
    cfg = SHAPES["d192"]
    mesh = _mesh(mesh_name)
    params, _ = load_or_init(cfg, "", mesh)
    specs = param_pspecs(cfg, mesh)
    split = 0
    for (path, leaf), spec in zip(jax.tree.leaves_with_path(params), jax.tree.leaves(specs)):
        name = jax.tree_util.keystr(path)
        assert leaf.sharding.mesh.shape == mesh.shape and leaf.sharding.spec == spec, name
        assert len(leaf.addressable_shards) == mesh.size, name
        if "model" in spec:
            split += 1
            n = mesh.shape["model"]
            assert all(s.data.size * n == leaf.size for s in leaf.addressable_shards), name
    assert split == 8  # embed, wq, wk, wv, wo, w_gate, w_up, w_down


def _engine_cfg(data, model):
    return MCPXConfig.from_dict({
        "model": {"size": "test", "max_seq_len": 256},
        "engine": {"use_pallas": False, "max_batch_size": 4, "max_decode_len": 16,
                   "kv_page_size": 16, "max_pages_per_seq": 4, "temperature": 0.0,
                   "data_axis": data, "model_axis": model},
    })


def _started(cfg, model_cfg, mesh, check):
    async def go():
        eng = InferenceEngine(cfg, model_cfg=model_cfg, mesh=mesh)
        await eng.start()
        try:
            return check(eng)
        finally:
            await eng.aclose()

    return asyncio.run(go())


def test_pools_are_created_sharded_not_moved(monkeypatch):
    """``_init_pools`` builds the pools under ``jit`` with ``out_shardings``:
    what ``init_paged_kv`` returns is traced, so no device ever holds a
    whole pool that is then moved; each chip gets its KV heads' pages."""
    import mcpx.engine.engine as engine_mod

    seen = []
    real = engine_mod.init_paged_kv

    def spy(*args, **kwargs):
        pools = real(*args, **kwargs)
        seen.append(all(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(pools)))
        return pools

    monkeypatch.setattr(engine_mod, "init_paged_kv", spy)
    cfg = SHAPES["d96"]  # 4 KV heads: 2 a chip over model 2

    def check(eng):
        assert seen and all(seen)
        for pool in jax.tree.leaves(eng._paged_kv):
            assert pool.sharding.spec == P("model", None, None, None, None)
            assert {s.data.shape[0] for s in pool.addressable_shards} == {cfg.n_kv_heads // 2}
            assert len(pool.addressable_shards) == 4
        return True

    assert _started(_engine_cfg(2, 2), cfg, _mesh("2x2"), check)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_placement_gauges_and_queue_stats_sum_to_the_tree(mesh_name):
    cfg = SHAPES["d96"]
    mesh = _mesh(mesh_name)
    data, model = MESHES[mesh_name]

    def check(eng):
        tree_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(eng._params))
        specs = jax.tree.leaves(param_pspecs(cfg, mesh))
        a_chip = sum(
            leaf.nbytes // (model if "model" in spec else 1)
            for leaf, spec in zip(jax.tree.leaves(eng._params), specs)
        )
        held = bytes_per_device(eng._params)
        assert len(held) == mesh.size and set(held.values()) == {a_chip}
        if mesh.size == 1:
            assert a_chip == tree_bytes
        qs = eng.queue_stats()
        assert qs["mesh"] == {"data": data, "model": model}
        assert qs["weights"]["source"] == "random" and qs["weights"]["init_s"] > 0
        assert qs["weights"]["bytes_per_device"] == held
        text = eng.metrics.render().decode()
        assert "mcpx_engine_weights_init_seconds " in text
        for dev, n_bytes in held.items():
            assert f'mcpx_engine_weights_bytes{{device="{dev}"}} {float(n_bytes)}' in text
        return True

    assert _started(_engine_cfg(data, model), cfg, mesh, check)


def test_a_cold_engine_reports_no_placement():
    eng = InferenceEngine(_engine_cfg(1, 1))
    qs = eng.queue_stats()
    assert "weights" not in qs and "mesh" not in qs
