"""Distributed-without-a-cluster tests (SURVEY.md §4.3): 8 virtual CPU
devices; sharded execution must match single-device execution bit-for-bit
(same math, different layout)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mcpx.models.gemma import (
    GemmaConfig,
    decode_step,
    init_kv_cache,
    init_params,
    prefill,
)
from mcpx.parallel import (
    data_pspec,
    kv_cache_pspecs,
    make_mesh,
    param_pspecs,
    shard_pytree,
)


@pytest.fixture(scope="module")
def cfg():
    # d_ff=256 and n_heads=4 shard over model=4; batch 4 shards over data=2.
    return GemmaConfig(dtype="float32", max_seq_len=32)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def test_mesh_axes():
    mesh = make_mesh(data=2, model=4)
    assert mesh.shape == {"data": 2, "model": 4}


def test_mesh_too_big_raises():
    from mcpx.core.errors import ConfigError

    with pytest.raises(ConfigError, match="needs 16 devices"):
        make_mesh(data=4, model=4)


def test_param_shardings_applied(cfg, params):
    mesh = make_mesh(data=2, model=4)
    specs = param_pspecs(cfg, mesh)
    sharded = shard_pytree(params, specs, mesh)
    # n_heads=4 over model=4: wq sharded on the head axis.
    wq = sharded["layers"]["wq"]
    assert wq.sharding.spec == P(None, None, "model", None)
    # n_kv_heads=1 cannot shard over model=4: replicated.
    assert sharded["layers"]["wk"].sharding.spec == P(None, None, None, None)
    # MLP hidden dim sharded.
    assert sharded["layers"]["w_gate"].sharding.spec == P(None, None, "model")


def test_tp_dp_logits_match_single_device(cfg, params):
    B, T, S = 4, 6, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 256)
    seq_lens = jnp.full((B,), T)

    # Single device reference.
    ref_logits, ref_cache = jax.jit(prefill, static_argnums=1)(
        params, cfg, tokens, seq_lens, init_kv_cache(cfg, B, S)
    )

    # 2x4 mesh: DP over batch, TP over heads/ffn.
    mesh = make_mesh(data=2, model=4)
    sp = shard_pytree(params, param_pspecs(cfg, mesh), mesh)
    cache = shard_pytree(
        init_kv_cache(cfg, B, S), kv_cache_pspecs(cfg, mesh, B), mesh
    )
    dspec = data_pspec(mesh, B)
    st = jax.device_put(tokens, NamedSharding(mesh, P(*dspec, None)))
    sl = jax.device_put(seq_lens, NamedSharding(mesh, dspec))
    logits, new_cache = jax.jit(prefill, static_argnums=1)(sp, cfg, st, sl, cache)

    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=1e-5, atol=1e-5
    )

    # Decode one step on both and compare.
    next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    idx = jnp.full((B,), T)
    ref_step, _ = jax.jit(decode_step, static_argnums=1)(
        params, cfg, next_tok, idx, ref_cache
    )
    step, _ = jax.jit(decode_step, static_argnums=1)(sp, cfg, next_tok, idx, new_cache)
    np.testing.assert_allclose(np.asarray(step), np.asarray(ref_step), rtol=1e-5, atol=1e-5)


def test_pure_tp_8(cfg, params):
    """model=8: d_ff=256 and vocab=384 shard; heads(4) and kv(1) replicate."""
    B, T, S = 2, 5, 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 256)
    seq_lens = jnp.full((B,), T)
    ref, _ = jax.jit(prefill, static_argnums=1)(
        params, cfg, tokens, seq_lens, init_kv_cache(cfg, B, S)
    )
    mesh = make_mesh(data=1, model=8)
    sp = shard_pytree(params, param_pspecs(cfg, mesh), mesh)
    cache = shard_pytree(init_kv_cache(cfg, B, S), kv_cache_pspecs(cfg, mesh, B), mesh)
    logits, _ = jax.jit(prefill, static_argnums=1)(sp, cfg, tokens, seq_lens, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_hybrid_dcn_mesh_trains_with_cross_slice_grad_sync():
    """Multi-slice recipe (docs/DISTRIBUTION.md): a (dcn_data=2, data=2,
    model=2) hybrid mesh trains the planner model with the batch sharded
    over BOTH data axes and params replicated. GSPMD must insert an
    all-reduce whose replica groups span the dcn_data axis (the cross-slice
    DCN collective; on real hardware the outer axis maps to slice
    boundaries), and the training trajectory must be numerically identical
    to the same steps on a flat single-axis mesh — slicing is a layout
    choice, not a math change."""
    from mcpx.models.bpe import BPETokenizer
    from mcpx.models.corpus import CorpusConfig, build_corpus_sync
    from mcpx.models.train import TrainConfig, train
    from mcpx.parallel import batch_axes, make_hybrid_mesh

    tok = BPETokenizer()
    cfg = GemmaConfig.named("test", vocab_size=tok.vocab_size)
    corpus = build_corpus_sync(
        tok, CorpusConfig(n_examples=24, registry_size=40, seed=5)
    )
    tcfg = TrainConfig(steps=4, batch_size=8, warmup_steps=1, log_every=0)

    hybrid = make_hybrid_mesh(dcn_data=2, data=2, model=2)
    assert batch_axes(hybrid) == ("dcn_data", "data")
    params_h, report_h = train(cfg, corpus, tcfg, mesh=hybrid)

    flat = make_mesh(data=8, model=1)
    params_f, report_f = train(cfg, corpus, tcfg, mesh=flat)

    # Identical math: same seed, same batches, same updates.
    np.testing.assert_allclose(
        report_h["final_loss"], report_f["final_loss"], rtol=1e-4
    )
    for a, b in zip(jax.tree.leaves(params_h), jax.tree.leaves(params_f)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4
        )


def test_hybrid_mesh_grad_allreduce_spans_dcn_axis():
    """The lowered train-step HLO must carry a cross-slice reduction: an
    all-reduce (or reduce-scatter) whose replica groups include devices
    from different dcn_data rows — proof the sharding annotations alone
    produce the DCN collective, with no hand-written transport."""
    import re as _re

    from mcpx.models.train import _loss_fn
    from mcpx.parallel import make_hybrid_mesh

    tok_vocab = 384
    cfg = GemmaConfig.named("test", vocab_size=tok_vocab)
    import dataclasses as _dc

    cfg = _dc.replace(cfg, dtype="float32")
    mesh = make_hybrid_mesh(dcn_data=2, data=2, model=2)
    B, L = 8, 16
    tokens = jnp.zeros((B, L), jnp.int32)
    seq_lens = jnp.full((B,), L, jnp.int32)
    mask = jnp.ones((B, L), bool)
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    rep = NamedSharding(mesh, P())
    bsh = NamedSharding(mesh, P(("dcn_data", "data")))
    params = jax.device_put(params, rep)

    def grads(p, t, s, m):
        return jax.grad(_loss_fn)(p, cfg, t, s, m)

    lowered = jax.jit(grads).lower(
        params,
        jax.device_put(tokens, bsh),
        jax.device_put(seq_lens, NamedSharding(mesh, P(("dcn_data", "data")))),
        jax.device_put(mask, bsh),
    )
    hlo = lowered.compile().as_text()

    def decode_groups(line):
        """Materialise replica groups from either HLO syntax: explicit
        `{{0,2},{1,3}}` or iota `[2,4]<=[4,2]T(1,0)`."""
        m = _re.search(r"replica_groups=\{\{([0-9,{} ]+)\}\}", line)
        if m:
            return [
                [int(x) for x in _re.findall(r"\d+", g)]
                for g in _re.split(r"\}\s*,\s*\{", m.group(1))
            ]
        m = _re.search(
            r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
            line,
        )
        if not m:
            return []
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        shape = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(shape))).reshape(shape)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(n_groups, group_size).tolist()

    # Device ids 0-3 are dcn row 0, ids 4-7 row 1 (process-ordered reshape):
    # some gradient all-reduce must group a row-0 device with a row-1 one.
    crossing = [
        g
        for line in hlo.splitlines()
        if "all-reduce" in line or "reduce-scatter" in line
        for g in decode_groups(line)
        if any(i < 4 for i in g) and any(i >= 4 for i in g)
    ]
    assert crossing, "no gradient reduction spans the dcn_data axis"


@pytest.mark.parametrize("axes", [(1, 1), (2, 4), (1, 8)], ids=["1x1", "2x4", "1x8"])
def test_init_params_on_a_mesh_is_the_unsharded_draw_placed(cfg, params, axes):
    """``init_params(mesh=...)`` creates each leaf with its ``param_pspecs``
    sharding; values and placement are what ``shard_pytree`` of the
    unsharded draw gave, without a whole leaf ever on one device."""
    data, model = axes
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    drawn = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    placed = shard_pytree(params, param_pspecs(cfg, mesh), mesh)
    for (path, got), want in zip(jax.tree.leaves_with_path(drawn), jax.tree.leaves(placed)):
        name = jax.tree_util.keystr(path)
        assert got.sharding == want.sharding, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)
        if "model" in got.sharding.spec:
            assert {s.data.size for s in got.addressable_shards} == {got.size // model}, name
