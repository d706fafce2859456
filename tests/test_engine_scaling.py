"""Multi-chip scaling evidence beyond 1-vs-8 equality (VERDICT r3 next #5):

  - the TP path's lowered HLO carries the expected ICI collectives (psum
    after ``wo``/``w_down`` per layer — the GSPMD insertions the sharding
    annotations exist to produce), and the DP-only lowering carries no
    TP-shaped reduction of activations;
  - DP genuinely spreads slab rows: batch-major arrays placed with the
    engine's own ``_row_spec`` land one row-shard per data device;
  - cohort accounting through the real engine is mesh-invariant: N
    concurrent requests coalesce into ONE fused decode loop (forwards ≪
    N × per-request forwards) on 1x1, 2x4 and 8x1 meshes alike — DP adds
    capacity without multiplying model forwards.

  - a long prompt's full prefill on a data=4 mesh serves the one-device
    greedy tokens: the dense route is the one full-prefill route, whatever
    the prompt's length.

Wall-clock is deliberately NOT asserted (virtual CPU devices share host
cores; only accounting and sharding structure are stable evidence there).
"""

import asyncio
import dataclasses
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from mcpx.core.config import MCPXConfig
from mcpx.engine.engine import InferenceEngine
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, init_params, prefill
from mcpx.parallel.mesh import kv_cache_pspecs, make_mesh, param_pspecs

# GQA K=4 so KV heads genuinely shard over `model`.
MODEL = GemmaConfig(
    vocab_size=384,
    d_model=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    head_dim=32,
    d_ff=256,
    max_seq_len=256,
)

_COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)


def _lower_prefill_collectives(mesh, batch_axis):
    """Compile the model's prefill under the framework's own pspecs and
    count collective ops in the optimized HLO."""
    params = init_params(MODEL, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        params,
        param_pspecs(MODEL, mesh),
    )
    B, T = 8, 64
    kv = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        init_kv_cache(MODEL, B, T),
        kv_cache_pspecs(MODEL, mesh, B),
    )
    toks = jax.device_put(
        jnp.zeros((B, T), jnp.int32), NamedSharding(mesh, P(batch_axis))
    )
    lens = jax.device_put(
        jnp.full((B,), T, jnp.int32), NamedSharding(mesh, P(batch_axis))
    )
    f = jax.jit(lambda p, t, s, c: prefill(p, MODEL, t, s, c, last_only=True))
    txt = f.lower(params, toks, lens, kv).compile().as_text()
    return Counter(_COLLECTIVE_RE.findall(txt))


def test_tp_lowering_inserts_ici_psums():
    """model-axis sharding must produce the canonical TP collectives: one
    activation all-reduce after wo and one after w_down per layer (2L
    minimum) — proof the annotations, not luck, drive the communication."""
    tp = _lower_prefill_collectives(make_mesh(data=1, model=4), None)
    assert tp["all-reduce"] >= 2 * MODEL.n_layers, dict(tp)

    # DP-only: params are replicated, batch is sharded — the layer stack
    # runs without any cross-replica activation reduction. (The final
    # last-position gather may all-gather tiny [B]-indexed slices; layers
    # themselves must not communicate, which is what makes DP scale.)
    dp = _lower_prefill_collectives(make_mesh(data=8, model=1), "data")
    assert dp["all-reduce"] < tp["all-reduce"], (dict(dp), dict(tp))
    assert dp["reduce-scatter"] == 0 and dp["collective-permute"] == 0, dict(dp)


def _engine_cfg():
    return MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 8,
                "max_decode_len": 32,
                "kv_page_size": 16,
                "max_pages_per_seq": 8,
                "temperature": 0.0,
            },
        }
    )


def test_dp_rows_spread_one_per_device():
    """Batch-major arrays placed with the engine's own row spec land one
    row per data device — the slab's DP rows physically spread."""

    async def go():
        eng = InferenceEngine(_engine_cfg(), model_cfg=MODEL, mesh=make_mesh(data=8, model=1))
        await eng.start()
        try:
            spec = eng._row_spec(8, 1)
            assert spec[0] == "data"
            arr = eng._put(np.zeros((8, 4), np.int32), spec)
            assert len(arr.sharding.device_set) == 8
            shard_shapes = {s.data.shape for s in arr.addressable_shards}
            assert shard_shapes == {(1, 4)}, shard_shapes
        finally:
            await eng.aclose()

    asyncio.run(go())


@pytest.mark.parametrize(
    "mesh_shape", [(1, 1), (2, 4), (8, 1)], ids=["1x1", "2x4", "8x1"]
)
def test_cohort_accounting_is_mesh_invariant(mesh_shape):
    """8 concurrent requests coalesce into one fused decode loop on every
    mesh: total model forwards stay ~= one request's forwards (not 8x),
    and every request still completes — DP adds rows, not loops."""
    data, model = mesh_shape
    if data * model == 1:
        mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    else:
        mesh = make_mesh(data=data, model=model)

    async def go():
        eng = InferenceEngine(_engine_cfg(), model_cfg=MODEL, mesh=mesh)
        await eng.start()
        try:
            prompt = eng.tokenizer.encode("compose a plan. JSON:")
            results = await asyncio.gather(
                *(eng.generate(prompt, max_new_tokens=24) for _ in range(8))
            )
            assert all(r.generated_tokens > 0 for r in results)
            forwards = eng.metrics.decode_forwards._value.get()
            tokens = eng.metrics.decode_tokens._value.get()
            # Serial execution would cost ~8x one request's forwards; the
            # fused batched loop costs ~1x (all rows share each forward).
            # Bound generously: well under 2 forwards per generated token
            # of a SINGLE request (greedy + grammar fast-forward), i.e.
            # batching must amortise at least 4x of the naive 8x.
            per_request_tokens = tokens / 8
            assert forwards < 2 * per_request_tokens, (forwards, tokens)
            return forwards, tokens
        finally:
            await eng.aclose()

    asyncio.run(go())


def test_a_long_prompt_on_a_data_mesh_serves_the_one_device_greedy_tokens():
    """A ~300-token prompt (the 512 prefill bucket) and a short one, greedy,
    on a data=4 x model=2 mesh and on one device: the same tokens, through
    the one dense full-prefill executable. float32 end to end, so that the
    sharded sums cannot wobble the argmax."""
    model = dataclasses.replace(MODEL, dtype="float32", max_seq_len=512)
    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 512},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 2,
                "max_decode_len": 48,
                "kv_page_size": 16,
                "max_pages_per_seq": 32,
                "temperature": 0.0,
                "prefix_cache": False,  # every prompt a full prefill
            },
        }
    )
    long_prompt = (
        "Compose a service DAG over the following services. "
        + " ".join(f"svc-{i:03d} in:query out:result" for i in range(18))
        + " Intent: fetch then summarize. JSON:"
    )

    async def serve(mesh):
        eng = InferenceEngine(cfg, model_cfg=model, mesh=mesh)
        await eng.start()
        try:
            ids = eng.tokenizer.encode(long_prompt)
            assert len(ids) >= 256
            long = await eng.generate(ids, max_new_tokens=40)
            short = await eng.generate(eng.tokenizer.encode("plan. JSON:"), max_new_tokens=24)
            compiled = eng.costs.snapshot(materialize=False)["executables"]
            assert compiled["prefill"]["compiles"] == 2  # one a bucket, no other route
            return long.token_ids, short.token_ids
        finally:
            await eng.aclose()

    one = asyncio.run(serve(make_mesh(data=1, model=1, devices=jax.devices()[:1])))
    assert all(one)
    assert asyncio.run(serve(make_mesh(data=4, model=2))) == one
