"""The Nemotron-H block (NVIDIA-Nemotron-3-Super): layers that are a Mamba-2
mixer OR a feed-forward OR attention alone, a recurrent state a row beside the
KV pages that moves by exactly what a decode window accepted, routed experts
of two matrices in a latent beside a shared expert. CPU, small sizes, the
state pool's kernel interpreted AND the jnp form in lockstep; the plain
reference is the benchmark's block module (``benchmarks/chip/models/
nemotron_h.py``), imported by path, and the comparison is the one that decides
a benchmark run's ``correct`` (``benchmarks/chip/reference.py``),
run with its controls in ``tests/test_ssm_rehearsal.py`` beside the rehearsal child."""

import asyncio
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError
from mcpx.engine.kv_cache import (
    commit_prefill_to_pages, init_paged_kv, init_state_pool, write_prefill_state,
)
from mcpx.engine.paged_decode import keep_window
from mcpx.models.gemma import moe
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import (
    hybrid_feed_forward, init_kv_cache, init_params, rms_norm, stack_row,
)
from mcpx.parallel.mesh import make_mesh, param_pspecs
from tests.helpers import by_path, compiled, one_device, params_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
W = 8  # the decode window's slots
jit_prefill, jit_chunk = compiled()  # one executable a (configuration, route, shapes): tests/helpers.py


@pytest.fixture(scope="module")
def block():
    return by_path("chip_block_nemotron_t", os.path.join(CHIP_DIR, "models", "nemotron_h.py"))


def small(**kw):
    """The block at layer-test size, float32 so that sums can be compared."""
    base = dict(
        vocab_size=384, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=0,
        norm_eps=1e-5, max_seq_len=256, layer_pattern="ME*ME", mamba_n_heads=8, mamba_head_dim=16,
        mamba_n_groups=2, ssm_state_size=32, ssm_chunk_size=16, n_experts=16, n_experts_per_tok=3,
        d_expert=48, expert_first=4, experts_held=8, d_shared_expert=96, moe_latent_size=32,
        router_scoring="sigmoid", router_bias_scale=0.1, router_scale=5.0, rope_full_layers=False,
        activation="relu2", tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
        dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


def _small_params():
    cfg = small()
    return cfg, params_of(cfg)


# ------------------------------------------------------------ configuration
def test_the_tree_has_three_stacks_and_the_count_is_the_trees():
    cfg, params = _small_params()
    m, e, a = params["mamba_layers"], params["layers"], params["attn_layers"]
    assert m["w_in"].shape == (2, 64, 128 + 256 + 8) and m["conv_w"].shape == (2, 256, 4)
    assert m["A_log"].dtype == m["dt_bias"].dtype == jnp.float32 and m["w_out"].shape == (2, 128, 64)
    assert e["w_up"].shape == (2, 8, 32, 48) and e["w_down"].shape == (2, 8, 48, 32) and "w_gate" not in e
    assert e["router"].shape == (2, 64, 16) and e["latent_down"].shape == (2, 64, 32)
    assert a["wq"].shape == (1, 64, 128) and a["wk"].shape == (1, 64, 64) and a["wo"].shape == (1, 128, 64)
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == cfg.n_params
    # a token reads 3 of the 16 routed experts of each of the 2 expert layers
    assert cfg.n_params - cfg.n_active_params == 2 * (8 - 3) * 2 * 32 * 48
    # the family's draw: steps log-uniform in [time_step_min, time_step_max], A in [1, 16]
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 0.00099 < step.min() and step.max() < 0.1001
    assert 0 <= float(m["A_log"].min()) and float(m["A_log"].max()) <= np.log(16) + 1e-6
    assert (np.asarray(m["D_skip"]) == 1).all()


def test_published_counts_of_nemotron_3_super(block):
    """The arithmetic that says the structure is right: a Mamba layer is
    109,640,064 parameters, an attention layer 35,655,680, an expert layer
    54,530,560 outside its experts of 5,505,024 each; the published 88 layers
    and vocabulary come to the model's own name, 120B-A12B; the cut to 8.81 GB."""
    with open(os.path.join(CHIP_DIR, "configs", "nemotron-3-super.json")) as f:
        config = json.load(f)
    harness = {"name", "source", "module", "chips", "mesh", "slab_rows", "mcpx", "reduced",
               "assumed", "departures", "params", "max_batch_size", "max_pages_per_seq",
               "max_decode_len", "warmup_max_len"}
    keys = {k: v for k, v in config.items() if k not in harness}
    cut = block.model_config(keys, 3072)
    assert cut.n_params == 4_404_894_080 and cut.layer_pattern == "MEMEMEM*EME"
    assert (cut.n_mamba_layers, cut.n_sparse_layers, cut.n_attn_layers) == (5, 5, 1)
    assert cut.ssm_slot_bytes == 128 * 64 * 128 * 4 and cut.conv_width == 10240
    one = lambda pattern: dataclasses.replace(cut, n_layers=len(pattern), layer_pattern=pattern)
    base = one("E").n_params - 54_530_560 - 128 * 5_505_024
    assert one("ME").n_params - one("E").n_params == 109_640_064
    assert one("*E").n_params - one("E").n_params == 35_655_680
    assert base == 2 * 3072 * 4096 + 4096
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog) if "Nemotron-3-Super" in l)
        for key, value in row["config"].items():
            changed = {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}
            assert key in changed or config[key] == value, key
        pattern = row["config"]["hybrid_override_pattern"]
        assert pattern[:11] == cut.layer_pattern and (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
        whole = dataclasses.replace(
            cut, n_layers=88, layer_pattern=pattern, experts_held=0, vocab_size=131072
        )
        assert whole.n_params == 120_668_707_840 and whole.n_active_params == 12_770_237_440  # 120B-A12B


@pytest.mark.parametrize("bad", [
    dict(layer_pattern="MEXME"),  # an unknown letter
    dict(layer_pattern="ME*M"),  # one letter a layer
    dict(layer_pattern="M*M*M"),  # no E layer
    dict(mamba_n_groups=3),  # heads in whole groups
    dict(rope_full_layers=True),  # the pattern's attention is unrotated
    dict(qk_norm=True),
    dict(n_dense_layers=1),
    dict(d_shared_expert=0),
])
def test_a_pattern_that_cannot_be_is_refused(bad):
    with pytest.raises(ConfigError):
        small(**bad)


def test_the_new_fields_belong_to_a_pattern():
    for field in (dict(moe_latent_size=32), dict(mamba_n_heads=8), dict(activation="relu2")):
        with pytest.raises(ConfigError):
            GemmaConfig(**field)


@pytest.mark.parametrize("feature, cfg_json", [
    ("hetero_batch", {"engine": {"hetero_batch": True}}),
    ("kv_tier", {"engine": {"kv_tier": {"enabled": True, "host_mb": 8}}}),
    ("kv_tier", {"engine": {"kv_tier": {"snapshot_path": "/tmp/never-written.snap"}}}),
    ("int8", {"model": {"quantize": "int8"}}),
    ("speculative", {"engine": {"hetero_batch": False, "speculative": {"enabled": True}}}),
])
def test_what_does_not_carry_the_state_is_an_error_at_construction(feature, cfg_json):
    """Spill, the warm-restart snapshot, the stacked-grammar segments, int8
    and the drafter do not carry a recurrent state: refused by name when the
    engine is built, never a wrong answer later."""
    from mcpx.engine.engine import InferenceEngine

    with pytest.raises(ConfigError, match=feature):
        InferenceEngine(MCPXConfig.from_dict(cfg_json), model_cfg=small())


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_every_leaf_has_a_spec(mesh_shape):
    cfg, params = _small_params()
    data, model = mesh_shape
    mesh = make_mesh(data=data, model=model, devices=jax.devices()[: data * model])
    specs = param_pspecs(cfg, mesh)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, dict))
    )
    sharded = init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_costs_count_the_new_leaves():
    from mcpx.telemetry.costs import model_cost

    cfg, params = _small_params()
    cost = model_cost(cfg)
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert cost["params_held"] == held and cost["flops_per_token"] == 2 * cfg.n_active_params
    weight_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    expert, rest = moe.forward_weight_bytes(cfg, params)
    assert expert == 2 * 32 * 48 * 4
    assert rest == weight_bytes - 2 * 8 * expert - params["embed"].nbytes


# ------------------------------------------------------- the experts' share
def test_the_four_shares_add_up_with_the_shared_expert_counted_once():
    """The guide's section 4: an expert layer's 16 experts held as four
    shares of 4, each routing over all 16 and each computing the shared
    expert: the four partial results, less the shared expert's three times,
    sum to what the layer gives with all 16 held."""
    cfg = small(expert_first=0, experts_held=0)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 6, 64)), jnp.float32)
    live = jnp.ones((2, 6), bool)
    whole, _, chosen = hybrid_feed_forward(x, params["layers"], 1, cfg, live)
    lp = stack_row({k: v for k, v in params["layers"].items() if k not in moe.EXPERT_LEAVES}, 1)
    n = rms_norm(x, lp["norm"], cfg.norm_eps, False)
    shared = jnp.square(jax.nn.relu(n @ lp["shared_up"])) @ lp["shared_down"]
    assert float(jnp.abs(shared).max()) > 0
    parts = []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, expert_first=first, experts_held=4)
        mine = {**params["layers"], **{k: params["layers"][k][:, first : first + 4] for k in ("w_up", "w_down")}}
        out, stats, ch = hybrid_feed_forward(x, mine, 1, share, live)
        assert (np.asarray(ch) == np.asarray(chosen)).all()  # every share routes over all 16
        parts.append(out - x)
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole - x), atol=2e-5)
    assert sum(float(jnp.abs(p - shared).max()) > 1e-4 for p in parts) >= 3  # the shares differ


def test_past_the_ridge_the_grouped_form_computes_what_the_loop_does(monkeypatch):
    cfg, params = _small_params()
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 80, 64)), jnp.float32)  # 320 slots: past the ridge of 256
    live = jnp.asarray(rng.random((4, 80)) < 0.8)
    grouped, g_stats, _ = hybrid_feed_forward(x, params["layers"], 0, cfg, live)
    monkeypatch.setattr(moe, "RIDGE_SLOTS", 1024)
    loop, l_stats, _ = hybrid_feed_forward(x, params["layers"], 0, cfg, live)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(loop), atol=2e-5)
    assert (np.asarray(g_stats[:8]) == np.asarray(l_stats[:8])).all() and int(g_stats[:8].sum()) > 0


# ------------------------------------------ the state, at the model's level
def _prefilled(cfg, params, toks, lens, T, n_slots):
    B = toks.shape[0]
    last, dense = jit_prefill(params, cfg, toks[:, :T], lens, init_kv_cache(cfg, B, T), last_only=True)
    table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    pools = commit_prefill_to_pages(init_paged_kv(cfg, 1 + B * 4, 16), dense, table, lens, 16)
    pools["state"] = write_prefill_state(init_state_pool(cfg, n_slots, W), jnp.arange(B), dense["ssm"])
    return last, pools, table, dense


def test_a_padded_prefills_state_is_the_unpadded_ones():
    """A prefill at a bucket of a shorter prompt gives the state AT the
    prompt's length (a pad position has dt 0; the tail is taken at the
    length), at a length inside a chunk, on a chunk's edge and past one."""
    cfg, params = _small_params()
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 48)), jnp.int32)
    lens = [20, 16, 37]
    _, padded = jit_prefill(params, cfg, toks, jnp.asarray(lens), init_kv_cache(cfg, 3, 48), last_only=True)
    for b, n in enumerate(lens):
        _, alone = jit_prefill(params, cfg, toks[b : b + 1, :n], jnp.asarray([n]), init_kv_cache(cfg, 1, n),
                               last_only=True)
        for (h, tail), (h1, tail1) in zip(padded["ssm"], alone["ssm"]):
            np.testing.assert_allclose(np.asarray(h[b]), np.asarray(h1[0]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(tail[b]), np.asarray(tail1[0]), atol=1e-5)
            assert float(jnp.abs(h1).max()) > 1e-3


@pytest.mark.parametrize("path", ["kernel", "jnp"])
def test_windows_with_rejected_proposals_equal_token_by_token_decode(path):
    """Decode windows ``[the token, proposals]`` of which a row keeps 0..8
    (the rest WRONG tokens), uneven ``q_lens``, an idle row: every kept
    position's logits are the whole sequence's own (the dense forward over
    all of it), window after window, so the state moved by what was kept and
    by nothing else. An idle row's slots, and the slots no row owns, are
    bit-unchanged."""
    cfg, params = _small_params()
    rng = np.random.default_rng(0)
    B, T = 3, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 64)), jnp.int32)
    lens = jnp.asarray([20, 9, 14])
    full, _ = jit_prefill(params, cfg, toks, jnp.asarray([64] * B), init_kv_cache(cfg, B, 64))
    last, pools, table, _ = _prefilled(cfg, params, toks, lens, T, B + 2)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(last[b]), np.asarray(full[b, lens[b] - 1]), atol=2e-4)
    mesh = one_device()
    step = functools.partial(jit_chunk, use_pallas=path == "kernel", interpret=True, mesh=mesh)
    pos = lens
    plan = [([3, 0, 8], [1, 0, 5]), ([8, 4, 1], [8, 2, 1]), ([5, 5, 5], [1, 1, 1]), ([0, 8, 2], [0, 3, 2])]
    for q, keep in plan:
        q, keep = jnp.asarray(q), jnp.asarray(keep)
        window = jnp.stack([jax.lax.dynamic_slice(toks[b], (pos[b],), (W,)) for b in range(B)])
        wrong = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, W)), jnp.int32)
        window = jnp.where(jnp.arange(W)[None, :] < keep[:, None], window, wrong)
        before = jax.tree.map(np.asarray, pools["state"])
        logits, pools = step(params, cfg, window, pos, table, pools, q_lens=q)
        for b in range(B):
            for s in range(int(keep[b])):
                np.testing.assert_allclose(
                    np.asarray(logits[b, s]), np.asarray(full[b, pos[b] + s]), atol=3e-4
                )
        after = jax.tree.map(np.asarray, pools["state"])
        idle = [b for b in range(B) if int(q[b]) == 0] + [B, B + 1]
        for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            if old.ndim == 4:  # the recurrent states: [layers, slots, ...]
                old, new = np.moveaxis(old, 1, 0), np.moveaxis(new, 1, 0)
            np.testing.assert_array_equal(old[idle], new[idle])
        pools["state"] = keep_window(pools["state"], jnp.arange(B), keep, q > 0)
        pos = pos + keep


def test_the_kernel_and_the_jnp_form_move_the_pool_alike():
    """``kernels/ssm.ssm_window`` against the jnp form it stands for, on a
    pool of its own: the second of three layers' states, live rows in any
    order of slots, idle rows first, last and between, no live row at all."""
    from mcpx.engine.kernels.ssm import ssm_window
    from mcpx.models.gemma import ssm

    rng = np.random.default_rng(2)
    B, S, G, N, H, P, slots_n = 5, 8, 2, 32, 8, 16, 9
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool, total, xs = f(3, slots_n, N, H * P), jnp.abs(f(B, H)), f(B, W, H, P)
    p_b, c = f(B, W, G, N), f(B, S, G, N)
    slots = jnp.asarray([7, 2, 0, 5, 3], jnp.int32)
    for q in ([0, 3, 0, 8, 0], [2, 2, 2, 2, 2], [0, 0, 0, 0, 1], [0, 0, 0, 0, 0]):
        q = jnp.asarray(q, jnp.int32)
        got_pool, got_hc = ssm_window(
            pool, 1, slots, q, jnp.repeat(total, P, axis=1), xs.reshape(B, W, H * P),
            jnp.transpose(p_b, (0, 2, 3, 1)), jnp.transpose(c, (0, 2, 1, 3)), interpret=True,
        )
        h = ssm.advance_state(pool[1, slots].reshape(B, N, H, P), total, xs, p_b)
        want_hc = np.where(np.asarray(q > 0)[:, None, None, None], np.asarray(ssm.state_outputs(h, c)), 0.0)
        want_pool = np.asarray(pool).copy()
        for b in range(B):
            if int(q[b]):
                want_pool[1, int(slots[b])] = np.asarray(h[b]).reshape(N, H * P)
        np.testing.assert_allclose(np.asarray(got_pool), want_pool, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_hc).reshape(B, S, H, P), want_hc, rtol=1e-5, atol=1e-5)
        untouched = [s for s in range(slots_n) if s not in {int(slots[b]) for b in range(B) if int(q[b])}]
        np.testing.assert_array_equal(np.asarray(got_pool)[1, untouched], np.asarray(pool)[1, untouched])
        np.testing.assert_array_equal(np.asarray(got_pool)[[0, 2]], np.asarray(pool)[[0, 2]])


def test_a_window_wider_than_the_pending_width_is_refused():
    """Nothing serves a recurrent layer a suffix prefill (no radix node holds
    the state a hit would start from: its rows prefill whole), so a window of
    more slots than the pool keeps pending has no route, and says so."""
    cfg, params = _small_params()
    toks = jnp.zeros((2, 64), jnp.int32)
    lens = jnp.asarray([16, 11])
    _, pools, table, _ = _prefilled(cfg, params, toks, lens, 16, 2)
    with pytest.raises(ValueError, match="the state pool keeps 8 pending"):
        jit_chunk(params, cfg, toks[:, :32], lens, table, pools, use_pallas=False,
                  q_lens=jnp.asarray([24, 19]))


def test_the_kernels_blocks_are_a_groups_lanes_under_the_budget():
    """``kernels/ssm._blocking``: the lanes of one group's heads a grid step
    takes, from the shapes alone: the widest whole number of lane widths that
    divides them and keeps the state's four buffers under the budget."""
    from mcpx.engine.kernels.ssm import VMEM_BUDGET, _blocking

    assert _blocking(1024, 128) == 1024  # the sparse hybrid cell: a whole group a step
    assert _blocking(1024, 1024) == 512 and 4 * 1024 * 512 * 4 <= VMEM_BUDGET
    assert _blocking(1024, 8192) == 128  # a lane width is the least a step takes
    assert _blocking(32, 32) == 32  # narrower than a lane width: the tests' sizes


# ------------------------------------------- the served path, at every length
def _engine_config(**engine):
    return MCPXConfig.from_dict({
        "model": {"max_seq_len": 256},
        "engine": {"max_batch_size": 4, "max_decode_len": 40, "kv_page_size": 16, "max_pages_per_seq": 16,
                   "temperature": 0.0, "use_pallas": True, "interpret": True, "prefix_cache": False,
                   "warmup_compile": True, "warmup_max_len": 64, **engine},
    })


PROMPTS = [f"Length parity.\nintent {i}: compose. JSON:" for i in range(5)]
BUDGETS = [3, 38, 9, 21, 14]


async def _serve(eng, prompts=PROMPTS, budgets=BUDGETS):
    ids = [eng.tokenizer.encode(p) for p in prompts]
    rs = await asyncio.gather(*(
        eng.generate(p, max_new_tokens=b, constrained=True, temperature=0.0) for p, b in zip(ids, budgets)))
    return [r.token_ids for r in rs]


def _at_every_length(config, one_device=True):
    """The tokens one engine serves at 4, 8, 12 and 16 forwards a segment
    (equal, and nothing compiled between), and its lifetime counters. On one
    device, where the state pool's kernel runs, unless told otherwise (the
    engine's own mesh takes all of the tests' 8 virtual devices)."""
    from mcpx.engine.engine import InferenceEngine
    from mcpx.engine.pacing import SegmentPacer

    class Fixed(SegmentPacer):
        def __init__(self, n):
            super().__init__()
            self.n, self.lengths = n, []

        def window(self, tick, ceiling):
            return min(ceiling, self.n)

        def dispatched(self, t0, t1, forwards):
            self.lengths.append(forwards)
            super().dispatched(t0, t1, forwards)

    async def go():
        mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1]) if one_device else None
        eng = InferenceEngine(config, model_cfg=small(), mesh=mesh)
        await eng.start()
        try:
            compiles = lambda: {name: e["compiles"] for name, e in
                                eng.costs.snapshot(materialize=False)["executables"].items()}
            snap, got = compiles(), {}
            for n in (4, 8, 12, 16):
                pacer = eng._pacer = Fixed(n)
                got[n] = await _serve(eng)
                assert set(pacer.lengths) == {n} and compiles() == snap, (n, pacer.lengths)
            assert all(got[4]) and got[4] == got[8] == got[12] == got[16]
            for _ in range(200):  # the worker harvests the last segment in its own time
                if not eng._inflight:
                    break
                await asyncio.sleep(0.05)
            return got[4], dict(eng._layer_kind_totals), eng._prefix_state_misses, eng.pallas_paths()
        finally:
            await eng.aclose()

    return asyncio.run(go())


@pytest.fixture(scope="module")
def served():
    """One engine a way of serving: the prompt draft on at the decode window
    of 8 (the cell's), off, a window of 4, one token a forward, the jnp
    forms, the engine's own mesh over the tests' 8 devices (the state pool
    whole on each, its jnp form partitioned), and the radix cache on."""
    return {
        "draft": _at_every_length(_engine_config()),
        "no_draft": _at_every_length(_engine_config(draft_mode="off")),
        "chunk_4": _at_every_length(_engine_config(speculate_k=4)),
        "chunk_1": _at_every_length(_engine_config(speculate_k=1)),
        "jnp": _at_every_length(_engine_config(use_pallas=False, interpret=False)),
        "mesh": _at_every_length(_engine_config(), one_device=False),
        "radix": _at_every_length(_engine_config(prefix_cache=True)),
    }


def test_the_engine_serves_the_same_tokens_at_every_segment_length(served):
    """The pacer asks for 4, 8, 12 or 16 forwards a segment: the same greedy,
    grammar-constrained requests, with budgets that retire rows mid-segment
    and rows reused by later plans, decode byte-identical tokens at each
    length (``_at_every_length``), at every compiled ``chunk`` (8, 4, 1), with
    the prompt draft on and off, through the interpreted kernels and the jnp
    forms: a state that moved by the window, or by a segment's length, could
    not."""
    want = served["draft"][0]
    assert [len(t) for t in want] == BUDGETS
    for way, (tokens, _, _, _) in served.items():
        assert tokens == want, way


def test_the_counters_say_what_the_state_kept(served):
    _, totals, _, paths = served["draft"]
    cfg = small()
    assert paths["paths"]["ssm"]["engaged"] and paths["paths"]["ssm"]["dispatches"] > 0
    assert totals["ssm_row_calls"] > 0 and totals["ssm_row_calls"] % cfg.n_mamba_layers == 0
    assert totals["ssm_state_bytes"] == totals["ssm_row_calls"] * cfg.ssm_slot_bytes * 2
    # some proposal was rejected and not kept; every kept token was a live slot
    assert 0 < totals["ssm_tokens"] < totals["ssm_slots"]
    # 4 rounds of 5 prompts, every token of each through each Mamba layer (no radix cache here)
    from mcpx.models.tokenizer import make_tokenizer

    n_prompt = sum(len(make_tokenizer("byte").encode(p)) for p in PROMPTS)
    assert totals["ssm_prefill_tokens"] == 4 * n_prompt * cfg.n_mamba_layers
    # with no draft every live slot is kept: the fast-forward's tokens are forced
    _, plain, _, _ = served["no_draft"]
    assert plain["ssm_tokens"] == plain["ssm_slots"] == totals["ssm_tokens"]
    # a round's 85 decoded tokens each go back through the model once (the
    # admission's sample first; the last forward's sample ends the plan)
    assert totals["ssm_tokens"] == 4 * sum(BUDGETS) * cfg.n_mamba_layers
    assert not served["jnp"][3]["paths"]["ssm"]["engaged"]
    assert not served["mesh"][3]["paths"]["ssm"]["engaged"] and "one device" in served["mesh"][3]["paths"]["ssm"]["reason"]


def test_with_the_radix_cache_on_every_row_prefills_whole_and_resident_pages_are_a_counted_miss(served):
    """No radix node holds the state a recurrent layer would start from, so
    a prompt whose pages are resident is NOT started from pages alone: it
    prefills whole, serves the cache-off engine's tokens, and the counter
    says what that cost."""
    tokens, totals, misses, _ = served["radix"]
    assert tokens == served["draft"][0]
    assert totals["ssm_prefill_tokens"] == served["draft"][1]["ssm_prefill_tokens"]
    # 5 prompts x 4 rounds. The first admitted inserts its two pages, the
    # other four share its first page: from round 2 on each finds pages
    # resident (15), and in round 1 those admitted after it.
    assert 15 <= misses <= 19
    assert served["draft"][2] == 0


def test_a_row_reused_after_another_plan_serves_what_a_fresh_engine_serves():
    """Two rows, six plans: every row is taken, finished and taken again; each
    plan's tokens are what an engine that has served nothing else gives it."""
    from mcpx.engine.engine import InferenceEngine

    config = _engine_config(max_batch_size=2, warmup_compile=False)  # no compile count is read here
    prompts = [f"Reuse.\nintent {i}: route and merge. JSON:" for i in range(6)]
    budgets = [17, 5, 11, 23, 8, 14]

    async def one(ps, bs):
        eng = InferenceEngine(config, model_cfg=small())
        await eng.start()
        try:
            return await _serve(eng, ps, bs)
        finally:
            await eng.aclose()

    together = asyncio.run(one(prompts, budgets))
    fresh = asyncio.run(one(prompts[4:], budgets[4:]))
    assert together[4:] == fresh and all(together)
