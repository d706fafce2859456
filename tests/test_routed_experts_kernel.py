"""The routed experts' kernel (``mcpx/engine/kernels/routed_experts.py``)
against the jnp loop it stands in for (``moe.moe_forward`` at a window under
the ridge), in interpret mode on the CPU; its blocking at the three sparse
cells' shapes; its lowering for a TPU."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.engine.kernels import routed_experts as kernel
from mcpx.models.gemma import moe
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small(**kw):
    base = dict(
        vocab_size=384, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128,
        n_experts=8, n_experts_per_tok=2, d_expert=256, activation="silu",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False, dtype="float32",
    )
    return GemmaConfig(**{**base, **kw})


def _bound_terms(cfg, x, experts, layer, chosen, w, live):
    """sum_e combine[t, e] (|a_e| @ |w_down_e|)[t, d] in float64: the sum of
    the absolute values of every term that enters an output entry."""
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    x, out = f64(x), 0.0
    for e in range(cfg.n_experts_held):
        mine = (np.asarray(chosen) - cfg.expert_first == e) & np.asarray(live)[:, None]
        combine = (np.asarray(w, np.float64) * mine).sum(axis=1)
        g, u = x @ f64(experts["w_gate"][layer, e]), x @ f64(experts["w_up"][layer, e])
        a = np.abs(g / (1 + np.exp(-g)) * u)
        out = out + combine[:, None] * (a @ np.abs(f64(experts["w_down"][layer, e])))
    return out


CASES = {
    # name: (config, slots B x S, live slots, F_BLK to hold the budget to or None, touched experts)
    "none-touched": (dict(), (2, 4), 0, None, 0),
    "one-touched": (dict(n_experts_per_tok=1), (2, 4), 1, None, 1),
    "all-touched": (dict(), (8, 8), 64, None, 8),
    "held-window": (dict(n_experts=16, expert_first=4, experts_held=8), (8, 8), 64, None, None),
    "idle-rows": (dict(), (4, 8), 11, None, None),
    "f-in-three-blocks": (dict(d_expert=384), (2, 8), 16, 128, None),
    "f-not-a-multiple": (dict(d_expert=320), (2, 8), 16, 128, None),
    "gelu": (dict(activation="gelu_tanh"), (2, 8), 16, None, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_computes_what_the_loop_computes(case, dtype, monkeypatch):
    """``moe_forward`` by the kernel (interpreted) and by the loop: counters
    and experts chosen equal exactly; the outputs within the bound below.

    Both forms take the same products in the same order over experts. With
    float32 rows what may differ is the order of float32 sums (the ``F``
    blocks, and each matmul's own accumulation): at most a few units of
    2^-24 of the sum of the terms' absolute values, taken here as 64. With
    bfloat16 rows the kernel rounds ``act(gate) * up`` to bfloat16 once,
    from float32, where the CPU's loop rounds after each elementwise op (the
    logistic, its product with the gate, the product with up): up to three
    roundings of 2^-9 against one, 2^-7 of that sum at most."""
    over, slots, n_live, fit, n_touched = CASES[case]
    cfg = small(dtype=dtype, **over)
    if fit is not None:
        T, D, F, E = slots[0] * slots[1], cfg.d_model, cfg.d_expert, cfg.n_experts_held
        item = jnp.dtype(dtype).itemsize
        monkeypatch.setattr(kernel, "VMEM_BUDGET", kernel._vmem_bytes(
            fit, T=T, D=D, E=E, F=F, w_itemsize=item, x_itemsize=item))
        assert kernel._blocking(T, D, F, E, item, item) == fit
        assert (F % fit == 0) == (case == "f-in-three-blocks")
    params = init_params(cfg, jax.random.PRNGKey(3))["layers"]
    experts = {k: params[k] for k in moe.EXPERT_LEAVES}
    B, S = slots
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model), jnp.float32).astype(dtype)
    live = (jnp.arange(B * S) % 3 != 1) if case == "idle-rows" else jnp.arange(B * S) < n_live
    live = jax.random.permutation(jax.random.PRNGKey(5), live).reshape(B, S)
    layer = jnp.int32(1)

    args = (h, params["router"][1], experts, layer, cfg, live)
    out_l, stats_l, chosen_l = jax.jit(lambda h: moe.moe_forward(h, *args[1:]))(h)
    out_k, stats_k, chosen_k = jax.jit(
        lambda h: moe.moe_forward(h, *args[1:], use_pallas=True, interpret=True)
    )(h)

    E = cfg.n_experts_held
    assert np.asarray(chosen_k).tolist() == np.asarray(chosen_l).tolist()
    # every counter but the kernel's own steps, which the loop took none of
    assert np.asarray(stats_k)[: E + 3].tolist() == np.asarray(stats_l)[: E + 3].tolist()
    assert int(stats_l[E + 3]) == 0 and int(stats_k[E + 3]) == int(stats_k[E + 2]) == int(stats_k[E])
    if n_touched is not None:
        assert int(stats_k[E]) == n_touched
    if case == "held-window":
        assert 0 < int(stats_k[:E].sum()) < int(live.sum()) * cfg.n_experts_per_tok

    out_l, out_k = (np.asarray(a, np.float64).reshape(B * S, -1) for a in (out_l, out_k))
    assert out_k.dtype == out_l.dtype and (out_k[~np.asarray(live).reshape(-1)] == 0).all()
    if n_touched == 0:
        assert (out_k == 0).all()
        return
    assert np.abs(out_l).max() > 0
    if cfg.activation == "silu":
        x = h.reshape(B * S, -1)
        _, w = moe.route(x, params["router"][1], cfg)
        terms = _bound_terms(cfg, x, experts, 1, np.asarray(chosen_l).reshape(B * S, -1), w, live.reshape(-1))
        unit = 64 * 2.0**-24 if dtype == "float32" else 2.0**-7
        assert (np.abs(out_k - out_l) <= unit * terms + 1e-30).all()
    else:
        tol = 1e-5 if dtype == "float32" else 2.0**-7
        np.testing.assert_allclose(out_k, out_l, rtol=0, atol=tol * np.abs(out_l).max())


# A sparse cell's widths as its configuration file gives them, and the slots
# of the two windows that reach the kernel: a decode segment's 8 x 8 and a
# cohort of one's suffix prefill, 1 x 128.
CELL_SHAPES = {
    "mellum2-12b-a2.5b": (2304, 896, 64, 896),
    "trinity-mini": (2048, 1024, 128, 1024),
    "a.x-k1": (7168, 2048, 12, 512),
}


@pytest.mark.parametrize("T", [64, 128])
@pytest.mark.parametrize("name", list(CELL_SHAPES))
def test_the_blocking_at_the_cells_shapes_fits_the_vmem_asked_for(name, T):
    """A whole expert a step where two of them fit (12.4 / 12.6 MB each), A.X-K1's
    88 MB in four blocks of 512; what the blocking counts lies under its
    budget, and the budget under the limit the call asks Mosaic for."""
    D, F, E, want = CELL_SHAPES[name]
    config = json.load(open(os.path.join(REPO, "benchmarks", "chip", "configs", name + ".json")))
    flat = json.dumps(config)
    assert all(f": {n}" in flat for n in (D, F)), "the cell's widths moved: update CELL_SHAPES"
    f_blk = kernel._blocking(T, D, F, E, 2, 2)
    assert f_blk == want and F % f_blk == 0
    held = kernel._vmem_bytes(f_blk, T=T, D=D, E=E, F=F, w_itemsize=2, x_itemsize=2)
    assert 2 * 3 * D * f_blk * 2 < held <= kernel.VMEM_BUDGET < kernel.VMEM_LIMIT <= 100 * 2**20
    # several MB a block, not a tile: a DMA's start-up is what the loop paid
    assert D * f_blk * 2 >= 4 * 10**6


def test_a_width_no_budget_fits_falls_to_one_lane_width(monkeypatch):
    monkeypatch.setattr(kernel, "VMEM_BUDGET", 1)
    assert kernel._blocking(64, 2304, 896, 64, 2, 2) == 128
    assert kernel._blocking(64, 256, 96, 8, 2, 2) == 96  # never wider than F


@pytest.mark.parametrize("name", list(CELL_SHAPES))
def test_the_kernel_lowers_for_a_tpu_at_the_cells_shapes(name):
    """jaxpr -> Mosaic at the published widths, bfloat16, a decode segment's
    64 slots: what interpret mode cannot show (block shapes Mosaic tiles, the
    dynamic trip count). The custom call carries the name the trace's
    records select it by."""
    D, F, E, _ = CELL_SHAPES[name]
    bf, S = jnp.bfloat16, jax.ShapeDtypeStruct
    shapes = (
        S((64, D), bf), S((64, E), jnp.float32), S((2, E, D, F), bf), S((2, E, D, F), bf),
        S((2, E, F, D), bf), S((E,), jnp.int32), S((), jnp.int32), S((), jnp.int32),
    )
    fn = jax.jit(lambda *a: kernel.routed_experts(*a, act=jax.nn.silu))
    text = fn.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "routed_experts" in text


# ------------------------------------- an expert of two matrices, in a latent
def _latent_block(dtype):
    """A ``layer_pattern`` model's expert layer: 16 experts of TWO matrices
    (``relu(l U)^2 V``, no gate) on a 32-wide latent, 8 held, top-3."""
    return GemmaConfig(
        vocab_size=384, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=0,
        layer_pattern="ME", mamba_n_heads=8, mamba_head_dim=16, mamba_n_groups=2, ssm_state_size=32,
        n_experts=16, n_experts_per_tok=3, d_expert=48, expert_first=4, experts_held=8,
        d_shared_expert=96, moe_latent_size=32, router_scoring="sigmoid", router_bias_scale=0.1,
        router_scale=5.0, rope_full_layers=False, activation="relu2", tie_embeddings=False,
        scale_embeddings=False, norm_plus_one=False, dtype=dtype,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_two_matrix_experts_in_a_latent_by_the_kernel_and_by_the_loop(dtype):
    """The router reads the full width, the experts read and write the
    latent's rows (``moe_forward(rows=...)``); the stacks hold no ``w_gate``,
    which is what tells the loop and the kernel that an expert is
    ``act(x U) V``: same counters, same choices, the outputs within the
    rounding of one product."""
    cfg = _latent_block(dtype)
    params = init_params(cfg, jax.random.PRNGKey(3))["layers"]
    experts = {k: params[k] for k in moe.expert_leaves(params)}
    assert set(experts) == {"w_up", "w_down"} and experts["w_up"].shape == (1, 8, 32, 48)
    B, S = 4, 8
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, 64), jnp.float32).astype(dtype)
    rows = jnp.einsum("btd,dl->btl", h, params["latent_down"][0])
    live = jax.random.permutation(jax.random.PRNGKey(5), jnp.arange(B * S) % 4 != 1).reshape(B, S)
    args = (params["router"][0], experts, jnp.int32(0), cfg, live, params["router_bias"][0])
    out_l, stats_l, chosen_l = jax.jit(lambda h, r: moe.moe_forward(h, *args, rows=r))(h, rows)
    out_k, stats_k, chosen_k = jax.jit(
        lambda h, r: moe.moe_forward(h, *args, rows=r, use_pallas=True, interpret=True))(h, rows)
    assert out_k.shape == (B, S, 32) and out_k.dtype == jnp.float32
    assert np.asarray(chosen_k).tolist() == np.asarray(chosen_l).tolist()
    assert np.asarray(stats_k)[:11].tolist() == np.asarray(stats_l)[:11].tolist() and int(stats_k[8]) > 0
    assert (np.asarray(out_k)[~np.asarray(live)] == 0).all() and float(jnp.abs(out_l).max()) > 0
    tol = 1e-5 if dtype == "float32" else 2.0**-7
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_l), rtol=0,
                               atol=tol * float(jnp.abs(out_l).max()))


def test_the_two_matrix_kernel_lowers_for_a_tpu_at_the_cells_shape():
    """nemotron-3-super: 128 experts held of 1,024 x 2,688 x 2 (11.0 MB, a
    whole expert a step), a decode segment's 64 slots, no ``w_gate`` operand."""
    D, F, E = 1024, 2688, 128
    assert kernel._blocking(64, D, F, E, 2, 2) == F
    bf, S = jnp.bfloat16, jax.ShapeDtypeStruct
    shapes = (S((64, D), bf), S((64, E), jnp.float32), S((5, E, D, F), bf), S((5, E, F, D), bf),
              S((E,), jnp.int32), S((), jnp.int32), S((), jnp.int32))
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    fn = jax.jit(lambda x, c, up, down, *s: kernel.routed_experts(x, c, None, up, down, *s, act=relu2))
    text = fn.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "routed_experts" in text
