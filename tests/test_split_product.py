"""``ssm.dot_split``: a float32 operand read as two operands of the weights'
type, ``hi @ w + lo @ w`` in float32, in TWO forms chosen by the rows of the
product (``ssm.SPLIT_STACK_ROWS``): stacked along the rows of one product where
the weights' read binds (a decode window), summed where the multiplier does (a
prefill), so that nothing of twice the prompt's rows is written there. Over
the two blocks whose mixers call it (``benchmarks/chip/models/jamba.py`` and
``lfm2.py`` at their rehearsal sizes, bfloat16 weights): which form each
route's lowered program takes, the decode window bit for bit the program it
was before the forms were two, and the arithmetic of both forms. CPU, jnp
routes (the recurrence's kernel is not this file's)."""

import functools
import hashlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.engine.kv_cache import (
    commit_prefill_tails, commit_prefill_to_pages, init_paged_kv, init_state_pool,
    write_prefill_state,
)
from mcpx.engine.paged_decode import decode_chunk_paged
from mcpx.models.gemma import ssm
from mcpx.models.gemma.model import init_kv_cache, init_params, prefill

CHIP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip")
W, PSZ, ROWS = 8, 16, 8  # the decode window's slots, a page, the slab's rows
# block -> an admission prefill's (rows, bucket) of the cell that takes the
# summed form: one row of ~890 tokens at the 1,024 bucket; a slab's eight
# 82-token prompts at the 128 bucket (lfm2's cohorts of up to four rows, 512
# rows of a product, stack: ``ssm.SPLIT_STACK_ROWS``).
PREFILL = {"jamba": (1, 1024), "lfm2": (8, 128)}


@functools.lru_cache(maxsize=None)
def _block(name):
    spec = importlib.util.spec_from_file_location(
        f"chip_block_{name}_split_t", os.path.join(CHIP_DIR, "models", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def _cfg(name):
    cfg = _block(name).rehearsal_config(3072)
    assert cfg.dtype == "bfloat16"  # the split acts on weights narrower than float32
    return cfg


def _products(cfg):
    """(K, N) of every split product of the block's mixer, at this width."""
    D = cfg.d_model
    if cfg.n_scan_layers:
        I, N, R = cfg.scan_inner, cfg.ssm_state_size, cfg.mamba_dt_rank
        return {"w_in": (D, 2 * I), "w_x": (I, R + 2 * N), "w_dt": (R, I), "w_out": (I, D)}
    return {"w_in": (D, 3 * D), "w_out": (D, D)}


def _ints(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _window(cfg, params, window, pos, table, pools, q_lens):
    return decode_chunk_paged(
        params, cfg, window, pos, table, pools, use_pallas=False,
        logits_at=jnp.maximum(q_lens - 1, 0), q_lens=q_lens,
    )


def _lowered(name, route):
    cfg = _cfg(name)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if route == "prefill":
        B, T = PREFILL[name]
        fn = lambda p, t, l: prefill(p, cfg, t, l, init_kv_cache(cfg, B, T), last_only=True, use_pallas=False)
        return cfg, B, T, jax.jit(fn).lower(params, _ints(B, T), _ints(B)).as_text()
    n_pages = 1 + ROWS * 8
    pools = jax.eval_shape(
        lambda: {**init_paged_kv(cfg, n_pages, PSZ), "state": init_state_pool(cfg, ROWS, W, n_pages)})
    fn = lambda p, w, pos, tab, pl, q: _window(cfg, p, w, pos, tab, pl, q)
    text = jax.jit(fn).lower(params, _ints(ROWS, W), _ints(ROWS), _ints(ROWS, 8), pools, _ints(ROWS)).as_text()
    return cfg, ROWS, W, text


# ------------------------------------------------- which form a route takes
@pytest.mark.parametrize("route", ["prefill", "window"])
@pytest.mark.parametrize("name", ["jamba", "lfm2"])
def test_a_prefill_sums_its_halves_and_a_decode_window_stacks_them(name, route):
    """From the lowered program of the block's own route. The PREFILL has no
    float32 result of twice its rows, no concatenate along the time axis
    (nothing feeds a product but operands of T rows), and each product of the
    mixer is there on ``[B, T, K]`` operands, once a half. The DECODE WINDOW
    stacks: each product's result has ``2 S`` rows and its operand is a
    concatenate along the time axis."""
    cfg, B, T, text = _lowered(name, route)
    lo = cfg.dtype.replace("bfloat16", "bf16")
    dot = lambda rows, K, N: re.findall(
        rf"dot_general[^\n]*\(tensor<{B}x{rows}x{K}x{lo}>, tensor<{K}x{N}x{lo}>\) -> tensor<{B}x{rows}x{N}xf32>", text)
    stacked_operands = re.findall(rf"concatenate[^\n]*dim = 1[^\n]*-> tensor<{B}x{2 * T}x\d+x{lo}>", text)
    assert B * T > ssm.SPLIT_STACK_ROWS if route == "prefill" else B * T <= ssm.SPLIT_STACK_ROWS
    for product, (K, N) in _products(cfg).items():
        if route == "prefill":
            assert len(dot(T, K, N)) >= 2 and not dot(2 * T, K, N), product
        else:
            assert dot(2 * T, K, N), product
    if route == "prefill":
        assert not re.findall(rf"tensor<{B}x{2 * T}x\d+xf32>", text) and not stacked_operands
    else:
        assert stacked_operands


# ------------------------------------- the decode window, as it always was
# sha256[:16] of (a decode window's logits, the state pool it leaves) at the
# blocks' rehearsal sizes in bfloat16, recorded at the parent commit (d5ab3eb,
# where ``dot_split`` had the stacked form alone) by ``_window_digests``. The
# prefill before the window is 3 x 32 = 96 rows: under ``SPLIT_STACK_ROWS``,
# so the pool the window starts from is the parent's too.
PINNED_WINDOW = {
    "jamba": ("97d4a8c8484dcab7", "5770ab6c1ca04c83"),
    "lfm2": ("d5c9bdbd342e25fe", "a7cc3cbe1552032b"),
}


def _window_digests(name):
    cfg = _cfg(name)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, T, lens = 3, 32, jnp.asarray([20, 9, 14])
    assert B * T <= ssm.SPLIT_STACK_ROWS
    n_pages = 1 + B * 4
    table = jnp.asarray(1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4))
    rng = np.random.default_rng(59)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    _, dense = prefill(params, cfg, toks, lens, init_kv_cache(cfg, B, T), last_only=True, use_pallas=False)
    pools = commit_prefill_to_pages(init_paged_kv(cfg, n_pages, PSZ), dense, table, lens, PSZ)
    state = write_prefill_state(init_state_pool(cfg, B, W, n_pages), jnp.arange(B, dtype=jnp.int32), dense["ssm"])
    if cfg.page_state:
        state["tails"] = commit_prefill_tails(state["tails"], dense["ssm"], table, PSZ)
    q_lens = jnp.asarray([3, 0, 8])
    window = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, W)), jnp.int32)
    logits, out = jax.jit(lambda p, w, pl: _window(cfg, p, w, lens, table, pl, q_lens))(
        params, window, {**pools, "state": state})
    sha = hashlib.sha256()
    for leaf in jax.tree.leaves(out["state"]):
        sha.update(np.asarray(leaf).tobytes())
    return hashlib.sha256(np.asarray(logits).tobytes()).hexdigest()[:16], sha.hexdigest()[:16]


@pytest.mark.parametrize("name", list(PINNED_WINDOW))
def test_a_decode_window_is_bit_for_bit_the_one_form_program(name):
    """The rows choose the form, and a decode window's rows choose the form
    that was the only one: its logits and the state pool it leaves are the
    parent commit's to the bit."""
    assert _window_digests(name) == PINNED_WINDOW[name]


# --------------------------------------------------------- the arithmetic
SHAPES = {f"{name}.{product}": shape for name in PREFILL for product, shape in _products(_cfg(name)).items()}


def _in_form(form, x, w):
    """``dot_split`` with the form forced, whatever the rows -> (the lowered
    text, the result). The form is read where the function is TRACED: a jit of
    its own each time, or the second form would be served the first's trace."""
    was = ssm.SPLIT_STACK_ROWS
    ssm.SPLIT_STACK_ROWS = {"stacked": 1 << 30, "summed": 0}[form]
    try:
        fn = jax.jit(lambda x, w: ssm.dot_split(x, w))
        return fn.lower(x, w).as_text(), np.asarray(fn(x, w), np.float64)
    finally:
        ssm.SPLIT_STACK_ROWS = was


def _operands(shape, rows=(2, 80), dtype=jnp.bfloat16):
    K, N = SHAPES[shape]
    rng = np.random.default_rng(K + N)
    x = jnp.asarray(rng.normal(size=(*rows, K)), jnp.float32)
    return x, jnp.asarray(rng.normal(size=(K, N)) / K**0.5, dtype)


def _err(got, exact):
    return np.sqrt(np.mean((got - exact) ** 2)) / np.std(exact)


@pytest.mark.parametrize("form", ["stacked", "summed"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_both_forms_are_the_float64_product_to_a_part_in_2_to_the_15(shape, form):
    """Against the float64 product of the UNROUNDED operand: under 2^-15 of the
    result's spread in either form, where the operand rounded once (what
    ``mixer_in_bfloat16`` puts in its place) is off by 2^-9; and the form is
    the one asked for."""
    x, w = _operands(shape)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    text, got = _in_form(form, x, w)
    once = jnp.einsum("bte,ed->btd", x.astype(w.dtype), w, preferred_element_type=jnp.float32)
    assert _err(got, exact) < 2.0**-15 and _err(np.asarray(once, np.float64), exact) > 2.0**-10
    assert "reduce_precision" in text and text.count("dot_general") == {"stacked": 1, "summed": 2}[form]
    assert ("concatenate" in text) == (form == "stacked")
    # the summed form's halves are made once and pinned: neither product recomputes them
    assert ("optimization_barrier" in text) == (form == "summed")


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_two_forms_agree_to_float32_rounding(shape):
    """One arithmetic, written twice: the same two products of the same weights
    summed in float32, so the forms differ by the last bits of a float32 sum
    (by nothing, where the backend accumulates a row the same way in both)."""
    x, w = _operands(shape)
    stacked, summed = _in_form("stacked", x, w)[1], _in_form("summed", x, w)[1]
    np.testing.assert_allclose(summed, stacked, rtol=0, atol=4 * 2.0**-24 * np.abs(stacked).max())


@pytest.mark.parametrize("rows", [(8, 8), (1, 1024)], ids=["window", "prefill"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_float32_weights_take_the_plain_product(shape, rows):
    """Nothing to split for: one product on the float32 operand, at a window's
    rows and at a prefill's."""
    x, w = _operands(shape, rows=rows, dtype=jnp.float32)
    text = jax.jit(ssm.dot_split).lower(x, w).as_text()
    assert text.count("dot_general") == 1 and "reduce_precision" not in text and "concatenate" not in text
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    assert _err(np.asarray(ssm.dot_split(x, w), np.float64), exact) < 1e-5


def _rounded_once(x32, w):
    """What ``models/jamba.py``'s ``mixer_in_bfloat16`` control puts in
    ``ssm.dot_split``'s place."""
    info = jnp.finfo(w.dtype)
    hi = jax.lax.reduce_precision(x32, exponent_bits=info.nexp, mantissa_bits=info.nmant)
    return jnp.einsum("bte,ed->btd", hi.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _mixer(name, route, rng):
    """One mixer of the block on a random float32 input, through its prefill or
    its decode window (the jnp walk) -> its output [B, T, D]."""
    cfg = _cfg(name)
    params = init_params(cfg, jax.random.PRNGKey(0))
    stack = params["scan_layers" if name == "jamba" else "conv_layers"]
    lp = jax.tree.map(lambda a: a[0], stack)
    B, T = PREFILL[name] if route == "prefill" else (ROWS, W)
    n = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)), jnp.float32)
    lens = jnp.full((B,), T, jnp.int32)
    if route == "prefill":
        run = ssm.selective_prefill if name == "jamba" else ssm.conv_prefill
        return run(n, lp, cfg, lens)[0]
    pool = init_state_pool(cfg, B, W, 9)
    slots, kept = jnp.arange(B, dtype=jnp.int32), jnp.zeros((B,), jnp.int32)
    if name == "jamba":
        return ssm.selective_window(n, lp, cfg, pool, jnp.asarray(0), slots, lens, kept)[0]
    return ssm.conv_window(n, lp, pool["layers"][0], slots, lens, kept)[0]


@pytest.mark.parametrize("route", ["prefill", "window"])
@pytest.mark.parametrize("name", ["jamba", "lfm2"])
def test_replacing_dot_split_bites_on_the_prefill_as_on_the_window(name, route, monkeypatch):
    """``dot_split`` is ONE name that both routes look up where they are
    traced: the benchmark's control replaces it, and the mixer's output then
    moves by a bfloat16 rounding's worth through a prefill (the summed form)
    as through a decode window (the stacked one)."""
    sound = np.asarray(_mixer(name, route, np.random.default_rng(3)), np.float64)
    monkeypatch.setattr(ssm, "dot_split", _rounded_once)
    low = np.asarray(_mixer(name, route, np.random.default_rng(3)), np.float64)
    moved = _err(low, sound)
    assert 2.0**-10 < moved < 2.0**-5, moved
