"""The paged forward's K/V write (``paged_decode._write_kv_window``): whole
pages, in place, in the pool's own shape.

Three guards:
  - the write leaves the pools bit-identical to the row-granular flat
    scatter it replaced (kept HERE as the oracle, nowhere in ``mcpx/``),
    through the whole forward on both attention routes and directly on
    random pools;
  - the traced forward never shows the pool in a flat ``[K, L, N*psz, hd]``
    view and only ever scatters whole ``[psz, hd]`` pages into it;
  - compiled for a described v5e (no chip attached), the layer loop holds
    no ``copy`` of the pool: the relayout the flat scatter cost on the
    chip (PERF.md, PR 25) cannot come back unnoticed.

Beside them, compiled the same way: at 128 heads the layer loop writes no
copy of a layer's ``wo`` before the projection reads it (PERF.md, PR 46).
And the pool's other two writers: the prefill's commit and the allocator
that says which pages a row owns.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcpx.core.errors import EngineError
from mcpx.engine import paged_decode
from mcpx.engine.kv_cache import PageAllocator, commit_prefill_to_pages, init_paged_kv
from mcpx.engine.paged_decode import _kv_window, _write_kv_window, decode_chunk_paged
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_params
from mcpx.parallel.mesh import make_mesh


# ------------------------------------------------------------------ oracle
def _flat_scatter_oracle(positions, page_table, S):
    """The write this PR removed, verbatim: one row-granular scatter per
    layer through the flat ``[K, L, N*psz, hd]`` view of the pool."""

    def write(pool, layer, new, _window):
        K, L, N, psz, hd = pool.shape
        pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)
        flat_idx = (
            jnp.take_along_axis(page_table, pos_mat // psz, axis=1) * psz + pos_mat % psz
        )
        return (
            pool.reshape(K, L, N * psz, hd)
            .at[:, layer, flat_idx]
            .set(new.transpose(2, 0, 1, 3).astype(pool.dtype))
            .reshape(K, L, N, psz, hd)
        )

    return write


def _assert_pools_identical(got, want):
    """Bit-identical on every page but null page 0 (never read; pads, done
    rows and dropped columns may leave either write's garbage there)."""
    for name in ("k", "v"):
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g[:, :, 1:], w[:, :, 1:], err_msg=name)


# --------------------------------------------------- through the forward
_PSZ, _PMAX = 16, 10

# head layout: (n_heads, n_kv_heads, head_dim)
_LAYOUTS = {
    "mha16": (16, 16, 128),  # olmo2-1b: 16/16
    "gqa8of32": (32, 8, 128),  # mistral-7b: 8/32
    "mqa-hd256": (8, 1, 256),  # chip_smoke's Gemma-2B shape
}


def _window_case(name):
    """(S, positions, q_lens, table edit) of one window class; four rows,
    each with ``_PMAX`` private pages unless the edit says otherwise."""
    B = 4
    table = np.arange(1, B * _PMAX + 1, dtype=np.int32).reshape(B, _PMAX)
    if name == "S1":
        S, pos = 1, [0, 15, 16, 37]
        q_lens = [1, 1, 1, 1]
    elif name == "S8-straddle":
        # windows that end on, start on and cross a page edge
        S, pos = 8, [8, 16, 12, 30]
        q_lens = [8, 3, 8, 1]
    elif name == "S128-ragged-prefill":
        # a 128-wide suffix prefill at page-aligned matched depths, one row
        # starting mid-page (a decode row riding a prefill-width window)
        S, pos = 128, [0, 16, 32, 5]
        q_lens = [128, 77, 1, 40]
    elif name == "done-rows":
        # retired rows: page table zeroed, position frozen, zero live slots
        S, pos = 8, [20, 44, 3, 90]
        q_lens = [8, 0, 5, 0]
        table[1] = 0
        table[3] = 0
    elif name == "overhang":
        # row 0's window runs past its last table column (dropped); row 1
        # ends exactly on it; row 2 has only three pages allocated
        S, pos = 8, [_PMAX * _PSZ - 3, _PMAX * _PSZ - 8, 44, 0]
        q_lens = [2, 8, 4, 8]
        table[2, 3:] = 0
    else:  # pragma: no cover
        raise AssertionError(name)
    return S, np.asarray(pos, np.int32), q_lens, table


_FORWARD_CASES = [
    (w, "mha16") for w in
    ("S1", "S8-straddle", "S128-ragged-prefill", "done-rows", "overhang")
] + [("S8-straddle", "gqa8of32"), ("S8-straddle", "mqa-hd256")]


@pytest.mark.parametrize("route", ["kernel-interpret", "jnp"])
@pytest.mark.parametrize("window,layout", _FORWARD_CASES)
def test_forward_leaves_the_pools_as_the_flat_scatter_did(window, layout, route, monkeypatch):
    """The whole forward, new write against the oracle: logits and BOTH
    pools bit-identical (every page but null page 0), for every window
    class the engine dispatches, on the kernel route (interpreted) and the
    jnp reference route."""
    n_heads, n_kv, hd = _LAYOUTS[layout]
    cfg = GemmaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=n_heads, n_kv_heads=n_kv,
        head_dim=hd, d_ff=64, dtype="float32",
    )
    S, pos, q_lens, table = _window_case(window)
    B = pos.shape[0]
    params = init_params(cfg, jax.random.PRNGKey(0))
    pools = init_paged_kv(cfg, B * _PMAX + 1, _PSZ)
    # a resident history: every slot holds something the write must keep
    pools = {
        n: jax.random.normal(jax.random.PRNGKey(i), p.shape, p.dtype)
        for i, (n, p) in enumerate(pools.items())
    }
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
    positions, page_table = jnp.asarray(pos), jnp.asarray(table)
    kw = dict(
        use_pallas=route != "jnp", interpret=True, q_lens=jnp.asarray(q_lens, jnp.int32),
        mesh=make_mesh(data=1, model=1, devices=jax.devices()[:1]),
    )

    def forward():
        return jax.jit(
            lambda p, t, po, tb, kv: decode_chunk_paged(p, cfg, t, po, tb, kv, **kw)
        )(params, tokens, positions, page_table, pools)

    got_logits, got = forward()
    monkeypatch.setattr(
        paged_decode, "_write_kv_window", _flat_scatter_oracle(positions, page_table, S)
    )
    want_logits, want = forward()
    _assert_pools_identical(got, want)
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    # not vacuous: the write changed pages other than page 0
    assert not np.array_equal(np.asarray(got["k"][:, :, 1:]), np.asarray(pools["k"][:, :, 1:]))


# ------------------------------------------------------ the write alone
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S", [1, 2, 8, 16, 17, 33, 128])
def test_write_alone_matches_the_flat_scatter_at_every_offset(S, dtype):
    """The write function on random pools, every start offset within a
    page at once (16 rows, offset = row), a window past the table's end
    among them, in the served dtype too (a bf16 page is one packed tile)."""
    K, L, psz, hd = 2, 3, 16, 128
    B = psz
    p_max = -(-(S + psz) // psz)
    N = B * p_max + 1
    pool = jax.random.normal(jax.random.PRNGKey(S), (K, L, N, psz, hd), jnp.dtype(dtype))
    new = jax.random.normal(jax.random.PRNGKey(S + 1), (B, S, K, hd), jnp.float32)
    table = jnp.arange(1, N, dtype=jnp.int32).reshape(B, p_max)
    # rows 0-7 start in their first page; rows 8-15 in their second, where
    # the later ones overhang the table's last column
    positions = jnp.arange(B, dtype=jnp.int32) + jnp.where(jnp.arange(B) >= 8, psz, 0)
    layer = jnp.asarray(1, jnp.int32)
    window = _kv_window(positions, table, S, psz, N)
    got = jax.jit(_write_kv_window)(pool, layer, new, window)
    want = jax.jit(_flat_scatter_oracle(positions, table, S))(pool, layer, new, None)
    np.testing.assert_array_equal(
        np.asarray(got[:, :, 1:].astype(jnp.float32)),
        np.asarray(want[:, :, 1:].astype(jnp.float32)),
    )
    # the other layers are untouched, bit for bit
    np.testing.assert_array_equal(
        np.asarray(got[:, 0].astype(jnp.float32)), np.asarray(pool[:, 0].astype(jnp.float32))
    )


def test_window_pages_are_static_in_the_window_width():
    """P = cdiv(S - 1, psz) + 1: 2 at the segment's S = 8, 9 at a 128-wide
    suffix prefill; a column past the table is routed out of range."""
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    for S, n_win in ((1, 1), (8, 2), (16, 2), (17, 2), (18, 3), (128, 9)):
        pages, slot, live = _kv_window(jnp.asarray([0, 63], jnp.int32), table, S, 16, 99)
        assert pages.shape == (2, n_win) and live.shape == (2, n_win, 16)
        assert slot.shape == (2, n_win * 16)
        assert int(live.sum()) == 2 * S  # the table's end is the pages' business, not the mask's
        assert int(pages[0, 0]) == 1 and int(pages[1, 0]) == 8
        assert all(int(p) == 99 for p in pages[1, 1:])  # past the table: dropped


# ------------------------------------- commit, then write; who owns a page
def test_commit_and_decode_write_roundtrip():
    cfg = GemmaConfig(dtype="float32", n_layers=2, n_kv_heads=2, head_dim=16)
    psz, n_pages, B, T = 4, 16, 2, 8
    paged = init_paged_kv(cfg, n_pages, psz)
    dense = {
        "k": jax.random.normal(jax.random.PRNGKey(1), (2, B, T, 2, 16)),
        "v": jax.random.normal(jax.random.PRNGKey(2), (2, B, T, 2, 16)),
    }
    table = jnp.array([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    seq_lens = jnp.array([T, 5])
    paged = commit_prefill_to_pages(paged, dense, table, seq_lens, psz)
    # Page 1 holds seq0 chunk0, page 2 chunk1.
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 0, 1]),  # [K, psz, hd]
        np.asarray(dense["k"][0, 0, :psz].transpose(1, 0, 2)),
    )
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 1, 4]),
        np.asarray(dense["k"][1, 1, psz:].transpose(1, 0, 2)),
    )
    # Decode write at position 5 for seq1 -> page 4 slot 1.
    k_new = jax.random.normal(jax.random.PRNGKey(3), (2, B, 2, 16))
    v_new = jax.random.normal(jax.random.PRNGKey(4), (2, B, 2, 16))
    window = _kv_window(jnp.array([8, 5]), table, 1, psz, n_pages)
    for layer in range(2):
        paged = {
            "k": _write_kv_window(paged["k"], layer, k_new[layer][:, None], window),
            "v": _write_kv_window(paged["v"], layer, v_new[layer][:, None], window),
        }
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 0, 4, 1]), np.asarray(k_new[0, 1])
    )
    np.testing.assert_allclose(
        np.asarray(paged["v"][:, 1, 4, 1]), np.asarray(v_new[1, 1])
    )
    # seq1's prefill rows in the same page are still there
    np.testing.assert_allclose(
        np.asarray(paged["k"][:, 1, 4, 0]), np.asarray(dense["k"][1, 1, psz])
    )


def test_allocator_invariants():
    a = PageAllocator(n_pages=32, page_size=8, max_pages_per_seq=8)
    p1 = a.allocate(1, 20)  # 3 pages
    assert len(p1) == 3
    p2 = a.allocate(2, 1)
    assert len(p2) == 1
    a.check_invariants()
    grown = a.extend(1, 40)  # 5 pages
    assert len(grown) == 5
    a.check_invariants()
    a.free(1)
    a.free(1)  # double-free is a no-op
    a.check_invariants()
    stats = a.stats()
    assert stats.sequences == 1
    assert stats.free_pages == 31 - 1  # only seq 2's single page held
    with pytest.raises(EngineError, match="already has pages"):
        a.allocate(2, 4)


def test_allocator_exhaustion():
    a = PageAllocator(n_pages=4, page_size=8, max_pages_per_seq=8)
    a.allocate(1, 24)  # 3 pages = all available
    assert not a.can_allocate(1)
    with pytest.raises(EngineError, match="out of KV pages"):
        a.allocate(2, 1)
    a.free(1)
    assert a.can_allocate(24)


def test_allocator_respects_max_pages_per_seq():
    a = PageAllocator(n_pages=64, page_size=8, max_pages_per_seq=2)
    with pytest.raises(EngineError, match="max_pages_per_seq"):
        a.allocate(1, 100)


def _free_then_allocate(a):
    first = a.allocate("a", 20 * 8)
    a.free("a")
    return first, a.allocate("b", 20 * 8)


def _interleaved_frees(a):
    for sid, pages in (("a", 5), ("b", 7), ("c", 3), ("d", 9)):
        a.allocate(sid, pages * 8)
    for sid in ("c", "a", "d"):  # b (pages 6..12) stays
        a.free(sid)
    return list(range(1, 6)) + list(range(13, 29)), a.allocate("e", 21 * 8)


def _split_then_free(a):
    a.allocate("a", 10 * 8)
    a.allocate("hold", 8)  # page 11
    head = a.split("a", "head", 4)
    a.free("a")  # the remainder 5..10 first, then the head 1..4
    a.free("head")
    assert head == [1, 2, 3, 4]
    return list(range(1, 11)) + [12, 13], a.allocate("b", 12 * 8)


def _extend_after_frees(a):
    a.allocate("a", 3 * 8)
    a.allocate("b", 2 * 8)
    a.allocate("c", 3 * 8)
    a.free("b")  # 4, 5
    return [6, 7, 8, 4, 5, 9, 10], a.extend("c", 7 * 8)


@pytest.mark.parametrize(
    "scenario", [_free_then_allocate, _interleaved_frees, _split_then_free, _extend_after_frees],
    ids=lambda f: f.__name__.strip("_"),
)
def test_allocator_hands_out_the_lowest_free_ids_ascending(scenario):
    """Whatever was freed and in what order, an allocation (and what
    ``extend`` adds) takes the lowest free ids, ascending: pages lie side by
    side in the pools wherever the free ids do."""
    a = PageAllocator(n_pages=64, page_size=8, max_pages_per_seq=32)
    want, got = scenario(a)
    assert got == want
    a.check_invariants()


def test_a_head_built_in_chunks_after_the_warm_ups_rows_were_freed_is_one_run():
    """deepseek's cell: the warm-up fills rows of 448 pages and frees them,
    then the first plan builds the catalogue head in chunks of 1,024 tokens,
    each a sequence of its own (``_ensure_prefix``): their pages, chunk after
    chunk in a row's table, are one ascending run, so every whole key block
    of the head is fetched in one copy."""
    import random

    from mcpx.engine.kernels.paged_attention import page_run_flags

    a = PageAllocator(n_pages=8 * 512 + 1, page_size=16, max_pages_per_seq=512)
    rows = list(range(8))
    for r in rows:
        a.allocate(("warm", r), 7168)
    random.Random(0).shuffle(rows)
    for r in rows:
        a.free(("warm", r))
    a.allocate(("tree", 0), 3 * 16)  # a short node the warm plans left resident
    head = []
    for chunk, tokens in enumerate([1024] * 6 + [41 * 16]):
        head += a.allocate(("head", chunk), tokens)
    assert head == list(range(head[0], head[0] + 425))
    own = a.allocate(("row", 0), 6 * 16)
    table = np.zeros((1, 512), np.int32)
    table[0, : len(head) + len(own)] = head + own
    flags = np.asarray(page_run_flags(jnp.asarray(table), 16, a.n_pages))[0]
    # (the heap keeps handing out what follows: the row's own pages extend the run)
    assert flags[:26].all() and not flags[27:].any()
    a.check_invariants()


def test_allocator_free_costs_the_pages_freed_not_the_pool():
    """``free`` (and ``allocate``) work on the pages they move, log(pool)
    each: no sort and no scan of the free list, which a pool of a million
    pages would show as milliseconds."""
    import time

    a = PageAllocator(n_pages=1_000_001, page_size=16, max_pages_per_seq=64)
    best = float("inf")
    for i in range(5):
        a.allocate(i, 16 * 16)
        a.allocate(("hold", i), 16)
        t0 = time.perf_counter()
        a.free(i)
        best = min(best, time.perf_counter() - t0)
    assert best < 1e-3
    assert a.allocate("again", 16 * 16)[:3] == [1, 2, 3]


# --------------------------------------------------------- structure
# The benchmark's slab (benchmarks/chip/configs/olmo2-1b.json): 8 rows, a
# page table 32 wide over a 257-page pool, MHA 16/16 at head_dim 128.
_SLAB = dict(B=8, p_max=32, psz=16)
_OLMO = dict(
    vocab_size=3072, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=16,
    head_dim=128, d_ff=8192, rope_theta=500000.0, dtype="bfloat16",
)


def _slab_args(cfg, S, sharding=None):
    B, p_max, psz = _SLAB["B"], _SLAB["p_max"], _SLAB["psz"]

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
        )

    params = sds(jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pools = sds(jax.eval_shape(lambda: init_paged_kv(cfg, B * p_max + 1, psz)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)  # noqa: E731
    return params, i32(B, S), i32(B), i32(B, p_max), pools, i32(B)


def _slab_step(cfg, mesh):
    def step(params, tokens, positions, table, pools, q_lens):
        return decode_chunk_paged(
            params, cfg, tokens, positions, table, pools, use_pallas=True,
            interpret=False, mesh=mesh, logits_at=jnp.maximum(q_lens - 1, 0),
            q_lens=q_lens,
        )

    return step


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


@pytest.mark.parametrize("S", [1, 8, 128])
def test_scan_body_never_sees_a_flat_pool_and_scatters_whole_pages(S):
    """Traced at the slab's shape: no operation in the layer scan has an
    operand or result of the pool's flat ``[K, L, N*psz, hd]`` shape, and
    every scatter into the pool writes whole ``[psz, hd]`` windows (two
    pools x one scatter a layer). A row-granular write, whichever view it
    goes through, fails here before it costs a relayout on the chip."""
    cfg = GemmaConfig(**_OLMO)
    mesh = make_mesh(data=1, model=1, devices=jax.devices()[:1])
    args = _slab_args(cfg, S)
    K, L, N, psz, hd = args[4]["k"].shape
    jaxpr = jax.make_jaxpr(_slab_step(cfg, mesh))(*args).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.eqns]
    pool_scatters = 0
    for eqn in _walk(scans[0].params["jaxpr"].jaxpr):
        shapes = [
            tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval")
        ]
        assert (K, L, N * psz, hd) not in shapes, f"flat pool view in the scan body: {eqn}"
        if eqn.primitive.name.startswith("scatter") and shapes[0] == (K, L, N, psz, hd):
            pool_scatters += 1
            dn = eqn.params["dimension_numbers"]
            updates = eqn.invars[2].aval.shape
            # the page's two dimensions are window dimensions, at full size
            assert 3 not in dn.inserted_window_dims and 4 not in dn.inserted_window_dims, dn
            assert 3 not in dn.scatter_dims_to_operand_dims, dn
            assert 4 not in dn.scatter_dims_to_operand_dims, dn
            assert tuple(updates[d] for d in dn.update_window_dims)[-2:] == (psz, hd), updates
        if eqn.primitive.name == "dynamic_update_slice":
            assert shapes[0] != (K, L, N, psz, hd) or shapes[1][-2:] == (psz, hd), eqn
    assert pool_scatters == 2, pool_scatters


# ------------------------------------------- compiled for a described v5e
@pytest.fixture(scope="module")
def one_v5e():
    """One chip of a DESCRIBED v5e:2x2 (nothing attached, nothing runs):
    the TPU compiler is installed beside jax, so the layout it assigns the
    pool can be read on the CPU. Built here, not at import: only this
    test's process may load the TPU library."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    return mesh, NamedSharding(mesh, PartitionSpec())


def _compile_uncached(fn, *args, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` compiled for ``args`` with the persistent
    compilation cache off: a described device's executable cannot be read
    back from it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, **jit_kw).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("S", [8, 128])
def test_compiled_for_v5e_the_layer_loop_copies_no_pool(one_v5e, S):
    """The optimised HLO of the forward at the slab's shape, compiled for
    the v5e: no ``copy`` (or copy-start) anywhere in the module produces an
    array of the pool's shape in either view, and the program needs no
    pool-sized temporary. With the flat scatter this module held four such
    copies in the while body and 271 MB of temporaries."""
    mesh, replicated = one_v5e
    cfg = GemmaConfig(**_OLMO)
    args = _slab_args(cfg, S, sharding=replicated)
    K, L, N, psz, hd = args[4]["k"].shape
    compiled = _compile_uncached(_slab_step(cfg, mesh), *args, donate_argnums=(4,))
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program
    views = (f"[{K},{L},{N},{psz},{hd}]", f"[{K},{L},{N * psz},{hd}]")
    copies = [
        line.strip()[:160]
        for line in text.splitlines()
        if re.search(r"= \S+ copy(-start)?\(", line) and any(v in line.split(" copy")[0] for v in views)
    ]
    assert not copies, copies
    pool_bytes = K * L * N * psz * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


@pytest.mark.parametrize("H, p_max, selecting", [(64, 128, False), (128, 512, True)])
def test_compiled_for_v5e_the_latent_kernel_with_an_arm_a_rung(one_v5e, H, p_max, selecting):
    """The absorbed kernel's decode window at the published widths (8 slots x
    64 heads over a.x-k1's table, x 128 heads under deepseek's selection):
    Mosaic takes every rung's arm (a partly live tile slices the query block,
    the selection's rows and the output), which lowering alone cannot show."""
    import functools

    from mcpx.engine.kernels.paged_attention import latent_rungs, ragged_paged_attention_latent

    _, replicated = one_v5e
    B, S, r, w, L, n_pages, psz = 8, 8, 512, 128, 2, 33, 16
    bf, i32 = jnp.bfloat16, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=replicated)
    shapes = [
        sd((B, S, H, r), bf), sd((B, S, H, w), bf), sd((1, L, n_pages, psz, w + (128 if selecting else 0)), bf),
        sd((1, L, n_pages, psz, r), bf), sd((B, p_max), i32), sd((B,), i32), sd((B,), i32), sd((), i32),
    ] + ([sd((B, S, p_max * psz), jnp.float32)] if selecting else [])
    assert latent_rungs(S) == (1, 2, 4, 8)
    compiled = _compile_uncached(functools.partial(ragged_paged_attention_latent, scale=0.13), *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_compiled_for_v5e_the_index_kernel_fetches_a_run_of_index_lanes(one_v5e):
    """The index kernel at the published widths (64 index heads x 128 behind
    the rotated key's 128 lanes, a table of 512 pages): Mosaic takes the run's
    one copy, a 16-page slice of the pool's page axis with the index lanes
    sliced out of every page row, beside the page-by-page loop."""
    import functools

    from mcpx.engine.kernels.paged_attention import lightning_indexer

    _, replicated = one_v5e
    B, S, Hi, di, L, n_pages, psz, p_max = 8, 8, 64, 128, 2, 33, 16, 512
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=replicated)
    compiled = _compile_uncached(
        functools.partial(lightning_indexer, topk=2048, lane0=128),
        sd((B, S, Hi, di), bf), sd((B, S, Hi), f32), sd((1, L, n_pages, psz, 128 + di), bf),
        sd((B, p_max), i32), sd((B,), i32), sd((B,), i32), sd((), i32), sd((B, p_max // 16), i32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def _unfused_results(text):
    """``(result type, op)`` of every instruction of an optimised HLO module
    that is not inside a fused computation: what the device runs as an op of
    its own, and writes out."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    computation, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            computation = head.group(1)
        elif computation not in fused and " = " in line:
            m = re.match(r"\s*\S+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", line)
            if m:
                out.append(m.groups())
    return out


@pytest.mark.parametrize("S", [8, 64])
def test_compiled_for_v5e_wo_at_128_heads_is_read_out_of_the_stack(one_v5e, S):
    """Latent attention at deepseek's widths (128 heads x 128 x 7,168: 235 MB
    of ``wo`` a layer), the decode window and the 64-slot suffix prefill: no
    op of its own produces a layer's ``wo`` (with the slice reshaped inside
    the scan body it was an HBM -> HBM copy that the dot read again: PERF.md,
    PR 46), the stack is not copied, the program's temporaries are under
    one slice, and the stack itself is an operand of the fusion that returns
    the branch's ``[B, S, D]``."""
    mesh, replicated = one_v5e
    cfg = GemmaConfig(
        vocab_size=3072, d_model=7168, n_layers=3, n_heads=128, n_kv_heads=1, head_dim=128, d_ff=2048,
        attention="latent", q_lora_rank=1536, kv_lora_rank=512, qk_rope_head_dim=64, v_head_dim=128,
        yarn_factor=40.0, yarn_original_max_pos=4096, attn_score_factor=1.8739, activation="silu",
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False,
    )
    args = _slab_args(cfg, S, sharding=replicated)
    n, H, dv, D = args[0]["layers"]["wo"].shape
    assert (n, H, dv, D) == (3, 128, 128, 7168)  # the tree keeps its heads
    compiled = _compile_uncached(_slab_step(cfg, mesh), *args, donate_argnums=(4,))
    text = compiled.as_text()
    slice_types = {f"bf16[{lead}{dims}]" for lead in ("", "1,") for dims in (f"{H},{dv},{D}", f"{H * dv},{D}")}
    stack_types = {f"bf16[{n},{H},{dv},{D}]", f"bf16[{n},{H * dv},{D}]"}
    views = ("parameter", "get-tuple-element", "bitcast")  # names of a buffer, not ops that write one
    written = [
        (type_, op) for type_, op in _unfused_results(text)
        if type_ in slice_types | stack_types and op not in views
    ]
    assert not written, written
    assert compiled.memory_analysis().temp_size_in_bytes < H * dv * D * 2 * 2 // 3
    B = _SLAB["B"]
    assert re.search(
        rf"%fused_computation[\w.\-]* \([^)]*bf16\[{n},{H * dv},{D}\][^)]*\) -> \(?[^{{]*bf16\[{B},{S},{D}\]", text
    ), "no fusion takes the stacked wo and returns the branch"


def test_compiled_for_v5e_the_state_pools_kernel_and_the_two_matrix_experts(one_v5e):
    """The sparse hybrid cell's two kernels at the published widths, compiled
    by Mosaic for the v5e (lowering alone cannot show a block Mosaic will not
    tile, nor a product's precision it will not take): ``ssm_window`` on a
    pool of 5 layers x 8 slots x 128 x 8,192 float32 with 8 rows' decode windows of 8
    slots (both products at ``highest``), and ``routed_experts`` with no gate
    on a 1,024-wide latent, 128 experts of 2,688 held."""
    import functools

    from mcpx.engine.kernels.routed_experts import routed_experts
    from mcpx.engine.kernels.ssm import _blocking, ssm_window

    _, replicated = one_v5e
    f32, bf, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=replicated)
    B, W, S, G, N, M = 8, 8, 8, 8, 128, 8192
    assert _blocking(M // G, N) == 1024  # a whole group's lanes a step
    shapes = [sd((5, 8, N, M), f32), sd((B,), i32), sd((B,), i32), sd((B, M), f32), sd((B, W, M), f32),
              sd((B, G, N, W), f32), sd((B, G, S, N), f32)]

    def forwards(pool, *window):
        # as the segment holds it: the donated pool carried through a loop of forwards
        def body(c):
            pool, acc = c[1], c[2]
            for layer in range(5):
                pool, hc = ssm_window(pool, layer, *window)
                acc = acc + hc
            return c[0] + 1, pool, acc

        return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, pool, jnp.zeros((B, S, M), f32)))[1:]

    compiled = _compile_uncached(forwards, *shapes, donate_argnums=(0,))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the pool is updated in place: no second pool among the temporaries, and
    # it is nowhere in VMEM (a layer's pool of its own that fits there was
    # staged whole around every call: 67 MB a layer a forward)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * N * M * 4 // 8
    assert not re.search(rf"f32\[5,8,{N},{M}\]\{{[^}}]*S\(1\)", text)
    D, F, E = 1024, 2688, 128
    relu2 = lambda a: jnp.square(jax.nn.relu(a))
    experts = lambda x, c, up, down, *s: routed_experts(x, c, None, up, down, *s, act=relu2)
    shapes = [sd((64, D), bf), sd((64, E), f32), sd((5, E, D, F), bf), sd((5, E, F, D), bf),
              sd((E,), i32), sd((), i32), sd((), i32)]
    assert "tpu_custom_call" in _compile_uncached(experts, *shapes).as_text()


# name: (the router's width, the experts the router scores) beside what
# ``tests/test_routed_experts_kernel.py::TILE_SHAPES`` says of the cell's widest window.
TILE_CELLS = {
    "trinity-mini": (2048, 128), "a.x-k1": (7168, 192), "deepseek-v3.2-exp": (7168, 256),
    "nemotron-3-super": (4096, 512),
}


def _sparse_prefill_layer(one_v5e, cell, T, Dm, E_all):
    """``moe_forward(use_pallas=True, prefill=True)`` at ``cell``'s published
    widths over a window of ``T`` slots, compiled for the described v5e."""
    import functools

    from mcpx.models.gemma import moe
    from tests.test_routed_experts_kernel import TILE_SHAPES

    _, replicated = one_v5e
    _, k, D, F, E, gated = TILE_SHAPES[cell]
    block = dict(d_ff=128) if gated else dict(  # two-matrix experts in a latent: a layer_pattern model's
        d_ff=0, layer_pattern="ME", mamba_n_heads=8, mamba_head_dim=16, mamba_n_groups=2, ssm_state_size=32,
        d_shared_expert=128, moe_latent_size=D, router_scoring="sigmoid", rope_full_layers=False, activation="relu2",
    )
    cfg = GemmaConfig(
        vocab_size=384, d_model=Dm, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=128,
        n_experts=E_all, n_experts_per_tok=k, d_expert=F, expert_first=0, experts_held=E,
        tie_embeddings=False, scale_embeddings=False, norm_plus_one=False, dtype="bfloat16", **block,
    )
    bf = jnp.bfloat16
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=replicated)
    leaves = {"w_up": sd((2, E, D, F), bf), "w_down": sd((2, E, F, D), bf)}
    if gated:
        leaves["w_gate"] = sd((2, E, D, F), bf)

    def layer(h, rows, router, experts, live):
        return moe.moe_forward(
            h, router, experts, jnp.int32(1), cfg, live, rows=rows, use_pallas=True, prefill=True)[:2]

    rows = None if D == Dm else sd((1, T, D), bf)
    return _compile_uncached(layer, sd((1, T, Dm), bf), rows, sd((Dm, E_all), bf), leaves, sd((1, T), jnp.bool_))


@pytest.mark.parametrize("cell", list(TILE_CELLS))
def test_compiled_for_v5e_a_sparse_prefill_past_the_ridge_is_one_tile_kernel(one_v5e, cell):
    """``moe_forward(use_pallas=True)`` at a sparse cell's published widths and
    its widest prefill window, compiled by Mosaic for the v5e: what lowering
    alone cannot show (a one-row slab that a DMA may slice, a tile's copy out
    at a start Mosaic can prove whole sublane tiles, 65,536 sorted rows'
    tokens in SMEM). ONE custom call, and beside its float32 result ``ys``
    (a row a (slot, choice) pair) no second buffer of that many rows: no
    sorted copy, no zero-fill, no gather-back."""
    from mcpx.models.gemma import moe
    from tests.test_routed_experts_kernel import TILE_SHAPES

    (T, k, D, F, E, gated), (Dm, E_all) = TILE_SHAPES[cell], TILE_CELLS[cell]
    compiled = _sparse_prefill_layer(one_v5e, cell, T, Dm, E_all)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "prefill_expert_tiles" in text
    A = moe.TILE_ALIGN
    ys = ((T * k + E * (A - 1) + A - 1) // A * A + moe.GROUP_TILE) * D * 4
    assert ys <= compiled.memory_analysis().temp_size_in_bytes < 1.25 * ys


# The cells whose whole prompts the cohort table's small bucket takes (ISSUE
# 55): name -> (the router's width, the experts the router scores).
SMALL_COHORT_CELLS = {
    "mellum2-12b-a2.5b": (2304, 64), "trinity-mini": (2048, 128), "nemotron-3-super": (4096, 512),
}


@pytest.mark.parametrize("cell", list(SMALL_COHORT_CELLS))
def test_compiled_for_v5e_a_cohort_of_fours_sparse_prefill(one_v5e, cell):
    """The window a 4-row cohort bucket adds to a sparse cell's warm-up, 4 x
    128 slots, compiled by Mosaic at the published widths: past the ridge, so
    the tile kernel at half its widest window. One custom call."""
    text = _sparse_prefill_layer(one_v5e, cell, 4 * 128, *SMALL_COHORT_CELLS[cell]).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "prefill_expert_tiles" in text
