"""Ragged mixed-phase paged-attention kernel (Pallas TPU) + jnp reference.

ONE kernel serves every attention shape the engine dispatches against the
shared page pool (``mcpx.engine.kv_cache`` layout: kv-head-major, all
layers in one array — ``[K, L, N_pages, page_size, head_dim]``; the kernel
streams one layer's slice selected by a prefetched scalar, so the decode
loop can carry the pools through ``lax.scan``). The batch is a RAGGED slab
(see README.md in this package): row ``b`` holds ``q_lens[b]`` live
queries of the padded ``[B, S_max, ...]`` window —

  - **suffix-prefill rows**: ``S_i`` new tokens attending the resident
    prefix pages plus themselves (intra-chunk causal),
  - **plain decode rows**: ``S = 1``,
  - **speculative verify rows**: a ``[K+1]`` draft window,
  - **idle rows** (done / cohort padding): ``q_lens[b] == 0`` — the
    program streams zero pages and writes zeros.

Per-row ``q_len`` / ``start_pos`` / page tables are scalar-prefetched
DATA, so one compiled launch serves any prefill/decode/spec mix — compile
count is a function of the padded window shape alone (the Ragged Paged
Attention design, PAPERS.md). Grid is ``(B, cdiv(K, H_BLK), cdiv(S, Sq))``:
a program holds ``H_BLK`` KV heads of its row and walks the row's pages a
BLOCK of ``P_BLK`` pages at a time — each page its own HBM→VMEM DMA for all
of the program's heads (the table scatters pages), a block's DMAs all in
flight at once and the next block's issued before this one is multiplied —
accumulating flash-style (online softmax in fp32). ``_blocking`` derives
``H_BLK`` and ``P_BLK`` from the shapes under ``VMEM_BUDGET``: every local
head in a decode window, as few as fit in a 128-query prefill block; a lane
width of keys a step (8 pages of 16), two where the heads leave room. So
  - no ``[B, S_max]`` dense cache is ever materialised (ragged batches share
    the pool — the RPA paper's point, PAPERS.md),
  - a row streams only ``cdiv(start + q_len, page_size)`` pages — a decode
    row pays decode traffic even when batched next to a prefill row; pages
    of a block past the row's last are neither fetched nor seen,
  - a key block is ``[H_BLK, P_BLK * page_size, head_dim]`` — page rows
    contiguous, lane-aligned (head_dim multiple of 128; heads of 64 lie two
    to a pool row, ``GemmaConfig.kv_pack``, and the kernel sees a head of 128
    with the softmax ``scale`` of 64), no in-kernel transposes,
  - arithmetic is one batched product over the program's heads,
    ``q [H, S*G, hd] @ k.T -> [H, S*G, P_BLK * page_size]`` (operands as
    stored, float32 out) then ``p @ v -> [H, S*G, hd]`` (float32): MXU
    matmuls with GQA group size G rows; a cell's 85-135-token context is
    one such step.

The jnp reference implements identical semantics by gathering pages; kernel
tests assert exact agreement in interpret mode on CPU (SURVEY.md §4.2) and
on real TPU in the benchmark harness — tier-1 exercises the same kernel
body TPUs run.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------- reference
def ragged_paged_attention_reference(
    q: jax.Array,  # [B, S, K, G, hd] — padded query windows
    k_pages: jax.Array,  # [K, L, N, Psz, hd] — all layers
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, Pmax] int32
    start_pos: jax.Array,  # [B] int32 — cache position of query 0
    q_lens: jax.Array,  # [B] int32 — live queries per row (0 = idle row)
    layer: jax.Array | int = 0,
    window: "jax.Array | int | None" = None,
    scale: "float | None" = None,  # the softmax scale (None: hd ** -0.5)
) -> jax.Array:
    """Ragged mixed-phase semantics, pure jnp: row ``b``'s queries at
    window index ``i < q_lens[b]`` attend through cache position
    ``start_pos[b] + i`` (itself + earlier window tokens, already written to
    the pools), and under a ``window`` no further back than the
    ``window - 1`` positions before itself; queries at ``i >= q_lens[b]``
    are pads and output exactly ZERO — the kernel's idle-row/pad contract,
    pinned here so the interpret-parity tests cover pads too, not just the
    positions the callers happen to read. Gathers each row's pages ONCE for
    all S queries — folding the window into the batch dim instead would
    re-gather the same pages S times, which at window width 8 is 8x the HBM
    traffic of this formulation (the dominant cost of jnp-path decode).
    Returns [B, S, K, G, hd] in q.dtype."""
    B, S, K, G, hd = q.shape
    _, _, _, psz, _ = k_pages.shape
    p_max = page_table.shape[1]
    L = p_max * psz
    k = k_pages[:, layer][:, page_table].transpose(1, 0, 2, 3, 4).reshape(B, K, L, hd)
    v = v_pages[:, layer][:, page_table].transpose(1, 0, 2, 3, 4).reshape(B, K, L, hd)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bskgh,bklh->bskgl", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    vis = start_pos[:, None] + jnp.arange(S) + 1  # [B, S]
    mask = jnp.arange(L)[None, None, :] < vis[:, :, None]  # [B, S, L]
    if window is not None:
        mask &= jnp.arange(L)[None, None, :] >= vis[:, :, None] - window
    logits = jnp.where(mask[:, :, None, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgl,bklh->bskgh", weights.astype(v.dtype), v)
    valid = jnp.arange(S)[None, :] < q_lens[:, None]  # [B, S]
    return jnp.where(valid[:, :, None, None, None], out, 0).astype(q.dtype)


# ------------------------------------------------------------------- kernel
def _ragged_n_pages(start, qn, page_size: int, p_max: int):
    """Pages a row streams: through its LAST LIVE query's visible position
    (``start + qn``), clamped to the table width (a finished row's frozen
    start + window may overhang its allocation — the caller reserves slack
    for the garbage writes, but the table has no column past ``p_max``).
    An idle row (``qn == 0``) streams EXACTLY ZERO pages — without the
    gate it would still DMA its whole frozen history (``cdiv(start,
    psz)`` pages of dead traffic per kv-head per layer per forward, and
    done rows ride many forwards under the fused dispatch window).
    Factored out of the kernel so the zero-page idle contract is directly
    unit-testable — from the outputs alone, streamed-then-masked and
    never-streamed are indistinguishable (that indistinguishability is
    the masking's correctness argument)."""
    n = jnp.minimum(pl.cdiv(start + qn, page_size), p_max)
    return jnp.where(qn > 0, n, 0)


# What the blocking may spend, counted in ``_blocking``: the page buffers in
# flight, the pipelined q/out blocks, the three carries and one score tile.
# Mosaic's default scoped-VMEM limit is 16 MiB; the rest is left to what the
# compiler materialises beside them (the float32 copy of a value block, the
# softmax weights, the masks), which are of the counted tiles' sizes.
VMEM_BUDGET = 6 * 2**20
# Keys a compute step multiplies: a lane width of scores, and a second one
# where the program's heads still fit (``_blocking``).
KEY_BLOCK = 128
# Queries per kernel program: a wider window runs as blocks of this many
# (a whole 1024-token prefill window's carries fit no program). Each query
# block re-streams the row's pages up to its own last query (PERF.md, open
# questions).
Q_BLOCK = 128


def _vmem_bytes(h_blk, p_blk, *, G, hd, page_size, sq, pool_itemsize, q_itemsize):
    """Bytes of VMEM one program holds at this blocking: K and V buffers of
    two blocks in flight, the q and out blocks (double-buffered by the
    pipeline), the three float32 carries (m and l one lane-padded column
    each) and one float32 score tile."""
    rows, keys = sq * G, p_blk * page_size
    buffers = 2 * 2 * h_blk * keys * hd * pool_itemsize
    q_out = 2 * 2 * h_blk * rows * hd * q_itemsize
    carries = h_blk * rows * (hd + 2 * 128) * 4
    scores = h_blk * rows * max(keys, 128) * 4
    return buffers + q_out + carries + scores


def _blocking(K, G, hd, page_size, sq, pool_itemsize, q_itemsize, p_max):
    """``(H_BLK, P_BLK)``: KV heads a program holds and key pages a compute
    step covers, from the shapes alone. ``P_BLK`` pages make a lane width
    of keys (``KEY_BLOCK``), no more than the table is wide; ``H_BLK`` is
    every local head where that fits ``VMEM_BUDGET`` (a decode window), else
    the heads split evenly over the fewest programs that do (a 128-query
    prefill block at a wide head takes one). Heads come first: a program
    more costs a row a DMA round trip nothing overlaps, a step more does
    not. What room the heads leave goes to a second lane width of keys
    (the cells' decode windows: a 135-token context is then one step). A
    layout whose single head does not fit halves the key block instead."""
    size = functools.partial(
        _vmem_bytes, G=G, hd=hd, page_size=page_size, sq=sq,
        pool_itemsize=pool_itemsize, q_itemsize=q_itemsize,
    )
    p_blk = max(1, min(KEY_BLOCK // page_size, p_max))
    while p_blk > 1 and size(1, p_blk) > VMEM_BUDGET:
        p_blk //= 2
    fit = max([h for h in range(1, K + 1) if size(h, p_blk) <= VMEM_BUDGET], default=1)
    h_blk = pl.cdiv(K, pl.cdiv(K, fit))
    if p_blk * page_size == KEY_BLOCK and size(h_blk, 2 * p_blk) <= VMEM_BUDGET:
        p_blk = min(2 * p_blk, p_max)
    return h_blk, p_blk


def _ragged_kernel(
    *refs, page_size: int, p_blk: int, n_heads: int, windowed: bool, scale: "float | None" = None
):
    """``refs``: the scalar prefetch — page_table [B, Pmax], start_pos [B],
    q_lens [B] (live queries per row; 0 = idle row), layer [1] (which
    layer's pool slice to stream) and, ``windowed``, window [1] (this
    call's attention window) — all SMEM; then the blocks q [1, Sq, H, G, hd]
    VMEM (one query block of the window, H = H_BLK heads), k_pages /
    v_pages [K, L, N, Psz, hd] ANY (they stay in HBM), out [1, Sq, H, G, hd]
    VMEM; then the scratch k_buf / v_buf [2, H, P_BLK * Psz, hd] VMEM (two
    key blocks in flight) and their DMA semaphores [2, 2]."""
    page_table_ref, start_pos_ref, q_lens_ref, layer_ref = refs[:4]
    window_ref = refs[4] if windowed else None
    q_ref, k_pages_ref, v_pages_ref, out_ref, k_buf, v_buf, sem = refs[4 + windowed :]
    b = pl.program_id(0)
    hb = pl.program_id(1)
    layer = layer_ref[0]
    S, H, G, hd = q_ref.shape[1:]
    keys = p_blk * page_size
    # Query block j of the row's window is itself a ragged window: it
    # starts S*j positions further into the cache and holds whatever part
    # of the row's live queries falls inside it. Everything below is the
    # whole-window kernel applied to that sub-window, so the carries and
    # the q/out blocks stay [H, S*G, hd] however long a prefill window is.
    q0 = pl.program_id(2) * S
    start = start_pos_ref[b] + q0
    qn = jnp.clip(q_lens_ref[b] - q0, 0, S)
    # The block's LAST LIVE query attends through position start+qn-1, so
    # only pages up to that position stream in — a decode row (qn=1) next
    # to a prefill row (qn=S) pays decode-sized page traffic, and an idle
    # row or an all-pad block (qn=0) streams nothing (see _ragged_n_pages)
    # and falls through to the zero output.
    n_pages = _ragged_n_pages(start, qn, page_size, page_table_ref.shape[1])
    # Under a window the block's FIRST query sees no key before position
    # start - window + 1, and a later query sees none earlier still: pages
    # wholly before it are never streamed, and the key blocks are counted
    # from the first page it can see. (No window: every page from 0.)
    first = 0
    if windowed:
        window = window_ref[0]
        first = jnp.minimum(jnp.maximum(start - window + 1, 0) // page_size, n_pages)
    n_stream = n_pages - first
    n_blocks = pl.cdiv(n_stream, p_blk)

    # The heads of this program, gathered head-major: [H, S*G, hd]. The
    # q @ k.T product takes both operands as they are stored where they
    # are stored alike (two bfloat16 values multiply exactly in float32).
    q = jnp.stack([q_ref[0, :, h].reshape(S * G, hd) for h in range(H)])
    if q.dtype != k_buf.dtype:
        q = q.astype(jnp.float32)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    # Visible length per q row r (row r is query r//G): start + r//G + 1;
    # pad queries (r//G >= qn) see nothing and zero out below.
    row_q = lax.broadcasted_iota(jnp.int32, (S * G, 1), 0) // G
    q_valid = row_q < qn  # [S*G, 1]
    vis = start + row_q + 1  # [S*G, 1]

    # A page is its own DMA (the table scatters them), for all of the
    # program's heads at once: H runs of one page each. The last head block
    # of a K that H does not divide copies only the heads there are.
    tail = n_heads % H
    head_counts = [(H, None)] if not tail else [
        (H, hb < pl.num_programs(1) - 1), (tail, hb == pl.num_programs(1) - 1)
    ]

    def page_copies(slot, blk, p, nh):
        page = page_table_ref[b, first + blk * p_blk + p]
        rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
        heads = pl.ds(hb * H, nh)
        return [
            pltpu.make_async_copy(
                pages.at[heads, layer, page], buf.at[slot, pl.ds(0, nh), rows], sem.at[i, slot]
            )
            for i, (pages, buf) in enumerate(((k_pages_ref, k_buf), (v_pages_ref, v_buf)))
        ]

    def each_page(slot, blk, act):
        """``act`` (start / wait) on the DMAs of block ``blk``'s pages that
        the row streams; returns how many those are."""
        n_here = jnp.minimum(n_stream - blk * p_blk, p_blk)

        def one(p, carry):
            for nh, here in head_counts:
                def go(nh=nh):
                    for copy in page_copies(slot, blk, p, nh):
                        act(copy)

                if here is None:
                    go()
                else:
                    pl.when(here)(go)
            return carry

        lax.fori_loop(0, n_here, one, 0)
        return n_here

    def start_block(slot, blk):
        n_here = each_page(slot, blk, operator.methodcaller("start"))

        # Pages of the block past the row's last are not fetched. Their keys
        # are masked by position; their values meet a weight of exactly 0,
        # which only a finite value leaves 0: what the buffer held before
        # this kernel ran is anything.
        def blank(p, carry):
            rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            v_buf[slot, :, rows] = jnp.zeros((H, page_size, hd), v_buf.dtype)
            return carry

        lax.fori_loop(n_here, p_blk, blank, 0)

    @pl.when(n_blocks > 0)
    def _():
        start_block(0, 0)

    def body(i, carry):
        m, l, acc = carry  # [H, S*G, 1], [H, S*G, 1], [H, S*G, hd] fp32
        slot = lax.rem(i, 2)

        # The next block's pages are in flight while this one is multiplied.
        @pl.when(i + 1 < n_blocks)
        def _():
            start_block(1 - slot, i + 1)

        each_page(slot, i, operator.methodcaller("wait"))
        k_tile = k_buf[slot]  # [H, keys, hd]
        if k_tile.dtype != q.dtype:
            k_tile = k_tile.astype(jnp.float32)
        v_tile = v_buf[slot].astype(jnp.float32)

        s = lax.dot_general(
            q, k_tile, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [H, S*G, keys]
        s = s * scale
        pos = (first + i * p_blk) * page_size + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        seen = q_valid & (pos < vis)  # [S*G, keys]
        if windowed:
            seen &= pos >= vis - window
        s = jnp.where(seen[None], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # Fully-masked rows (pad queries of a live row) keep m_new at
        # NEG_INF, where exp(s - m_new) would be exp(0) = 1 — guard so
        # their weights stay exactly 0 and the l == 0 fallthrough below
        # emits the reference's zeros (with no window live queries always
        # see block 0's position 0, so the guard never fires for them; under
        # a window a block's later queries may see nothing of its first
        # key blocks, and the guard keeps those tiles at weight 0 for them).
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))  # [H, S*G, keys]
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            p, v_tile, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((H, S * G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, S * G, 1), jnp.float32)
    acc0 = jnp.zeros((H, S * G, hd), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    out = jnp.where(l > 0.0, acc / jnp.maximum(l, 1e-30), 0.0)
    for h in range(H):
        out_ref[0, :, h] = out[h].reshape(S, G, hd).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "name", "scale"))
def ragged_paged_attention(
    q: jax.Array,  # [B, S, K, G, hd] — padded query windows
    k_pages: jax.Array,  # [K, L, N, Psz, hd] — all layers (stays in HBM)
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, Pmax]
    start_pos: jax.Array,  # [B] — cache position of query 0
    q_lens: jax.Array,  # [B] — live queries per row (0 = idle row)
    layer: jax.Array | int = 0,
    window: "jax.Array | int | None" = None,
    *,
    interpret: bool = False,
    name: "str | None" = None,
    scale: "float | None" = None,
) -> jax.Array:
    """The ragged mixed-phase kernel: grid (B, cdiv(K, H_BLK), cdiv(S, Sq));
    ONE program streams a row's pages once, a block of P_BLK pages a step,
    for H_BLK of its KV heads and a block of Sq <= Q_BLOCK of its queries
    (``_blocking`` derives both from the shapes). Decode, draft and verify
    windows are one query block. Row raggedness (``q_lens``) is
    scalar-prefetched DATA like the start offsets and page tables, so
    suffix-prefill, plain-decode and spec-verify rows share ONE launch of
    ONE executable per padded window shape — compile count is independent
    of the phase mix. The pools hold every layer ([K, L, ...]) so the
    decode loop can carry them through lax.scan and the kernel streams just
    ``layer``'s slice — slicing host-side would materialise a per-layer
    copy. The kernel only READS the pools: the window's own K/V rows are
    already in them when it is called, written by
    ``paged_decode._write_kv_window`` as whole pages in this same shape, so
    the pools reach the Mosaic call in the layout they are kept in and XLA
    copies nothing to reconcile the two.

    ``window`` (None: no window, and the program this always was) is this
    CALL's attention window, one more prefetched scalar beside ``layer``,
    so the layers of one scan may differ in it: a query at position p sees
    keys in (p - window, p]. The key blocks start at the first page the
    query block's first query can see.

    ``scale`` (None: ``hd ** -0.5``, and the program this always was) is the
    softmax scale where a pool row is wider than a head: heads of 64 lie TWO to
    a 128-lane row (``GemmaConfig.kv_pack``), each query padded with zeros over
    its row-mate's lanes, and the scale stays the head's own ``64 ** -0.5``."""
    B, S, K, G, hd = q.shape
    windowed = window is not None
    _, _, _, page_size, _ = k_pages.shape
    # A window up to Q_BLOCK is one block of its own width. A wider one runs
    # as Q_BLOCK-query blocks; the engine's prefill buckets past 128 are all
    # multiples of it, and any other width is padded up with dead queries
    # (past every row's q_len, so they stream nothing and are sliced off).
    sq = min(S, Q_BLOCK)
    s_pad = pl.cdiv(S, sq) * sq
    if s_pad != S:
        q = jnp.pad(q, ((0, 0), (0, s_pad - S), (0, 0), (0, 0), (0, 0)))
    h_blk, p_blk = _blocking(
        K, G, hd, page_size, sq, k_pages.dtype.itemsize, q.dtype.itemsize, page_table.shape[1]
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + windowed,
        grid=(B, pl.cdiv(K, h_blk), s_pad // sq),
        in_specs=[
            pl.BlockSpec(
                (1, sq, h_blk, G, hd), lambda b, h, j, *_: (b, j, h, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, sq, h_blk, G, hd), lambda b, h, j, *_: (b, j, h, 0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((2, h_blk, p_blk * page_size, hd), k_pages.dtype),
            pltpu.VMEM((2, h_blk, p_blk * page_size, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, p_blk=p_blk, n_heads=K, windowed=windowed,
        **({} if scale is None else {"scale": scale}),
    )
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),)
    if windowed:
        scalars += (jnp.asarray(window, jnp.int32).reshape(1),)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        **({"name": name} if name else {}),
    )(
        page_table.astype(jnp.int32),
        start_pos.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        *scalars,
        q,
        k_pages,
        v_pages,
    )
    return out[:, :S]


# ------------------------------------------------- latent (absorbed) kernel
# Rows of the score tile (queries x heads) a latent program holds: its three
# float32 carries are rows x (latent width + 2 lane columns), 1.5 MB at 512
# rows of a 512-wide latent.
LATENT_ROWS = 512
# Keys a latent step multiplies: two lane widths of scores.
LATENT_KEY_BLOCK = 256
# Scoped VMEM of a latent program whose score rows pass LATENT_ROWS.
LATENT_VMEM_LIMIT = 48 * 2**20


def latent_paged_attention_reference(
    q_latent: jax.Array,  # [B, S, H, r] — the queries in the latent's space
    q_rope: jax.Array,  # [B, S, H, w] — their rotated part, zeros past its width
    rope_pages: jax.Array,  # [1, L, N, Psz, w] — the shared rotated key
    latent_pages: jax.Array,  # [1, L, N, Psz, r] — the normed latent
    page_table: jax.Array,  # [B, Pmax]
    start_pos: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    select: "jax.Array | None" = None,  # [B, S, Pmax * Psz]: 0 a key the query reads, NEG_INF one it does not
    *,
    scale: float,
) -> jax.Array:
    """The absorbed form, pure jnp: one shared "KV head" whose key is the
    latent beside the rotated key and whose value is the latent again.
    ``score = (q_latent . c + q_rope . k_rope) * scale`` over the keys a
    query sees (the window contract of ``ragged_paged_attention_reference``;
    under ``select`` those of them it names, ``index_select``), ``out = sum_t
    p_t c_t`` [B, S, H, r]; pad queries output zeros. The rotated key is the
    first ``w`` values of its page row."""
    B, S, H, r = q_latent.shape
    psz = latent_pages.shape[3]
    n_keys = page_table.shape[1] * psz
    c = latent_pages[0, layer][page_table].reshape(B, n_keys, r)
    kr = rope_pages[0, layer][page_table].reshape(B, n_keys, -1)[..., : q_rope.shape[3]]
    logits = jnp.einsum("bshr,blr->bshl", q_latent, c, preferred_element_type=jnp.float32)
    logits += jnp.einsum("bshw,blw->bshl", q_rope, kr, preferred_element_type=jnp.float32)
    logits = logits * scale
    vis = start_pos[:, None] + jnp.arange(S) + 1  # [B, S]
    mask = jnp.arange(n_keys)[None, None, :] < vis[:, :, None]
    if select is not None:
        mask &= select > NEG_INF * 0.5
    logits = jnp.where(mask[:, :, None, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bshl,blr->bshr", weights.astype(c.dtype), c)
    valid = jnp.arange(S)[None, :] < q_lens[:, None]
    return jnp.where(valid[:, :, None, None], out, 0).astype(q_latent.dtype)


def latent_rungs(sq: int) -> tuple[int, ...]:
    """The query slots a latent program's score tile may hold, of a block of
    ``sq``: the powers of two under it, then the block."""
    return tuple(1 << i for i in range((sq - 1).bit_length())) + (sq,)


def latent_rung(qn, sq: int, rungs: "tuple[int, ...] | None" = None):
    """The rung a block with ``qn`` live slots takes (``qn`` a number, an
    array or a scalar inside the kernel): the lowest of ``latent_rungs(sq)``
    (of ``rungs``, which end in ``sq``, where a test compiles fewer arms)
    that holds them. A block with none takes the lowest, and streams nothing."""
    *lower, rung = rungs or latent_rungs(sq)
    for n in reversed(lower):
        rung = jnp.where(qn <= n, n, rung)
    return rung


def latent_query_slots(q_lens: jax.Array, S: int, H: int) -> jax.Array:
    """The query slots one ``ragged_paged_attention_latent`` call over a
    window of ``S`` slots x ``H`` heads multiplies for rows of ``q_lens`` [B]
    live queries: the rung of every query block that holds a live one."""
    sq = _latent_blocking(S, H, 1, 1)[0]  # the pages do not enter the query block
    qn = jnp.clip(q_lens[:, None] - jnp.arange(0, S, sq), 0, sq)  # [B, blocks]
    return jnp.sum(jnp.where(qn > 0, latent_rung(qn, sq), 0))


def latent_key_pages(page_size: int, p_max: int) -> int:
    """``P_BLK``: the pages one key block of the latent and the index kernel
    covers (``LATENT_KEY_BLOCK`` keys, or the whole of a narrower table)."""
    return max(1, min(LATENT_KEY_BLOCK // page_size, p_max))


def page_run_flags(page_table: jax.Array, page_size: int, n_pool_pages: int) -> jax.Array:
    """Which key blocks of a page table are RUNS: int32 [B, cdiv(Pmax, P_BLK)],
    1 where the block's ``P_BLK`` table columns hold ``a, a + 1, ..., a +
    P_BLK - 1`` (every step checked: distinct ids whose ends differ by
    ``P_BLK - 1`` may still be out of order) inside the pool, so that the
    pages lie side by side in a pool's layer and ONE DMA a pool fetches the
    block. Off the table alone: the same for every layer and for the latent,
    the selecting and the index kernel, so a forward computes it once,
    outside its layer scan. A block the table's width cuts short is no run.
    What a kernel does with a flag is speed, never the result."""
    B, p_max = page_table.shape
    p_blk = latent_key_pages(page_size, p_max)
    n_blk = pl.cdiv(p_max, p_blk)
    table = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, n_blk * p_blk - p_max)))
    table = table.reshape(B, n_blk, p_blk)
    ascending = jnp.all(table[..., 1:] - table[..., :-1] == 1, axis=-1)
    inside = (table[..., 0] >= 0) & (table[..., -1] < n_pool_pages)
    return (ascending & inside).astype(jnp.int32)


def latent_key_blocks(
    runs: jax.Array, start_pos: jax.Array, q_lens: jax.Array, S: int, H: int,
    page_size: int, p_max: int,
) -> tuple[jax.Array, jax.Array]:
    """The key blocks one ``ragged_paged_attention_latent`` call over a window
    of ``S`` slots x ``H`` heads fetches, over its rows, head blocks and query
    blocks, and those of them it fetches as one run (``runs``:
    ``page_run_flags`` of its table): what ``_latent_kernel`` decides a block
    by, a flag set and every page of the block streamed."""
    sq, g, p_blk = _latent_blocking(S, H, page_size, p_max)
    q0 = jnp.arange(0, S, sq)
    qn = jnp.clip(q_lens[:, None] - q0, 0, sq)  # [B, query blocks]
    n_pages = _ragged_n_pages(start_pos[:, None] + q0, qn, page_size, p_max)
    whole = jnp.arange(runs.shape[1]) < (n_pages // p_blk)[..., None]  # [B, query blocks, blocks]
    as_run = jnp.sum(jnp.where(whole, runs[:, None, :], 0))
    return jnp.sum(pl.cdiv(n_pages, p_blk)) * (H // g), as_run * (H // g)


def _latent_kernel(
    *refs, page_size: int, p_blk: int, scale: float, selecting: bool = False,
    rungs: "tuple[int, ...] | None" = None,
):
    """``refs``: the scalar prefetch (page_table [B, Pmax], runs [B, blocks]
    (``page_run_flags``), start_pos [B], q_lens [B], layer [1]; SMEM), the
    blocks q_latent [1, Sq, G, r] and q_rope [1, Sq, G, w] VMEM (one query
    block, G of the heads), rope_pages / latent_pages [1, L, N, Psz, w / r]
    ANY, out [1, Sq, G, r] VMEM; then the
    scratch rope_buf / latent_buf [2, P_BLK, Psz, w / r] (two key blocks in
    flight) and their DMA semaphores [2, 2]. The whole-window kernel's
    structure (``_ragged_kernel``) with ONE shared key head: a page is
    fetched once and its latent rows serve as the keys' first part and as
    the values. A key block that is a run (its flag set, and every page of
    it streamed) is ONE DMA a pool, started and awaited once: the scalar core
    pays for a copy, not for its bytes. Any other block is fetched page by
    page. ``selecting``: one more block after q_rope, select [1, Sq,
    Pmax * Psz] float32 (``index_select``), added to the scores: a key the
    query does not read weighs nothing. Every page is still streamed.

    The score tile covers the block's LIVE slots, its leading ones, rounded
    up to a rung (``latent_rung``): one arm a rung around the same key-block
    loop at ``n * G`` rows, the slots past ``n`` stored as the zeros a pad
    query outputs. ``rungs``: the arms compiled, ``latent_rungs(Sq)`` unless
    a test asks for fewer (``(Sq,)``: the whole block whatever is live)."""
    page_table_ref, runs_ref, start_pos_ref, q_lens_ref, layer_ref = refs[:5]
    refs = list(refs[5:])
    select_ref = refs.pop(2) if selecting else None
    ql_ref, qr_ref, rope_pages_ref, latent_pages_ref, out_ref, rope_buf, latent_buf, sem = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    S, G, r = ql_ref.shape[1:]
    w = qr_ref.shape[3]
    keys = p_blk * page_size
    q0 = pl.program_id(2) * S
    start = start_pos_ref[b] + q0
    qn = jnp.clip(q_lens_ref[b] - q0, 0, S)
    n_pages = _ragged_n_pages(start, qn, page_size, page_table_ref.shape[1])
    n_blocks = pl.cdiv(n_pages, p_blk)

    def copies(slot, pages, at):
        """The two pools' copies of ``pages`` of the layer (one page id, or a
        run's ``pl.ds``) into ``at`` of the slot's buffers."""
        # (an index key lies behind the rotated key's lanes)
        lanes = slice(None) if rope_pages_ref.shape[4] == w else pl.ds(0, w)
        return [
            pltpu.make_async_copy(src, buf.at[(slot,) + at], sem.at[i, slot])
            for i, (src, buf) in enumerate((
                (rope_pages_ref.at[0, layer, pages, :, lanes], rope_buf),
                (latent_pages_ref.at[0, layer, pages], latent_buf),
            ))
        ]

    def each_copy(slot, blk, act):
        """``act`` (start or wait) on every copy of key block ``blk``: one a
        pool where the block is a run, else one a pool a page. -> the pages."""
        n_here = jnp.minimum(n_pages - blk * p_blk, p_blk)
        run = (runs_ref[b, blk] != 0) & (n_here == p_blk)

        @pl.when(run)
        def _():
            for copy in copies(slot, pl.ds(page_table_ref[b, blk * p_blk], p_blk), ()):
                act(copy)

        def one(p, carry):
            for copy in copies(slot, page_table_ref[b, blk * p_blk + p], (p,)):
                act(copy)
            return carry

        @pl.when(jnp.logical_not(run))
        def _():
            lax.fori_loop(0, n_here, one, 0)

        return n_here

    def start_block(slot, blk):
        n_here = each_copy(slot, blk, operator.methodcaller("start"))

        # As in ``_ragged_kernel``: an unfetched page's latent rows meet a
        # weight of exactly 0 as VALUES, which only a finite value leaves 0.
        def blank(p, carry):
            latent_buf[slot, p] = jnp.zeros((page_size, r), latent_buf.dtype)
            return carry

        lax.fori_loop(n_here, p_blk, blank, 0)

    @pl.when(n_blocks > 0)
    def _():
        start_block(0, 0)

    def attend(n):
        """The key-block loop over the block's first ``n`` slots."""
        q_lat = ql_ref[0, :n].reshape(n * G, r)
        q_rot = qr_ref[0, :n].reshape(n * G, w)
        if q_lat.dtype != latent_buf.dtype:
            q_lat, q_rot = q_lat.astype(jnp.float32), q_rot.astype(jnp.float32)
        row_q = lax.broadcasted_iota(jnp.int32, (n * G, 1), 0) // G
        q_valid = row_q < qn
        vis = start + row_q + 1

        def body(i, carry):
            m, l, acc = carry  # [n*G, 1], [n*G, 1], [n*G, r] fp32
            slot = lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _():
                start_block(1 - slot, i + 1)

            each_copy(slot, i, operator.methodcaller("wait"))
            # Psz rows are whole sublane tiles: the merge moves nothing.
            c_tile = latent_buf[slot].reshape(keys, r)
            k_tile = rope_buf[slot].reshape(keys, w)
            if c_tile.dtype != q_lat.dtype:
                c_tile, k_tile = c_tile.astype(jnp.float32), k_tile.astype(jnp.float32)
            contract_last = (((1,), (1,)), ((), ()))
            s = lax.dot_general(q_lat, c_tile, contract_last, preferred_element_type=jnp.float32)
            s += lax.dot_general(q_rot, k_tile, contract_last, preferred_element_type=jnp.float32)
            s = s * scale  # [n*G, keys]
            pos = i * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            s = jnp.where(q_valid & (pos < vis), s, NEG_INF)
            if selecting:
                chosen = select_ref[0, :n, pl.ds(pl.multiple_of(i * keys, keys), keys)]  # [n, keys]
                s = (s.reshape(n, G, keys) + chosen[:, None, :]).reshape(n * G, keys)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # The weights in the latent's own type, as the jnp reference rounds
            # them: a bfloat16 product accumulated in float32.
            acc_new = acc * alpha + jnp.dot(
                p.astype(c_tile.dtype), c_tile, preferred_element_type=jnp.float32
            )
            return m_new, l_new, acc_new

        m0 = jnp.full((n * G, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((n * G, 1), jnp.float32)
        acc0 = jnp.zeros((n * G, r), jnp.float32)
        m, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
        out = jnp.where(l > 0.0, acc / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0, :n] = out.reshape(n, G, r).astype(out_ref.dtype)
        if n < S:
            out_ref[0, n:] = jnp.zeros((S - n, G, r), out_ref.dtype)

    rungs = rungs or latent_rungs(S)
    rung = latent_rung(qn, S, rungs)  # the block itself where it is the one rung: no branch
    for n in rungs:
        pl.when(rung == n)(functools.partial(attend, n))


def _latent_blocking(S: int, H: int, page_size: int, p_max: int) -> tuple[int, int, int]:
    """``(Sq, G, P_BLK)``: queries and heads a latent program holds, at most
    ``LATENT_ROWS`` rows of scores between them, and the pages a step covers.
    A decode window of 8 x 64 heads is one program a row; a wider window
    runs as query blocks of whole sublane tiles, a head count past the rows
    as head blocks, and each block re-streams the row's pages."""
    g = H
    while g > LATENT_ROWS and g % 2 == 0:
        g //= 2
    sq = max(1, min(S, Q_BLOCK, LATENT_ROWS // g))
    if sq < S:
        sq = max(8, sq // 8 * 8)
    return sq, g, latent_key_pages(page_size, p_max)


def _latent_call(
    q_latent, q_rope, rope_pages, latent_pages, page_table, start_pos, q_lens, layer=0,
    select=None, runs=None, *, scale: float, interpret: bool = False,
    rungs: "tuple[int, ...] | None" = None,
) -> jax.Array:
    """``ragged_paged_attention_latent``'s call; ``rungs`` as ``_latent_kernel``'s."""
    B, S, H, r = q_latent.shape
    w = q_rope.shape[3]
    page_size = latent_pages.shape[3]
    selecting = select is not None
    if runs is None:
        runs = page_run_flags(page_table, page_size, latent_pages.shape[2])
    sq, g, p_blk = _latent_blocking(S, H, page_size, page_table.shape[1])
    s_pad = pl.cdiv(S, sq) * sq
    if s_pad != S:
        pad = ((0, 0), (0, s_pad - S), (0, 0), (0, 0))
        q_latent, q_rope = jnp.pad(q_latent, pad), jnp.pad(q_rope, pad)
        if selecting:
            select = jnp.pad(select, pad[:3])
    q_block = lambda width: pl.BlockSpec(
        (1, sq, g, width), lambda b, h, j, *_: (b, j, h, 0), memory_space=pltpu.VMEM
    )
    blocks = [q_block(r), q_block(w)]
    if selecting:
        blocks.append(pl.BlockSpec(
            (1, sq, select.shape[2]), lambda b, h, j, *_: (b, j, 0), memory_space=pltpu.VMEM
        ))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, H // g, s_pad // sq),
        in_specs=blocks + [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_block(r),
        scratch_shapes=[
            # A page a leading index: a run's destination is the slot's whole buffer.
            pltpu.VMEM((2, p_blk, page_size, w), rope_pages.dtype),
            pltpu.VMEM((2, p_blk, page_size, r), latent_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, page_size=page_size, p_blk=p_blk, scale=scale, rungs=rungs
    )
    more = {}
    if selecting:
        kernel = functools.partial(kernel, selecting=True)
    if sq * g > LATENT_ROWS:
        # A window of whole sublane tiles over more heads than LATENT_ROWS
        # counts on (8 queries x 128 heads): the carries and the score tile
        # double, past Mosaic's default scoped limit; the chip's VMEM is 128 MiB.
        more["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=LATENT_VMEM_LIMIT)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_latent.shape, q_latent.dtype),
        interpret=interpret,
        name="ragged_paged_attention_selected" if selecting else "ragged_paged_attention_latent",
        **more,
    )(
        page_table.astype(jnp.int32),
        runs.astype(jnp.int32),
        start_pos.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_latent,
        q_rope,
        *((select,) if selecting else ()),
        rope_pages,
        latent_pages,
    )
    return out[:, :S]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_paged_attention_latent(
    q_latent: jax.Array,  # [B, S, H, r]
    q_rope: jax.Array,  # [B, S, H, w]
    rope_pages: jax.Array,  # [1, L, N, Psz, w] (stays in HBM)
    latent_pages: jax.Array,  # [1, L, N, Psz, r] (stays in HBM)
    page_table: jax.Array,  # [B, Pmax]
    start_pos: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    select: "jax.Array | None" = None,  # [B, S, Pmax * Psz] float32 (``index_select``)
    runs: "jax.Array | None" = None,  # [B, blocks] ``page_run_flags`` (None: computed here)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """The ragged kernel for a latent cache, in ABSORBED form
    (``latent_paged_attention_reference``): grid (B, cdiv(H, G), cdiv(S, Sq));
    one program streams a row's pages once, ``P_BLK`` pages a step, each step
    two DMAs (the block's rotated keys and its latent rows) where the block's
    pages lie side by side in the pool (``runs``: a forward computes the flags
    once for all its layers' calls) and two a page where they do not, and
    multiplies them by a block of Sq queries x G heads: ``[Sq*G, r] @
    latent.T + [Sq*G, w] @ rope.T`` for the scores, ``p @ latent`` for the output, flash-style in
    float32; of the block's Sq slots the tile covers the live ones, rounded
    up to a rung (``latent_rung``: a decode row with one live query
    multiplies G rows, not Sq * G). Rows ragged by ``q_lens`` as in
    ``ragged_paged_attention``. Its
    custom call carries this function's name: ``ragged_paged_attention``
    selects both kernels in a trace, the whole name this one.

    Under ``select`` (a latent block with an index) a query reads only the
    keys it names: the same program with the selection added to its scores,
    every page streamed and the unselected weighing nothing, under a name of
    its own, ``ragged_paged_attention_selected``: ``ragged_paged_attention``
    selects it with the other two in a trace, ``ragged_paged_attention_latent``
    does not. The rotated key is then the first ``w`` lanes of its page row."""
    return _latent_call(
        q_latent, q_rope, rope_pages, latent_pages, page_table, start_pos, q_lens, layer, select,
        runs, scale=scale, interpret=interpret,
    )


# ------------------------------------------------------- the learned index
# Queries an index program scores: one bfloat16 sublane tile (16 rows), so
# that the head-major query rows merge as they are stored and the sum over
# the index heads is a sum of whole float32 tiles.
INDEX_QUERIES = 16


def index_select_reference(
    q_index: jax.Array,  # [B, S, Hi, di]
    w_index: jax.Array,  # [B, S, Hi] float32
    key_pages: jax.Array,  # [1, L, N, Psz, lane0 + di]: the index key behind lane0
    page_table: jax.Array,  # [B, Pmax]
    start_pos: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    *,
    topk: int,
    lane0: int,
) -> jax.Array:
    """Which cached keys each query reads, pure jnp: float32 [B, S, Pmax *
    Psz], 0 at the ``topk`` keys of largest index score ``sum_h w_h relu(q_h
    . k)`` among those the query can see (all of them where it sees no more
    than ``topk``; ties to the lower position), NEG_INF elsewhere and
    everywhere for a pad query."""
    from mcpx.models.gemma.model import index_scores, select_top

    B, S = q_index.shape[:2]
    n_keys = page_table.shape[1] * key_pages.shape[3]
    k_i = key_pages[0, layer][page_table].reshape(B, n_keys, -1)[..., lane0:]
    vis = start_pos[:, None] + jnp.arange(S) + 1
    visible = jnp.arange(n_keys)[None, None, :] < vis[:, :, None]
    visible &= (jnp.arange(S)[None, :] < q_lens[:, None])[:, :, None]
    chosen = select_top(index_scores(q_index, w_index, k_i), visible, topk)
    return jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)


def _index_kernel(*refs, page_size: int, p_blk: int, topk: int, lane0: int):
    """``refs``: the scalar prefetch (page_table, runs, start_pos, q_lens,
    layer; SMEM), q [1, Hi, Sq, di] and w [1, Hi, Sq, 128] VMEM (one block of Sq =
    ``INDEX_QUERIES`` queries, head-major; a weight repeated along its lane
    row), key_pages [1, L, N, Psz, lane0 + di] ANY, out [1, Sq, Pmax * Psz]
    float32 VMEM; then key_buf [2, P_BLK, Psz, di] and its DMA semaphores
    [2]. The program streams its row's index keys a block of pages at a time
    as ``_latent_kernel`` streams the latents (a run in one copy, by the same
    flags), writes each block's scores
    into ``out``, and then turns ``out`` into the selection in place: the
    ``topk``-th largest score of every query by a search over the bits of the
    float32 (32 counts), ties cut at a position found the same way. A block
    whose queries all see no more than ``topk`` keys streams nothing: they
    read every key they see."""
    page_table_ref, runs_ref, start_pos_ref, q_lens_ref, layer_ref = refs[:5]
    q_ref, w_ref, key_pages_ref, out_ref, key_buf, sem = refs[5:]
    b = pl.program_id(0)
    layer = layer_ref[0]
    Hi, S, di = q_ref.shape[1:]
    n_keys = out_ref.shape[2]
    keys = p_blk * page_size
    q0 = pl.program_id(1) * S
    start = start_pos_ref[b] + q0
    qn = jnp.clip(q_lens_ref[b] - q0, 0, S)
    searching = start + qn > topk
    n_pages = jnp.where(
        searching, _ragged_n_pages(start, qn, page_size, page_table_ref.shape[1]), 0
    )
    n_blocks = pl.cdiv(n_pages, p_blk)
    q = q_ref[0].reshape(Hi * S, di)
    weight = w_ref[0].reshape(Hi * S, w_ref.shape[3])[:, :1]  # [Hi * S, 1]

    def copy(slot, pages, at):
        return pltpu.make_async_copy(
            key_pages_ref.at[0, layer, pages, :, pl.ds(lane0, di)],
            key_buf.at[(slot,) + at], sem.at[slot],
        )

    def each_copy(slot, blk, act):
        """As ``_latent_kernel``'s: a run's index keys are one copy."""
        n_here = jnp.minimum(n_pages - blk * p_blk, p_blk)
        run = (runs_ref[b, blk] != 0) & (n_here == p_blk)

        @pl.when(run)
        def _():
            act(copy(slot, pl.ds(page_table_ref[b, blk * p_blk], p_blk), ()))

        def one(p, carry):
            act(copy(slot, page_table_ref[b, blk * p_blk + p], (p,)))
            return carry

        @pl.when(jnp.logical_not(run))
        def _():
            lax.fori_loop(0, n_here, one, 0)

    out_ref[0] = jnp.full((S, n_keys), -jnp.inf, jnp.float32)

    @pl.when(n_blocks > 0)
    def _():
        each_copy(0, 0, operator.methodcaller("start"))

    def body(i, carry):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _():
            each_copy(1 - slot, i + 1, operator.methodcaller("start"))

        each_copy(slot, i, operator.methodcaller("wait"))
        # [keys, di]; rows of a page not fetched are masked below
        k_tile = key_buf[slot].reshape(keys, di)
        s = lax.dot_general(
            q, k_tile, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Hi * S, keys]
        part = jnp.sum((jnp.maximum(s, 0.0) * weight).reshape(Hi, S, keys), axis=0)
        part = jnp.where(part == 0.0, 0.0, part)  # no -0.0: the search below orders bits
        out_ref[0, :, pl.ds(pl.multiple_of(i * keys, keys), keys)] = part
        return carry

    lax.fori_loop(0, n_blocks, body, 0)

    row = lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    pos = lax.broadcasted_iota(jnp.int32, (S, n_keys), 1)
    visible = (row < qn) & (pos < start + row + 1)

    @pl.when(jnp.logical_not(searching))
    def _():
        out_ref[0] = jnp.where(visible, 0.0, NEG_INF)

    @pl.when(searching)
    def _():
        # A float32's bits as an int32 that orders as the float does.
        bits = lax.bitcast_convert_type(jnp.where(visible, out_ref[0], -jnp.inf), jnp.int32)
        x = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        count = lambda hit: jnp.sum(hit.astype(jnp.int32), axis=-1, keepdims=True)

        def raise_threshold(i, t):  # the largest t with at least topk scores >= t
            cand = t ^ lax.shift_left(jnp.int32(1), 31 - i)
            return jnp.where(count(x >= cand) >= topk, cand, t)

        kth = lax.fori_loop(0, 32, raise_threshold, jnp.full((S, 1), -(2**31), jnp.int32))
        tie = x == kth
        need = topk - count(x > kth)
        n_bits = n_keys.bit_length()

        def raise_cut(i, cut):  # the largest cut with at most ``need`` ties before it
            cand = cut | lax.shift_left(jnp.int32(1), n_bits - 1 - i)
            return jnp.where(count(tie & (pos < cand)) <= need, cand, cut)

        cut = lax.fori_loop(0, n_bits, raise_cut, jnp.zeros((S, 1), jnp.int32))
        chosen = visible & ((x > kth) | (tie & (pos < cut)))
        out_ref[0] = jnp.where(chosen, 0.0, NEG_INF)


@functools.partial(jax.jit, static_argnames=("topk", "lane0", "interpret"))
def lightning_indexer(
    q_index: jax.Array,  # [B, S, Hi, di]
    w_index: jax.Array,  # [B, S, Hi] float32
    key_pages: jax.Array,  # [1, L, N, Psz, lane0 + di] (stays in HBM)
    page_table: jax.Array,  # [B, Pmax]
    start_pos: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array | int = 0,
    runs: "jax.Array | None" = None,  # [B, blocks] ``page_run_flags`` (None: computed here)
    *,
    topk: int,
    lane0: int,
    interpret: bool = False,
) -> jax.Array:
    """``index_select_reference`` as a kernel: grid (B, cdiv(S, 16)); one
    program scores a block of 16 queries x every index head against its row's
    cached index keys through the page table (lanes ``lane0`` on of the row's
    rotated-key pages: one DMA a key block that is a run, ``runs``, one a
    page of any other), ``[Hi * 16, di] @ keys.T`` a block of
    ``LATENT_KEY_BLOCK`` keys, and selects in VMEM. Its custom call carries
    this function's name, which is part of no other kernel's."""
    B, S, Hi, di = q_index.shape
    page_size = key_pages.shape[3]
    p_max = page_table.shape[1]
    n_keys = p_max * page_size
    sq = INDEX_QUERIES
    p_blk = latent_key_pages(page_size, p_max)
    if runs is None:
        runs = page_run_flags(page_table, page_size, key_pages.shape[2])
    # Head-major, so that the sum over heads adds whole [16, keys] tiles; the
    # window padded to whole query blocks with dead queries.
    q = q_index.transpose(0, 2, 1, 3)
    w = jnp.broadcast_to(w_index.transpose(0, 2, 1)[..., None], (B, Hi, S, 128))
    s_pad = pl.cdiv(S, sq) * sq
    if s_pad != S:
        pad = ((0, 0), (0, 0), (0, s_pad - S), (0, 0))
        q, w = jnp.pad(q, pad), jnp.pad(w, pad)
    block = lambda width: pl.BlockSpec(
        (1, Hi, sq, width), lambda b, j, *_: (b, 0, j, 0), memory_space=pltpu.VMEM
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, s_pad // sq),
        in_specs=[block(di), block(128), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, sq, n_keys), lambda b, j, *_: (b, j, 0), memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, p_blk, page_size, di), key_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, page_size=page_size, p_blk=p_blk, topk=topk, lane0=lane0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, s_pad, n_keys), jnp.float32),
        interpret=interpret,
        name="lightning_indexer",
    )(
        page_table.astype(jnp.int32),
        runs.astype(jnp.int32),
        start_pos.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q,
        w,
        key_pages,
    )
    return out[:, :S]
