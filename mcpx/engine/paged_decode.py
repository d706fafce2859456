"""Decode-step forward pass against the paged KV cache.

Same math as ``mcpx.models.gemma.model`` (shares its RMSNorm/RoPE
primitives and param pytree) but the attention reads/writes go to the shared
page pools via the Pallas ragged paged-attention kernel
(``engine/kernels/paged_attention.py``) instead of a dense per-batch cache.
Kept separate from the model so the dense path stays a clean correctness
reference (SURVEY.md §4.2) and the paged path owns its layout decisions.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mcpx.engine.kernels.paged_attention import (
    index_select_reference,
    latent_paged_attention_reference,
    lightning_indexer,
    page_run_flags,
    ragged_paged_attention,
    ragged_paged_attention_latent,
    ragged_paged_attention_reference,
)
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import (
    _join,
    attention_inputs,
    attention_residual,
    conv_attention_inputs,
    conv_feed_forward,
    conv_norm,
    embed_tokens,
    feed_forward_residual,
    gated_attention_out,
    hybrid_attention_inputs,
    hybrid_feed_forward,
    join_scaled,
    layer_kinds,
    layer_stacks,
    mixer_feed_forward,
    mixer_norm,
    mixer_stream,
    output_logits,
    pack_kv,
    pattern_rows,
    rms_norm,
    scan_norm,
    sparse_index,
    stack_at,
    stack_row,
    walk_runs,
)
from mcpx.models.gemma.moe import add_forward_stats, add_layer_stats, moe_stats_init
from mcpx.parallel.mesh import DATA_AXIS, MODEL_AXIS, _axis


def _ragged_kernel_on_mesh(
    mesh: Mesh,
    qg: jax.Array,  # [B, S, K, G, hd]
    k_all: jax.Array,  # [K, L, N, Psz, hd]
    v_all: jax.Array,
    page_table: jax.Array,  # [B, Pmax]
    positions: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: jax.Array,
    window: "jax.Array | None" = None,
    *,
    interpret: bool,
    name: "str | None" = None,  # the call's own name in a device trace (None: the kernel's)
    scale: "float | None" = None,  # the softmax scale (None: the kernel's hd ** -0.5)
) -> jax.Array:
    """The ragged kernel under ``jax.shard_map`` over the engine mesh: XLA
    will not partition a Mosaic call by itself, so each device runs the
    kernel on its own block. Rows split over ``data``; KV heads over
    ``model`` where they divide — the pools' own sharding
    (``InferenceEngine._init_pools``) — else the query-group axis with the
    pools replicated (MQA). An axis that does not divide stays whole on
    every device, so a one-device mesh is the same code with nothing split.
    No collective is needed: a (row, head) pair's attention is complete on
    the device that holds it."""
    B, _, K, G, _ = qg.shape
    rows = _axis(mesh, DATA_AXIS, B)
    heads = _axis(mesh, MODEL_AXIS, K)
    groups = None if heads else _axis(mesh, MODEL_AXIS, G)
    q_spec = P(rows, None, heads, groups, None)
    pool_spec = P(heads, None, None, None, None)
    # A layer's window, where the model has any, is one more replicated
    # scalar; a model without keeps the call it always made.
    scalars = (jnp.asarray(layer, jnp.int32),)
    if window is not None:
        scalars += (jnp.asarray(window, jnp.int32),)
    return jax.shard_map(
        functools.partial(
            ragged_paged_attention, interpret=interpret, **({"name": name} if name else {}),
            **({} if scale is None else {"scale": scale}),
        ),
        mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(rows, None), P(rows), P(rows))
        + (P(),) * len(scalars),
        out_specs=q_spec,
        check_vma=False,
    )(qg, k_all, v_all, page_table, positions, q_lens, *scalars)


def _latent_attend(
    q: jax.Array,  # [B, S, H, hd + dr]: a head's unrotated values, then its rotated ones
    lp: dict[str, jax.Array],
    cfg: GemmaConfig,
    rope_pool: jax.Array,  # [1, L, N, Psz, w]: paged_kv["k"]
    latent_pool: jax.Array,  # [1, L, N, Psz, r]: paged_kv["v"]
    page_table: jax.Array,
    positions: jax.Array,
    q_lens: jax.Array,  # [B] live window slots of each row
    layer: jax.Array,
    index: "tuple | None" = None,  # the window's index queries and weights
    runs: "jax.Array | None" = None,  # [B, blocks] the table's ``page_run_flags``
    *,
    mesh: Optional[Mesh],
    use_pallas: bool,
    interpret: bool,
) -> tuple:
    """Latent attention against the pages, ABSORBED: a head's unrotated
    query goes through its own key expansion ``W_uk,h`` into the latent's
    space (``q~ = q_nope W_uk^T``), scores and weighted sums are taken on the
    cached latents themselves (one shared key head, ``kernels/
    paged_attention.py``), and the head's value expansion ``W_uv,h`` comes
    after: [B, S, H * dv]. The same mathematics as the expanded form of the
    dense prefill (``model.latent_expand``), with no per-head key or value
    ever built for a cached token. Heads split over ``model`` where they
    divide; the pools are whole on every device (a latent has no head axis).
    -> (the attention's output, the selection it read under or None).

    With an index (``index``, and a table wide enough to hold a key the
    selection drops) each query first scores its row's cached index keys
    (``kernels/paged_attention.lightning_indexer``: they lie in the rotated
    key's page rows) and the attention reads its ``index_topk`` best alone:
    every page is still streamed, the unselected weigh nothing. Each device
    selects for its own rows, every index head at once.

    ``runs``: which key blocks of the table lie side by side in the pools
    (``page_run_flags``), for both kernels: the caller computes them once a
    forward, outside its layer scan (None: each call computes its own)."""
    B, S, H, _ = q.shape
    hd, dr = cfg.head_dim, cfg.qk_rope_head_dim
    w_uk, w_uv = lp["w_ukv"][..., :hd], lp["w_ukv"][..., hd:]  # [r, H, hd], [r, H, dv]
    q_latent = jnp.einsum("bshe,rhe->bshr", q[..., :hd], w_uk)
    q_rope = q[..., hd:]
    pad = cfg.index_key_offset - dr
    if pad:
        q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, 0), (0, pad)))
    scale = cfg.attn_score_factor / (hd + dr) ** 0.5
    selecting = index is not None and page_table.shape[1] * rope_pool.shape[3] > cfg.index_topk
    rows = None if mesh is None else _axis(mesh, DATA_AXIS, B)
    row_specs = (P(rows, None), P(rows), P(rows), P())  # table, positions, q_lens, layer
    if runs is None:
        runs = page_run_flags(page_table, rope_pool.shape[3], rope_pool.shape[2])
    select = None
    if selecting:
        choose = functools.partial(
            lightning_indexer if use_pallas else index_select_reference,
            topk=cfg.index_topk, lane0=cfg.index_key_offset,
            **({"interpret": interpret} if use_pallas else {}),
        )
        if use_pallas and mesh is not None:
            choose = jax.shard_map(
                choose, mesh=mesh,
                in_specs=(P(rows, None, None, None), P(rows, None, None), P()) + row_specs
                + (P(rows, None),),
                out_specs=P(rows, None, None), check_vma=False,
            )
        select = choose(
            *index, rope_pool, page_table, positions, q_lens, jnp.asarray(layer, jnp.int32),
            *((runs,) if use_pallas else ()),
        )
    selected = () if select is None else (select,)
    if use_pallas:
        kernel = functools.partial(ragged_paged_attention_latent, scale=scale, interpret=interpret)
        if mesh is not None:
            heads = _axis(mesh, MODEL_AXIS, H)
            q_spec = P(rows, None, heads, None)
            kernel = jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(q_spec, q_spec, P(), P()) + row_specs
                + (P(rows, None, None) if selecting else None, P(rows, None)),
                out_specs=q_spec, check_vma=False,
            )
        out = kernel(
            q_latent, q_rope, rope_pool, latent_pool, page_table, positions, q_lens,
            jnp.asarray(layer, jnp.int32), select, runs,
        )
    else:
        out = latent_paged_attention_reference(
            q_latent, q_rope, rope_pool, latent_pool, page_table, positions, q_lens, layer,
            *selected, scale=scale,
        )
    attn = jnp.einsum("bshr,rhe->bshe", out, w_uv).reshape(B, S, cfg.attn_out_width)
    return attn, select


def _kv_window(
    positions: jax.Array,  # [B] slot of each row's window slot 0
    page_table: jax.Array,  # [B, Pmax]
    S: int,
    psz: int,
    n_pool_pages: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Where a forward's ``[B, S]`` window of new K/V rows lands in the page
    pool, at page granularity. A window of S slots starting anywhere in a
    page touches at most ``P = cdiv(S - 1, psz) + 1`` pages of its row
    (static per executable: 2 at a decode segment's S = 8, 9 at a 128-wide
    suffix prefill). Returns

      - ``pages`` ``[B, P]``: the pool page behind each touched table column;
        a column past the table's width gets ``n_pool_pages``, out of range,
        so the scatter drops it (a window that overhangs the row's last
        column writes nothing there),
      - ``slot`` ``[B, P * psz]``: the window slot that page slot ``t`` of
        the gathered run holds, ``t - positions % psz``, clipped into
        ``[0, S)``,
      - ``live`` ``[B, P, psz]``: whether that page slot is inside the
        window at all; the others keep what the page held.

    Computed once per forward, outside the layer scan."""
    n_win = -(-(S - 1) // psz) + 1
    p_max = page_table.shape[1]
    cols = (positions // psz)[:, None] + jnp.arange(n_win, dtype=positions.dtype)
    pages = jnp.where(
        cols < p_max,
        jnp.take_along_axis(page_table, jnp.minimum(cols, p_max - 1), axis=1),
        n_pool_pages,
    )
    slot = jnp.arange(n_win * psz, dtype=positions.dtype) - (positions % psz)[:, None]
    live = ((slot >= 0) & (slot < S)).reshape(-1, n_win, psz)
    return pages, jnp.clip(slot, 0, S - 1), live


def _write_kv_window(
    pool: jax.Array,  # [K, L, N, psz, hd], one of the two pools
    layer: jax.Array,
    new: jax.Array,  # [B, S, K, hd] this layer's new K or V rows
    window: tuple[jax.Array, jax.Array, jax.Array],  # _kv_window(...)
    with_pages: bool = False,  # also the touched pages as written, [K, B, P, psz, hd]
) -> jax.Array:
    """Write one layer's window of new rows into the pool IN PLACE, page by
    page, in the pool's own shape: gather the touched pages of ``layer``
    (``[K, B, P, psz, hd]``, 1 MB at the benchmark's slab), merge the new
    rows in by the slot mask, scatter whole pages back. The pool is never
    reshaped and never written through XLA at less than a whole
    ``[psz, hd]`` page, which is a whole device tile, so XLA keeps it in
    the layout the Mosaic call reads. (A row-granular scatter through a
    flat ``[K, L, N*psz, hd]`` view made XLA relayout the whole all-layers
    pool before and after it, for K and for V, in every layer of every
    forward: 66-88% of device time on the v5e, PERF.md PR 25.) Rows own
    the pages they write (shared radix pages lie wholly before a row's
    ``positions``), so two rows meet only on null page 0, where either's
    garbage may win."""
    pages, slot, live = window
    K, _, _, psz, hd = pool.shape
    B, n_win = pages.shape
    old = pool[:, layer, pages]  # [K, B, P, psz, hd]
    rows = jnp.take_along_axis(new, slot[:, :, None, None], axis=1)  # [B, P*psz, K, hd]
    rows = rows.transpose(2, 0, 1, 3).reshape(K, B, n_win, psz, hd)
    merged = jnp.where(live[None, ..., None], rows.astype(pool.dtype), old)
    pool = pool.at[:, layer, pages].set(merged, mode="drop")
    return (pool, merged) if with_pages else pool


# Queries the masked form of a block-selecting layer scores at a time, a row:
# its float32 scores are queries x heads x context.
BLOCK_QUERY_BLOCK = 128


def _block_attend(
    qg: jax.Array,  # [B, S, K, G, hd]
    k_all: jax.Array,  # [K, L, N, psz, hd]
    v_all: jax.Array,
    ksum: jax.Array,  # [K, L, N, hd] float32: every page's key sum
    page_table: jax.Array,  # [B, Pmax]
    positions: jax.Array,  # [B]
    q_lens: jax.Array,  # [B]
    layer: int,
    cfg: GemmaConfig,
    *,
    gathered: bool,
    mesh: Optional[Mesh],
    use_pallas: bool,
    interpret: bool,
) -> jax.Array:
    """A block-selecting (``S``) layer's attention against the pages, for a
    table wide enough to hold a block a query drops: every (query slot, KV
    head) scores the row's pooled keys (two neighbouring pages' sums, read
    out of ``ksum`` through the table) and reads its own ``block_topk``
    blocks (``models/gemma/sparse.py``). -> ([B, S, K, G, hd], the blocks each
    (slot, KV head) chose [B, S, K, blocks] bool).

    ``gathered`` (a decode window): the selection becomes a PAGE LIST a
    (row, slot, KV head), its blocks ascending and ``block_size / psz`` pages
    each, and the ragged kernel runs over those lists as its rows' tables: it
    FETCHES the listed pages and no others. Unrotated keys carry no position,
    so a list is a context of its own: the query's place in it is its place
    in the LAST block (its own, always chosen) behind the whole blocks before,
    and that is all the causal bound needs. The pools are read through a
    view with KV head, layer and page merged, so one call serves both KV
    heads with a page id that names all three.

    Else (a prefill window: a suffix over a shared head, a chunk of a head's
    build) the masked form in jnp, a row and ``BLOCK_QUERY_BLOCK`` queries at
    a time: every page is gathered, the unselected tokens weigh nothing."""
    from mcpx.models.gemma import sparse

    B, S, K, G, hd = qg.shape
    _, L, N, psz, _ = k_all.shape
    p_max = page_table.shape[1]
    pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)
    sums = ksum[:, layer][:, page_table].transpose(1, 0, 2, 3)  # [B, K, Pmax, hd]
    kc = sparse.pooled_keys(sums, psz)
    if not gathered:
        def one_row(args):
            q_r, table, kc_r, pos_r, qn = args  # [S, K, G, hd], [Pmax], [K, J, hd], [S], []
            k = k_all[:, layer][:, table].reshape(K, p_max * psz, hd)
            v = v_all[:, layer][:, table].reshape(K, p_max * psz, hd)

            def one_block(i):
                q_b = lax.dynamic_slice_in_dim(q_r, i, sb, axis=0)
                t_b = lax.dynamic_slice_in_dim(pos_r, i, sb, axis=0)
                chosen = sparse.selected_blocks(q_b[None], kc_r[None], t_b[None], cfg)[0]  # [sb, K, Nb]
                mask = sparse.token_mask(chosen, cfg.block_size, p_max * psz)
                mask = jnp.pad(mask, ((0, 0), (0, 0), (0, p_max * psz - mask.shape[-1])))
                mask &= (jnp.arange(p_max * psz)[None, :] <= t_b[:, None])[:, None, :]
                logits = jnp.einsum("skgh,klh->skgl", q_b, k, preferred_element_type=jnp.float32)
                logits = jnp.where(mask[:, :, None, :], logits * hd**-0.5, -1e30)
                w = jax.nn.softmax(logits, axis=-1)
                out = jnp.einsum("skgl,klh->skgh", w.astype(v.dtype), v)
                out = jnp.where(((i + jnp.arange(sb)) < qn)[:, None, None, None], out, 0)
                return out.astype(q_r.dtype), chosen

            sb = min(S, BLOCK_QUERY_BLOCK)
            out, chosen = lax.map(one_block, jnp.arange(0, S, sb))
            return out.reshape(S, K, G, hd), chosen.reshape(S, K, -1)

        if S % min(S, BLOCK_QUERY_BLOCK):
            raise ValueError(f"a prefill window of {S} slots is no multiple of {BLOCK_QUERY_BLOCK}")
        return lax.map(one_row, (qg, page_table, kc, pos_mat, q_lens))
    if use_pallas and (mesh is None or mesh.size == 1):
        from mcpx.engine.kernels.block_score import block_score

        pooled = block_score(qg, kc, positions, q_lens, stride=psz, interpret=interpret)
    else:
        pooled = sparse.pooled_scores(qg, kc, pos_mat, psz)
    chosen = sparse.blocks_from_scores(pooled, pos_mat, cfg)  # [B, S, K, Nb]
    ids, count = sparse.block_lists(chosen, cfg.block_topk)  # [B, S, K, topk], [B, S, K]
    r = cfg.block_size // psz
    cols = (ids[..., None] * r + jnp.arange(r, dtype=ids.dtype)).reshape(B, S, K, -1)
    pages = jnp.take_along_axis(
        page_table[:, None, None, :], jnp.minimum(cols, p_max - 1).reshape(B, 1, 1, -1), axis=-1
    ).reshape(cols.shape)
    head = (jnp.arange(K, dtype=pages.dtype) * L + layer) * N
    lists = jnp.where(cols < p_max, pages + head[None, None, :, None], 0)
    live = jnp.arange(S)[None, :] < q_lens[:, None]
    start = (count - 1) * cfg.block_size + (pos_mat % cfg.block_size)[:, :, None]
    rows = B * S * K
    flat = (1, 1, K * L * N, psz, hd)
    args = (
        qg.reshape(rows, 1, 1, G, hd), k_all.reshape(flat), v_all.reshape(flat),
        lists.reshape(rows, -1), start.reshape(rows),
        jnp.broadcast_to(live[:, :, None], (B, S, K)).reshape(rows).astype(jnp.int32),
    )
    if use_pallas:
        out = _ragged_kernel_on_mesh(
            mesh, *args, 0, interpret=interpret, name="ragged_paged_attention_gathered"
        )
    else:
        out = ragged_paged_attention_reference(*args, 0, None)
    return out.reshape(B, S, K, G, hd), chosen


def _mixer_ffn_chunk(
    params, cfg: GemmaConfig, x, positions, page_table, paged_kv, kv_window, q_lens, slots, *,
    use_pallas, interpret, mesh, logits_at, active_cols, moe_stats, commit, selection,
) -> tuple:
    """``decode_chunk_paged`` for an ``L`` / ``S`` pattern: a static walk, each
    layer its mixer then the dense feed-forward. A linear layer reads and
    writes its part of the state pool (``ssm.linear_window``; ``slots``:
    (read from, written to), a row); an ``S`` layer writes its keys and
    values into the pages as every model's do, unrotated, sums the pages it
    touched again into the key-sum pool, and attends by block selection
    where the table can hold a block a query drops (``_block_attend``), else
    as plain grouped attention. ``commit``: the window is a prefill's, all of
    it stays."""
    from mcpx.models.gemma import sparse
    from mcpx.models.gemma.ssm import linear_window

    B, S, _ = x.shape
    state = paged_kv["state"]
    src, dst = slots
    one_device = mesh is None or mesh.size == 1
    kernel = None
    if use_pallas and one_device and not commit:
        from mcpx.engine.kernels.ssm import ssm_window

        kernel = functools.partial(ssm_window, interpret=interpret)
    n_slots = state["n"].shape[0]
    kept = state["n"][jnp.minimum(src, n_slots - 1)]
    pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)
    k_all, v_all, ksum = paged_kv["k"], paged_kv["v"], state.get("ksum")
    psz, p_max = k_all.shape[3], page_table.shape[1]
    selecting = sparse.selects(cfg, p_max * psz)
    if selection and not selecting:
        raise ValueError("selection=True: this table is too narrow to hold a block a query drops")
    ssm, layers_new, reads = state["ssm"], list(state["layers"]), []
    for kind, j in pattern_rows(cfg):
        lp = stack_row(params["linear_layers" if kind == "L" else "block_layers"], j)
        n = mixer_norm(x, lp["norm"], cfg)
        if kind == "L":
            out, ssm, layers_new[j] = linear_window(
                n, lp, cfg, ssm, j, state["layers"][j], src, dst, q_lens, kept, pos_mat,
                commit=commit, kernel=kernel,
            )
        else:
            q, k, v = hybrid_attention_inputs(n, lp, cfg)
            k_all, written = _write_kv_window(k_all, j, k, kv_window, with_pages=True)
            v_all = _write_kv_window(v_all, j, v, kv_window)
            ksum = ksum.at[:, j, kv_window[0]].set(
                jnp.sum(written.astype(jnp.float32), axis=3), mode="drop"
            )
            qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            if selecting:
                attn, chosen = _block_attend(
                    qg, k_all, v_all, ksum, page_table, positions, q_lens, j, cfg,
                    gathered=not commit, mesh=mesh, use_pallas=use_pallas, interpret=interpret,
                )
                reads.append(chosen)
            elif use_pallas:
                attn = _ragged_kernel_on_mesh(
                    mesh, qg, k_all, v_all, page_table, positions, q_lens, j, interpret=interpret
                )
            else:
                attn = ragged_paged_attention_reference(
                    qg, k_all, v_all, page_table, positions, q_lens, j, None
                )
            out = gated_attention_out(attn.reshape(B, S, cfg.attn_out_width), n, lp, cfg)
        x = mixer_feed_forward(join_scaled(x, out, cfg), lp, cfg)
    x = mixer_norm(x, params["final_norm"], cfg)
    # A decode window stays pending until the caller says what of it was
    # kept; a prefill's is in the state already. Either way nothing is kept yet.
    n_new = state["n"].at[jnp.where(q_lens > 0, dst, n_slots)].set(0, mode="drop")
    new_state = {"ssm": ssm, "layers": tuple(layers_new), "n": n_new}
    if ksum is not None:
        new_state["ksum"] = ksum
    pools = {"k": k_all, "v": v_all, "state": new_state}
    stats = None
    if moe_stats:
        stats = add_forward_stats(
            cfg, moe_stats_init(cfg), positions + q_lens, q_lens, S,
            blocks=(psz, p_max, commit),
        )
    extra = (stats,) if moe_stats else ()
    if selection:
        # What a comparison may ask for: the blocks every (slot, KV head) read,
        # a bit a block, a row an ``S`` layer.
        extra += (jnp.packbits(jnp.stack(reads), axis=-1),)
    if active_cols is not None:
        return (output_logits(params, cfg, x, subset=active_cols), pools) + extra
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    return (output_logits(params, cfg, x), pools) + extra


def _hybrid_chunk(
    params, cfg: GemmaConfig, x, positions, page_table, paged_kv, kv_window, q_lens, slots, *,
    use_pallas, interpret, mesh, logits_at, active_cols, moe_stats, routing,
) -> tuple:
    """``decode_chunk_paged`` for a ``layer_pattern`` model: a static walk
    over the pattern, each layer one thing alone. A Mamba layer reads and
    writes its part of the state pool by slot (``ssm.mamba_window``, through
    ``kernels/ssm.ssm_window`` on the kernel route); the attention layers
    write and read the pages as every model's do, unrotated; an ``E`` layer
    is the routed experts in their latent beside the shared expert."""
    from mcpx.models.gemma.ssm import mamba_window

    B, S, _ = x.shape
    state = paged_kv["state"]
    one_device = mesh is None or mesh.size == 1
    kernel = None
    if use_pallas and one_device:
        from mcpx.engine.kernels.ssm import ssm_window

        kernel = functools.partial(ssm_window, interpret=interpret)
    live = jnp.arange(S)[None, :] < q_lens[:, None]
    kept = state["n"][slots]
    k_all, v_all = paged_kv["k"], paged_kv["v"]
    stats = moe_stats_init(cfg) if cfg.n_experts else None
    ssm, layers_new, chosen_all = state["ssm"], list(state["layers"]), []
    for kind, j in pattern_rows(cfg):
        if kind == "M":
            lp = stack_row(params["mamba_layers"], j)
            n = rms_norm(x, lp["norm"], cfg.norm_eps, cfg.norm_plus_one)
            out, ssm, layers_new[j] = mamba_window(
                n, lp, cfg, ssm, j, state["layers"][j], slots, q_lens, kept, kernel=kernel
            )
            x = _join(x, out)
        elif kind == "E":
            x, layer_stats, chosen = hybrid_feed_forward(
                x, params["layers"], j, cfg, live,
                use_pallas=use_pallas and one_device, interpret=interpret,
            )
            stats = add_layer_stats(stats, layer_stats)
            chosen_all.append(chosen)
        else:
            lp = stack_row(params["attn_layers"], j)
            n = rms_norm(x, lp["norm"], cfg.norm_eps, cfg.norm_plus_one)
            q, k, v = hybrid_attention_inputs(n, lp, cfg)
            k_all = _write_kv_window(k_all, j, k, kv_window)
            v_all = _write_kv_window(v_all, j, v, kv_window)
            qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            if use_pallas:
                attn = _ragged_kernel_on_mesh(
                    mesh, qg, k_all, v_all, page_table, positions, q_lens, j, interpret=interpret
                )
            else:
                attn = ragged_paged_attention_reference(
                    qg, k_all, v_all, page_table, positions, q_lens, j, None
                )
            attn = attn.reshape(B, S, cfg.attn_out_width)
            x = _join(x, jnp.einsum("btf,fd->btd", attn, lp["wo"]))
    if stats is not None:
        stats = add_forward_stats(cfg, stats, positions + q_lens, q_lens, S)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    # The window this forward left pending is kept as far as the caller says
    # after it: nothing, until then.
    n_new = state["n"].at[jnp.where(q_lens > 0, slots, state["n"].shape[0])].set(0, mode="drop")
    pools = {"k": k_all, "v": v_all, "state": {"ssm": ssm, "layers": tuple(layers_new), "n": n_new}}
    extra = ((stats,) if moe_stats else ()) + ((jnp.stack(chosen_all),) if routing else ())
    if active_cols is not None:
        return (output_logits(params, cfg, x, subset=active_cols), pools) + extra
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    return (output_logits(params, cfg, x), pools) + extra


def _scan_chunk(
    params, cfg: GemmaConfig, x, positions, page_table, paged_kv, kv_window, q_lens, slots, *,
    use_pallas, interpret, mesh, logits_at, active_cols, moe_stats,
) -> tuple:
    """``decode_chunk_paged`` for a ``J`` / ``Q`` pattern: the walk SCANNED
    over each run of like layers (``model.walk_runs``), each layer its mixer
    then the dense feed-forward. The page pools and the state pool's stacked
    arrays are the scans' carry, indexed by the carried layer number: a ``J``
    layer reads and writes its rows of the state pool by slot
    (``ssm.selective_window``, through ``kernels/selective_scan.
    selective_scan_window`` on the kernel route, where ONE device holds the
    pool); a ``Q`` layer writes its unrotated keys and values into the pages
    and attends through the ragged kernel, every query head on the layer's
    ``n_kv_heads`` (1: MQA). No window of such a model is a prefill's: its
    rows prefill whole (``GemmaConfig.suffix_route``)."""
    from mcpx.models.gemma.ssm import selective_window

    B, S, _ = x.shape
    state = paged_kv["state"]
    kernel = None
    if use_pallas and (mesh is None or mesh.size == 1):
        from mcpx.engine.kernels.selective_scan import selective_scan_window

        kernel = functools.partial(selective_scan_window, interpret=interpret)
    n_slots = state["n"].shape[0]
    kept = state["n"][jnp.minimum(slots, n_slots - 1)]

    def scan_layer(carry, j):
        x, k_all, v_all, pool = carry
        lp = stack_at(params["scan_layers"], j)
        out, pool = selective_window(
            scan_norm(x, lp["norm"], cfg), lp, cfg, pool, j, slots, q_lens, kept, kernel=kernel
        )
        return (mixer_feed_forward(x + out, lp, cfg), k_all, v_all, pool), None

    def attn_layer(carry, j):
        x, k_all, v_all, pool = carry
        lp = stack_at(params["attn_layers"], j)
        n = mixer_norm(x, lp["norm"], cfg)
        q, k, v = hybrid_attention_inputs(n, lp, cfg)
        k_all = _write_kv_window(k_all, j, k, kv_window)
        v_all = _write_kv_window(v_all, j, v, kv_window)
        qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
        if use_pallas:
            attn = _ragged_kernel_on_mesh(
                mesh, qg, k_all, v_all, page_table, positions, q_lens, j, interpret=interpret
            )
        else:
            attn = ragged_paged_attention_reference(
                qg, k_all, v_all, page_table, positions, q_lens, j, None
            )
        out = jnp.einsum(
            "btf,fd->btd", attn.reshape(B, S, cfg.attn_out_width), lp["wo"],
            preferred_element_type=jnp.float32,
        )
        return (mixer_feed_forward(x + out, lp, cfg), k_all, v_all, pool), None

    pool = {k: v for k, v in state.items() if k != "n"}
    (x, k_all, v_all, pool), _ = walk_runs(
        cfg, {"J": scan_layer, "Q": attn_layer}, (x, paged_kv["k"], paged_kv["v"], pool)
    )
    x = mixer_norm(x, params["final_norm"], cfg)
    # The window this forward left pending is kept as far as the caller says
    # after it: nothing, until then.
    live = (q_lens > 0) & (slots < n_slots)
    n_new = state["n"].at[jnp.where(live, slots, n_slots)].set(0, mode="drop")
    pools = {"k": k_all, "v": v_all, "state": {**pool, "n": n_new}}
    extra = ()
    if moe_stats:
        extra = (add_forward_stats(cfg, moe_stats_init(cfg), positions + q_lens, q_lens, S),)
    if active_cols is not None:
        return (output_logits(params, cfg, x, subset=active_cols), pools) + extra
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    return (output_logits(params, cfg, x), pools) + extra


def _packed_attend(
    qg: jax.Array,  # [B, S, K, G, hd]
    k_all: jax.Array,  # [K / pack, L, N, psz, pack x hd]
    v_all: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    q_lens: jax.Array,
    layer: int,
    cfg: GemmaConfig,
    *,
    mesh: Optional[Mesh],
    use_pallas: bool,
    interpret: bool,
) -> jax.Array:
    """Grouped attention against pools whose rows hold ``GemmaConfig.kv_pack``
    neighbouring KV heads side by side (heads of 64, two to a 128-lane row):
    the ragged kernel as it stands, on a head of ``pack x hd`` lanes. A pool
    row serves the queries of all its KV heads; a query of the row's ``i``-th
    head is padded with ZEROS over the other heads' lanes, so its scores are
    its own head's (a product with 0 adds exactly 0), at the head's own scale
    ``hd ** -0.5``; of the weighted sum over the whole row it keeps its own
    head's lanes. No byte of the pools is padding, the kernel multiplies whole
    lane widths, and the arithmetic that is kept is what an unpacked pool
    would give. -> [B, S, K, G, hd]."""
    B, S, K, G, hd = qg.shape
    p = cfg.kv_pack
    scale = None
    if p > 1:
        q6 = qg.reshape(B, S, K // p, p, G, hd)
        qg = jnp.stack(
            [jnp.pad(q6[:, :, :, i], ((0, 0),) * 4 + ((i * hd, (p - 1 - i) * hd),)) for i in range(p)],
            axis=3,
        ).reshape(B, S, K // p, p * G, p * hd)
        scale = float(hd) ** -0.5
    if use_pallas:
        out = _ragged_kernel_on_mesh(
            mesh, qg, k_all, v_all, page_table, positions, q_lens, layer, interpret=interpret,
            scale=scale,
        )
    else:
        out = ragged_paged_attention_reference(
            qg, k_all, v_all, page_table, positions, q_lens, layer, None, scale
        )
    if p > 1:
        out = out.reshape(B, S, K // p, p, G, p, hd)
        out = jnp.stack([out[:, :, :, i, :, i] for i in range(p)], axis=3)
    return out.reshape(B, S, K, G, hd)


def _conv_chunk(
    params, cfg: GemmaConfig, x, positions, page_table, paged_kv, kv_window, q_lens, slots, *,
    use_pallas, interpret, mesh, logits_at, active_cols, moe_stats, routing, commit,
) -> tuple:
    """``decode_chunk_paged`` for a ``C`` / ``A`` pattern: a static walk, each
    layer its mixer then its feed-forward (dense in the leading layers, the
    routed experts after them). A ``C`` layer reads and writes its slot's
    tail and pending window (``ssm.conv_window``); an ``A`` layer writes its
    rotated, normed keys and its values into the pages, ``kv_pack`` heads a
    row, and attends through the ragged kernel (``_packed_attend``).

    ``commit``: the window is a PREFILL's (a suffix over matched pages, a
    chunk of a head's build; ``positions`` a page multiple): every ``C`` layer
    starts from the tail of the page before the window's first slot
    (``state["tails"]``; zeros at position 0), all of the window stays, and
    every page the window FILLS to its last slot gets its tail written, in
    this program, as its keys are. A decode window writes no page's tail: its
    pages are the row's own and never enter the radix tree."""
    from mcpx.models.gemma.ssm import conv_window

    B, S, _ = x.shape
    state = paged_kv["state"]
    one_device = mesh is None or mesh.size == 1
    live = jnp.arange(S)[None, :] < q_lens[:, None]
    n_slots = state["n"].shape[0]
    kept = state["n"][jnp.minimum(slots, n_slots - 1)]
    pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)
    k_all, v_all, tails = paged_kv["k"], paged_kv["v"], state["tails"]
    psz, p_max = k_all.shape[3], page_table.shape[1]
    K1 = tails.shape[2]
    before = None
    if commit:
        before = jnp.take_along_axis(
            page_table, jnp.clip(positions // psz - 1, 0, p_max - 1)[:, None], axis=1
        )[:, 0]
        # The window slot of each touched page's LAST slot; the page is filled
        # where that slot is live. In ``[tail | u]`` window slot s is row s + K1.
        last = jnp.arange(kv_window[0].shape[1]) * psz + (psz - 1) - (positions % psz)[:, None]
        dest = jnp.where(last < q_lens[:, None], kv_window[0], tails.shape[1])  # [B, P]
        cut = (jnp.minimum(last, S - 1)[:, :, None] + 1 + jnp.arange(K1)).reshape(B, -1, 1)
    stats = moe_stats_init(cfg) if cfg.n_experts else None
    layers_new, chosen_all = list(state["layers"]), []
    for layer, (kind, j) in enumerate(pattern_rows(cfg)):
        lp = stack_row(params["conv_layers" if kind == "C" else "attn_layers"], j)
        n = conv_norm(x, lp["norm"], cfg, kind)
        if kind == "C":
            # (a layer's rows of the pool read where the layer runs: gathered for every layer at
            # once, the compiler gives the whole 34 MB pool another layout first, a copy a program)
            start = None if before is None else jnp.where((positions > 0)[:, None, None], tails[j, before], 0)
            out, layers_new[j], tail, u = conv_window(n, lp, state["layers"][j], slots, q_lens, kept, start)
            if commit:
                rows = jnp.take_along_axis(jnp.concatenate([tail, u], axis=1), cut, axis=1)
                tails = tails.at[j, dest].set(
                    rows.reshape(dest.shape + (K1, -1)).astype(tails.dtype), mode="drop"
                )
        else:
            q, k, v = conv_attention_inputs(n, lp, cfg, pos_mat)
            k_all = _write_kv_window(k_all, j, pack_kv(k, cfg), kv_window)
            v_all = _write_kv_window(v_all, j, pack_kv(v, cfg), kv_window)
            qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            attn = _packed_attend(
                qg, k_all, v_all, page_table, positions, q_lens, j, cfg,
                mesh=mesh, use_pallas=use_pallas, interpret=interpret,
            ).reshape(B, S, cfg.attn_out_width)
            out = jnp.einsum("btf,fd->btd", attn, lp["wo"], preferred_element_type=jnp.float32)
        x, layer_stats, chosen = conv_feed_forward(
            x + out, params, layer, cfg, live,
            use_pallas=use_pallas and one_device, interpret=interpret,
        )
        if layer_stats is not None:
            stats = add_layer_stats(stats, layer_stats)
            chosen_all.append(chosen)
    if stats is not None:
        stats = add_forward_stats(cfg, stats, positions + q_lens, q_lens, S, blocks=(psz, p_max, commit))
    x = conv_norm(x, params["final_norm"], cfg)
    # A decode window stays pending until the caller says what of it was
    # kept; a prefill's is in the tail already. Either way nothing is kept yet.
    n_new = state["n"].at[jnp.where(q_lens > 0, slots, n_slots)].set(0, mode="drop")
    pools = {"k": k_all, "v": v_all, "state": {"layers": tuple(layers_new), "n": n_new, "tails": tails}}
    extra = ((stats,) if moe_stats else ()) + ((jnp.stack(chosen_all),) if routing and chosen_all else ())
    if active_cols is not None:
        return (output_logits(params, cfg, x, subset=active_cols), pools) + extra
    if logits_at is not None:
        x = x[jnp.arange(B), logits_at]
    return (output_logits(params, cfg, x), pools) + extra


def keep_window(state: dict, slots: jax.Array, kept: jax.Array, live: jax.Array) -> dict:
    """The state pool with ``kept`` [B] written as what the live rows keep
    of the window their last forward left pending (``1 + accepted`` tokens
    of a decode window): the next forward's read applies exactly those."""
    if not state:
        return state
    at = jnp.where(live, slots, state["n"].shape[0])
    return {**state, "n": state["n"].at[at].set(kept.astype(jnp.int32), mode="drop")}


def decode_chunk_paged(
    params: dict[str, Any],
    cfg: GemmaConfig,
    tokens: jax.Array,  # [B, S] int32 — chunk of new tokens per sequence
    positions: jax.Array,  # [B] int32 — slot tokens[:, 0] is written to
    page_table: jax.Array,  # [B, Pmax] int32
    paged_kv: dict[str, jax.Array],  # k/v: [K, L, N, Psz, hd]
    *,
    use_pallas: bool = True,
    interpret: bool = False,
    logits_at: "jax.Array | None" = None,  # [B] chunk slot per row, or None
    active_cols: "jax.Array | None" = None,  # [C] token ids: compact unembed
    q_lens: jax.Array,  # [B] live window slots of each row (0: an idle row)
    mesh: Optional[Mesh] = None,  # engine mesh; required with use_pallas
    moe_stats: bool = False,  # sparse models: also the forward's expert counters
    routing: bool = False,  # sparse models: also the experts chosen [Ls, B, S, k]
    selection: bool = False,  # a learned index: also the keys each query read [L, B, S, keys / 8]; block selection: the blocks [S layers, B, S, K, blocks / 8]
    state_slots: "tuple | None" = None,  # recurrent layers: each row's (slot read, slot written); None: row i's is i (a short convolution reads pages' tails: the first is unused)
    commit: bool = False,  # recurrent layers: the window is a prefill's, every live slot of it stays
) -> tuple:
    """Multi-token decode step: S new tokens per sequence in ONE forward.

    This is the verify/extend pass for grammar fast-forward speculation
    (SURVEY.md §6: "speculative decoding headroom"): forced-token chains
    from the plan DFA need no sampling, only KV population and the logits
    at the chain end — so S sequential decode steps collapse into one
    forward whose per-token cost is amortised over the weight loads that
    dominate decode on TPU. The pools ([K, L, N, Psz, hd], all layers) are
    carried through the layer scan; each layer writes its chunk K/V into
    the pages its window touches, whole pages at a time
    (``_write_kv_window``), then the chunk kernel streams that layer's
    pages once for all S queries (query i sees cache through
    ``positions+i``).

    Tokens past a sequence's valid chain are pads; their K/V slots hold
    garbage that the next chunk (which starts at the first invalid
    position) overwrites, and their logits are ignored by the caller.
    ``q_lens`` makes the raggedness explicit: with per-row live window
    widths the attention (kernel AND jnp reference, in lockstep) streams
    only each row's own pages and zeroes pad-query outputs — suffix
    prefill, plain decode and spec-verify rows share one executable whose
    compile key is the padded window shape alone. A live slot's logits do
    not depend on what its row's pad slots hold: a pad slot's cache position
    lies strictly past every live query's visible range at every layer.
    Returns ([B, S, V] logits, pools) — or
    ([B, V], pools) when ``logits_at`` names the single chunk slot per
    row to unembed.

    A model with recurrent layers (``GemmaConfig.hybrid``) carries its
    state pool in ``paged_kv["state"]`` (``kv_cache.init_state_pool``) and
    hands it back there: this forward first applies what each live row KEPT
    of its previous window (``state["n"]``), leaves its own window pending
    with ``n`` 0, and the caller writes how many of its tokens the row keeps
    (``models/gemma/ssm.py``).
    """
    B, S = tokens.shape
    _, _, N, psz, _ = paged_kv["k"].shape
    if use_pallas and mesh is None:
        # The ragged kernel only runs under shard_map (a one-device mesh is
        # its trivial case): a bare Mosaic call cannot lower on >1 chip, and
        # the CPU interpreter would not show that.
        raise ValueError("decode_chunk_paged: the kernel route (use_pallas) needs mesh=")
    from mcpx.models.gemma.quant import dequant_layer

    # Weight-only int8 serving mode (models/gemma/quant.py): identity
    # plumbing on plain params; the second of the two param choke points.
    # Quantized leaves stay the HBM-resident buffers — embed rows gather
    # as int8 + per-row scales, layers dequantize per layer INSIDE the
    # scan body (see dequant_layer), unembeds scale on the output.
    # (a mixer + feed-forward pattern carries its residual stream in float32)
    float_stream = cfg.dense_pattern or cfg.conv_ffn
    x = mixer_stream(params, cfg, tokens) if float_stream else embed_tokens(params, cfg, tokens)  # [B, S, D]

    pos_mat = positions[:, None] + jnp.arange(S, dtype=positions.dtype)  # [B, S]
    kv_window = _kv_window(positions, page_table, S, psz, N)
    if cfg.mixer_ffn:
        own = jnp.arange(B, dtype=jnp.int32)
        return _mixer_ffn_chunk(
            params, cfg, x, positions, page_table, paged_kv, kv_window, q_lens,
            state_slots or (own, own),
            use_pallas=use_pallas, interpret=interpret, mesh=mesh, logits_at=logits_at,
            active_cols=active_cols, moe_stats=moe_stats, commit=commit, selection=selection,
        )
    if cfg.scan_ffn:
        return _scan_chunk(
            params, cfg, x, positions, page_table, paged_kv, kv_window, q_lens,
            jnp.arange(B, dtype=jnp.int32),  # row i's state is slot i
            use_pallas=use_pallas, interpret=interpret, mesh=mesh, logits_at=logits_at,
            active_cols=active_cols, moe_stats=moe_stats,
        )
    if cfg.conv_ffn:
        return _conv_chunk(
            params, cfg, x, positions, page_table, paged_kv, kv_window, q_lens,
            jnp.arange(B, dtype=jnp.int32) if state_slots is None else state_slots[1],
            use_pallas=use_pallas, interpret=interpret, mesh=mesh, logits_at=logits_at,
            active_cols=active_cols, moe_stats=moe_stats, routing=routing, commit=commit,
        )
    if cfg.hybrid:
        return _hybrid_chunk(
            params, cfg, x, positions, page_table, paged_kv, kv_window, q_lens,
            jnp.arange(B, dtype=jnp.int32),  # row i's state is slot i
            use_pallas=use_pallas, interpret=interpret, mesh=mesh, logits_at=logits_at,
            active_cols=active_cols, moe_stats=moe_stats, routing=routing,
        )
    stacks, experts = layer_stacks(cfg, params)
    # A sparse feed-forward routes only the window's live slots: a pad slot
    # or an idle row chooses no expert, reads none and is counted nowhere.
    live = jnp.arange(S)[None, :] < q_lens[:, None]
    # Their kernel (``kernels/routed_experts.py``) where ONE device holds the
    # window's rows beside the stacks; a mesh of several keeps the jnp loop,
    # which XLA partitions (no cell runs a sparse model across chips).
    experts_kernel = use_pallas and (mesh is None or mesh.size == 1)

    # A latent cache's kernels fetch a key block whose pages lie side by side
    # in the pool in one copy: which blocks those are is the table's alone,
    # the same for every layer and for the index beside the attention.
    runs = page_run_flags(page_table, psz, N) if cfg.latent else None

    def attend(q, k_all, v_all, layer, window, lp, index):
        if cfg.latent:
            attn, select = _latent_attend(
                q, lp, cfg, k_all, v_all, page_table, positions, q_lens, layer, index, runs,
                mesh=mesh, use_pallas=use_pallas, interpret=interpret,
            )
            # What a comparison may ask for (``selection``): the keys every
            # query read, a bit a key of the row's table.
            return attn, (jnp.packbits(select == 0.0, axis=-1) if selection else None)
        # Both paths stream/gather each sequence's pages ONCE for all S
        # chunk queries (folding the chunk into the batch dim instead would
        # multiply page traffic by S — the dominant decode cost), and the
        # kernel and jnp reference stay in LOCKSTEP on the ragged contract
        # (q_lens) so tier-1's interpret/jnp runs exercise the same
        # semantics TPUs serve. ``window``: this layer's (None: the model
        # has none).
        qg = q.reshape(B, S, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
        if use_pallas:
            out = _ragged_kernel_on_mesh(
                mesh, qg, k_all, v_all, page_table, positions, q_lens, layer, window,
                interpret=interpret,
            )
        else:
            out = ragged_paged_attention_reference(
                qg, k_all, v_all, page_table, positions, q_lens, layer, window
            )
        return out.reshape(B, S, cfg.n_heads * cfg.head_dim), None

    def body(carry, scanned):
        x, k_all, v_all, layer, stats = carry  # pools: [K, L, N, Psz, hd]
        lp, kind = scanned
        lp = dequant_layer(lp, jnp.dtype(cfg.dtype))
        h = rms_norm(x, lp["pre_attn_norm"], cfg.norm_eps, cfg.norm_plus_one)
        q, k, v, index = attention_inputs(h, lp, cfg, pos_mat, kind)  # the pages hold k as attended
        k_all = _write_kv_window(k_all, layer, k, kv_window)
        v_all = _write_kv_window(v_all, layer, v, kv_window)
        attn, read = attend(q, k_all, v_all, layer, kind.get("window"), lp, index)
        x = attention_residual(x, h, attn, lp, cfg)
        x, layer_stats, chosen = feed_forward_residual(
            x, lp, cfg, moe=(experts, sparse_index(cfg, layer), live),
            use_pallas=experts_kernel, interpret=interpret,
        )
        if layer_stats is not None:
            stats = add_layer_stats(stats, layer_stats)
        return (x, k_all, v_all, layer + 1, stats), (chosen, read)

    # One scan a stack (one, but for leading dense layers before sparse
    # ones): the global layer counter, the pools and the counters cross.
    carry = (
        x, paged_kv["k"], paged_kv["v"], jnp.asarray(0, jnp.int32),
        moe_stats_init(cfg) if cfg.n_experts else None,
    )
    reads = []
    for scanned, lo, hi in stacks:
        carry, (chosen, read) = lax.scan(body, carry, (scanned, layer_kinds(cfg, lo, hi)))
        reads.append(read)
    x, k_new, v_new, _, stats = carry
    if stats is not None:
        # What this forward's attention calls read, by row: a live row's
        # context runs through its last live query (``_ragged_n_pages``).
        stats = add_forward_stats(
            cfg, stats, positions + q_lens, q_lens, S,
            None if runs is None else (runs, psz, page_table.shape[1]),
        )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_plus_one)
    pools = {"k": k_new, "v": v_new}
    # What a sparse model's callers may ask for beside the logits: the
    # forward's expert counters (``moe_stats_init``) and the experts chosen.
    extra = ((stats,) if moe_stats else ()) + ((chosen,) if routing else ())
    if selection:
        extra += (jnp.concatenate(reads),)
    if active_cols is not None:
        # Draft verification needs logits at EVERY chunk position, but only
        # over the grammar's C active columns: gather those unembed rows
        # and contract against them — [B, S, C] instead of [B, S, V]. At a
        # 256k SentencePiece vocab with a few-thousand-column grammar this
        # is ~100x less unembed compute/memory than full-vocab all-position
        # logits, which is what makes per-position verification affordable
        # at all (the "last-only unembed" optimisation stays intact for the
        # non-draft path below).
        return (output_logits(params, cfg, x, subset=active_cols), pools) + extra
    if logits_at is not None:
        # Serving only reads ONE position's logits per row (the last valid
        # chunk slot): gather the hidden state BEFORE the unembed so the
        # [B, S, V] logits buffer never exists and the unembed matmul costs
        # 1/S of the all-positions version — at subword vocab sizes that
        # buffer and those FLOPs rival a whole transformer layer.
        x = x[jnp.arange(B), logits_at]  # [B, D]
    return (output_logits(params, cfg, x), pools) + extra
