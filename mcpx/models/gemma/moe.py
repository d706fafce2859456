"""Sparse feed-forward: a router over ``n_experts`` choosing
``n_experts_per_tok`` experts a token, computed for the experts this device
holds (``GemmaConfig.expert_first`` / ``experts_held``; all of them on one
chip that holds the layer, a share under expert parallelism).

``p = softmax(h @ router)`` over ALL experts in float32; the k largest with
their indices; ``w = p_top / sum(p_top)``; the layer's output is ``sum_k w_k
* down_e(act(gate_e h) * (up_e h))`` over the chosen experts held here. What
the absent experts would have added is left out: under expert parallelism
the exchange adds the shares, and on one chip that holds every expert there
is nothing to add.

Under ``router_scoring == "sigmoid"``: ``s = sigmoid(h @ router)`` in float32
over all experts; chosen = the k largest of ``s + bias`` (a per-expert bias
that enters the CHOICE alone); ``w = s[chosen]``, over ``sum w + 1e-20``,
times ``router_scale``. A shared expert
(``d_shared_expert``) is not this file's: every token reads it, so it is a
dense feed-forward among the scanned leaves (``model.feed_forward``), which a
share computes beside its own routed experts' part.

The expert matmuls run as a loop over the experts that at least one LIVE
token chose, each step slicing that expert's three matrices out of the
sparse layers' stacks ``[Ls, E, D, F]`` / ``[Ls, E, F, D]`` (which therefore stay
OUT of the layer scan's ``xs``: scanned, every layer's whole expert stack
would be copied as the inner loop's operand). An expert no live token chose
is never read; a pad slot or an idle row is routed nowhere, reads nothing
and counts in no counter.

Which rows a step multiplies is read off the window's width ``T = B x S``,
static at trace time, against the chip's ridge: peak FLOP/s over HBM bytes/s,
about 240 bf16 rows on a v5e (197e12 / 819e9, ``telemetry/costs.py``), the
width at which multiplying a row by an expert costs what fetching the
expert's 12 MB does. Up to ``RIDGE_SLOTS`` (a decode segment's 64 slots, a
cohort of one's 128) a step multiplies every slot of the window by its
expert, a slot that did not choose it with weight 0: the step is bound by
the expert's bytes and the slots ride free. Past it (the admission cohort's
8 x 128 = 1,024 slots, the suffix prefill at that width) that product is
compute-bound and mostly zeros, so the live (token, expert) assignments are
sorted by expert, their rows of ``x`` gathered in that order, and a step
multiplies one tile of ``GROUP_TILE`` sorted rows by the expert that owns
them: ``sum_e ceil(count_e / GROUP_TILE)`` steps, every touched expert read
once a tile, no row multiplied by an expert it did not choose but the tail
of an expert's last tile. Only the sorted rows that count are gathered,
zero-filled and added back: the form runs over the first ``R`` of them, the
smallest rung of ``GROUP_RUNGS`` (a share of the window's ``T x k`` (slot,
choice) pairs) that holds the assignments counting here, because most pairs
are padding or another share's. What is computed is the same, term for term;
only the order of the float32 sum over a token's experts may differ. No
expert has a capacity: a crowded one takes more tiles.

Each form has a second one, chosen by the caller's ``use_pallas`` (the paged
forward's, ``engine/paged_decode.py``, and the dense admission prefill's,
``model.prefill``): the experts' products leave the jnp loop for ONE kernel
call a layer (``engine/kernels/routed_experts.py``), whose grid fetches each
step's matrices straight out of the stacks, the next step's while this one
multiplies. Same products in the same precisions, same order of the sum.

- At or under the ridge ``routed_experts`` walks the touched experts in the
  loop's order over every slot of the window: a decode segment's 64 slots,
  and a cohort of one's 64 or 128 (named ``prefill_expert_window`` where the
  dense prefill calls it, so that a device trace tells the decode's call,
  whose roofline counts the decode's bytes, from the admission's).
- Past the ridge ``prefill_expert_tiles`` walks the step table above, takes
  each tile's 64 rows out of ``x`` itself by DMA and writes its float32 rows
  into a result nobody zero-filled: no rung, no sorted copy of the rows, one
  call whose operands' shapes are the window's and the stacks' alone
  (``GROUP_RUNGS`` put three compiled arms into every sparse prefill
  executable, and a kernel an arm was three to lower and compile at every
  start: PERF.md, PR 52). A group's rows start at a multiple of
  ``TILE_ALIGN`` there; the rows are added back ``GROUP_TILE`` a turn,
  selected by their weight (what no tile wrote is uninitialised).

The jnp forms, rungs and all, stay what the CPU serves without the
interpreter and what a mesh of several devices partitions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from mcpx.models.gemma.config import GemmaConfig

# Leaves of params["layers"] that hold the experts: closed over by the layer
# scan and sliced by (layer, expert), never scanned.
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def expert_leaves(experts: dict) -> tuple[str, ...]:
    """The expert stacks a layer has, which is a property of the stacks: the
    three of a gated expert, or the two of one with no gate (``act(x U) V``)."""
    return tuple(k for k in EXPERT_LEAVES if k in experts)


def activation(cfg: GemmaConfig, x: jax.Array) -> jax.Array:
    if cfg.activation == "silu":
        return jax.nn.silu(x)
    if cfg.activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.gelu(x, approximate=True)


def split_layers(cfg: GemmaConfig, layers: dict) -> tuple[dict, dict]:
    """(what the layer scan scans, what it closes over)."""
    if not cfg.n_experts:
        return layers, {}
    return (
        {k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
        {k: layers[k] for k in expert_leaves(layers)},
    )


def forward_weight_bytes(cfg: GemmaConfig, params: dict) -> tuple[int, int]:
    """(the bytes of ONE routed expert's three matrices, the bytes of every
    other leaf a forward reads whole: attention, routers, shared experts,
    dense layers, gains, the head). The embedding table is among them only
    where it is the head too; untied, a forward gathers a few of its rows."""
    stacks = [params["layers"][k] for k in expert_leaves(params["layers"])]
    n_experts = stacks[0].shape[0] * stacks[0].shape[1]
    routed = sum(a.nbytes for a in stacks)
    rest = sum(a.nbytes for a in jax.tree.leaves(params)) - routed
    if not cfg.tie_embeddings:
        rest -= params["embed"].nbytes
    return routed // n_experts, rest


def route(
    x: jax.Array, router: jax.Array, cfg: GemmaConfig, bias: "jax.Array | None" = None
) -> tuple[jax.Array, jax.Array]:
    """x [T, D], router [D, E], bias [E] float32 (sigmoid scoring with a
    bias) -> (chosen [T, k] int32, weights [T, k] float32). The scores over
    every expert, in float32, come BEFORE the choice; softmax weights sum to
    1 a token, sigmoid ones to ``router_scale``."""
    logits = jnp.einsum("td,de->te", x, router, preferred_element_type=jnp.float32)
    if cfg.router_scoring == "softmax":
        p_top, chosen = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.n_experts_per_tok)
        return chosen.astype(jnp.int32), p_top / jnp.sum(p_top, axis=-1, keepdims=True)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    if cfg.router_groups:
        # Group-limited: a group of consecutive experts scores the sum of its
        # two largest; only the best ``router_groups_kept`` groups' experts
        # can be chosen.
        T, G = choice.shape[0], cfg.router_groups
        grouped = choice.reshape(T, G, -1)
        _, kept = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], axis=-1), cfg.router_groups_kept)
        in_kept = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None, :], axis=1)  # [T, G]
        choice = jnp.where(in_kept[:, :, None], grouped, -jnp.inf).reshape(T, -1)
    _, chosen = lax.top_k(choice, cfg.n_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)  # the bias chose; it weighs nothing
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.router_norm_eps)
    return chosen.astype(jnp.int32), w * cfg.router_scale


# What a forward counts once, after its layers' own counters (``moe_forward``).
FORWARD_STATS = 3


# What a layer counts after its tokens per held expert (``moe_forward``).
LAYER_STATS = 4

# What a forward of a block with a learned index counts after those.
INDEX_STATS = 2

# What a forward of a latent block counts last: its paged attention calls'
# query slots, the key blocks they fetched and those fetched as one run.
LATENT_STATS = 3

# What a forward of a model with recurrent layers counts last: its Mamba
# layers' calls on live rows, the live window slots those calls computed, and
# the tokens the state was advanced by (a prefill's all stay; a decode
# window's are known after its verify, and the segment adds them).
SSM_STATS = 3

# What a forward of a model with block-selecting attention layers counts
# before those: the tokens its calls attended after the selection, the pooled
# keys they scored, the query slots they computed, the pages those slots'
# programs FETCHED and the pages of their contexts.
BLOCK_STATS = 5


def moe_stats_init(cfg: GemmaConfig) -> jax.Array:
    """The counters a forward adds to: tokens per held expert ``[E_held]``
    (summed over layers), then the number of (layer, expert) pairs with at
    least one live token, then the rows multiplied by an expert's matrices
    (the window's slots a step of the loop, a tile's a grouped step), then
    the expert steps taken (one a touched expert in the loop or the kernel,
    one a tile grouped) and those of them taken through the kernel: a
    layer's own, ``moe_forward``. Then the forward's (``add_forward_stats``):
    the (token, choice) pairs its sparse layers routed, held here or not; the
    context tokens its attention calls read, over live rows and layers; and
    those calls, live rows times layers. A block with a learned index
    (``GemmaConfig.index_topk``) counts two more: the keys those calls
    attended after the selection, and the keys the index scored (a call whose
    row holds no more than ``index_topk`` tokens scores none). A latent block
    counts three more, last: the query slots its paged attention calls
    multiplied (``kernels/paged_attention.latent_query_slots``), the key
    blocks they fetched and those of them fetched in one copy a pool, their
    pages lying side by side (``latent_key_blocks``), each over layers.
    A model with recurrent layers counts ``SSM_STATS`` more, last."""
    more = (
        (INDEX_STATS if cfg.index_topk else 0) + (LATENT_STATS if cfg.latent else 0)
        + (BLOCK_STATS if cfg.n_block_layers else 0) + (SSM_STATS if cfg.hybrid else 0)
    )
    return jnp.zeros((cfg.n_experts_held + LAYER_STATS + FORWARD_STATS + more,), jnp.int32)


def add_layer_stats(stats: jax.Array, layer_stats: jax.Array) -> jax.Array:
    """A layer's counters into the forward's, which are longer by the
    forward's own."""
    return stats.at[: layer_stats.shape[0]].add(layer_stats)


def add_forward_stats(
    cfg: GemmaConfig, stats: jax.Array, context: jax.Array, q_lens: jax.Array,
    window: "int | None" = None, pages: "tuple | None" = None, blocks: "tuple | None" = None,
) -> jax.Array:
    """The forward's own counters: ``context`` [B] the cache positions a
    row's attention read through, ``q_lens`` [B] its live tokens (0: an idle
    row, which reads nothing and routes nothing), ``window`` the slots of
    the window its attention read the pages through (None: a dense prefill,
    which reads no page, and whose every live token stays in a recurrent
    state), ``pages`` of a latent block's window ``(runs, page_size, p_max)``:
    its table's ``page_run_flags`` and geometry, ``blocks`` of a block-selecting
    model's window ``(page_size, p_max, commit)``: None or a ``p_max`` too
    narrow to hold a block a query drops reads every page (a dense prefill,
    plain grouped attention), and so does a ``commit`` window's masked form;
    a decode window's gathered form fetches, a live slot, the pages of its
    chosen blocks up to its own place."""
    live = q_lens > 0
    own = [
        jnp.sum(q_lens) * (cfg.n_experts_per_tok * cfg.n_sparse_layers),
        jnp.sum(jnp.where(live, context, 0)) * cfg.n_attn_layers,
        jnp.sum(live) * cfg.n_attn_layers,
    ]
    if cfg.index_topk:
        k = cfg.index_topk
        own += [
            jnp.sum(jnp.where(live, jnp.minimum(context, k), 0)) * cfg.n_layers,
            jnp.sum(jnp.where(live & (context > k), context, 0)) * cfg.n_layers,
        ]
    if cfg.latent:
        from mcpx.engine.kernels.paged_attention import latent_key_blocks, latent_query_slots

        slots = 0 if window is None else latent_query_slots(q_lens, window, cfg.n_heads)
        blocks = (0, 0) if pages is None else latent_key_blocks(
            pages[0], context - q_lens, q_lens, window, cfg.n_heads, *pages[1:]
        )
        own += [jnp.asarray(n) * cfg.n_layers for n in (slots, *blocks)]
    if cfg.n_block_layers:
        kept = cfg.block_topk * cfg.block_size
        calls = cfg.n_block_layers
        own += [jnp.sum(jnp.where(live, jnp.minimum(context, kept), 0)) * calls]
        selecting = blocks is not None and blocks[1] * blocks[0] > kept
        if not selecting:
            zero = jnp.zeros((), jnp.int32)
            own += [zero, jnp.sum(q_lens) * calls if window is not None else zero, zero, zero]
        else:
            psz, _, commit = blocks
            S = window
            t = (context - q_lens)[:, None] + jnp.arange(S)[None, :]  # [B, S] each slot's position
            on = jnp.arange(S)[None, :] < q_lens[:, None]
            ctx_pages = t // psz + 1
            n_sel = jnp.minimum(t // cfg.block_size + 1, cfg.block_topk)
            got = ((n_sel - 1) * cfg.block_size + t % cfg.block_size) // psz + 1
            per = calls * cfg.n_kv_heads
            own += [
                jnp.sum(jnp.where(live, context // psz, 0)) * per,
                jnp.sum(q_lens) * calls,
                jnp.sum(jnp.where(on, ctx_pages if commit else got, 0)) * per,
                jnp.sum(jnp.where(on, ctx_pages, 0)) * per,
            ]
    if cfg.hybrid:
        slots = jnp.sum(q_lens) * cfg.n_recurrent_layers
        stays = window is None or (blocks is not None and blocks[2])  # a prefill's tokens all stay
        own += [jnp.sum(live) * cfg.n_recurrent_layers, slots, slots if stays else 0 * slots]
    return stats.at[-len(own) :].add(jnp.stack(own).astype(jnp.int32))


# The widest window whose expert steps multiply every slot: the v5e's ridge
# (~240 rows, the module docstring), rounded to the bucket above it.
RIDGE_SLOTS = 256
# Sorted rows a grouped step multiplies. A tile wider than the usual group
# pays for rows it masks, and a narrower one reads a crowded expert's
# weights once more a tile: on a v5e at 1,024 slots 64 read fastest at the
# cohorts the cells admit (about two prompts of 80-90 tokens in an 8 x 128
# window) and no slower than the loop with every slot live (PERF.md, PR 38).
GROUP_TILE = 64


def _expert_rows(cfg: GemmaConfig, rows: jax.Array, experts: dict, layer, e) -> jax.Array:
    """rows [R, D] through expert ``e`` of sparse layer ``layer``, float32
    [R, D]: gate and up in the rows' type, down accumulated in float32. An
    expert with no gate (no ``w_gate`` stack) is ``act(rows up) down``."""
    D, F = rows.shape[1], cfg.d_expert
    w_up = lax.dynamic_slice(experts["w_up"], (layer, e, 0, 0), (1, 1, D, F))[0, 0]
    w_down = lax.dynamic_slice(experts["w_down"], (layer, e, 0, 0), (1, 1, F, D))[0, 0]
    if "w_gate" in experts:
        w_gate = lax.dynamic_slice(experts["w_gate"], (layer, e, 0, 0), (1, 1, D, F))[0, 0]
        a = activation(cfg, jnp.einsum("td,df->tf", rows, w_gate)) * jnp.einsum("td,df->tf", rows, w_up)
    else:
        a = activation(cfg, jnp.einsum("td,df->tf", rows, w_up))
    return jnp.einsum("tf,fd->td", a, w_down, preferred_element_type=jnp.float32)


# Where a group's sorted rows start in what the tile kernel writes: a whole
# float32 sublane tile, the unit Mosaic slices an array in HBM by.
TILE_ALIGN = 8

# The sorted rows the grouped form may hold, as shares of the window's T x k
# (slot, choice) pairs: it runs at the smallest that holds the assignments
# that count here. A share of a sixteenth of the experts, or a cohort that is
# mostly padding, counts a twentieth to a sixth of its pairs.
GROUP_RUNGS = (16, 4, 1)


def _grouped_experts(
    cfg: GemmaConfig, x, experts: dict, layer, local, here, w, counts,
    *, use_pallas: bool = False, interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The routed experts' output for a window past the ridge -> (float32
    [T, D], rows multiplied). ``local`` [T, k] the chosen experts as held
    here, ``here`` [T, k] the assignments that count, ``w`` their weights,
    ``counts`` [E] assignments an expert. ``use_pallas``: the tiles through
    ONE kernel call (``engine/kernels/routed_experts.prefill_expert_tiles``),
    which takes a tile's rows out of ``x`` itself and has no rung."""
    (T, D), k, E, C = x.shape, cfg.n_experts_per_tok, cfg.n_experts_held, GROUP_TILE
    N = T * k
    keys = jnp.where(here, local, E).reshape(N)  # an assignment that counts nowhere: last
    w = jnp.where(here, w, 0.0)
    if use_pallas:
        # The kernel writes a tile's float32 rows as whole sublane tiles, so
        # a group starts at a multiple of ``TILE_ALIGN`` sorted rows: behind
        # an expert's assignments sort the few empty rows (no pair: token 0,
        # weight 0) that fill its group up. ONE array is sorted, expert and
        # pair packed into a word: XLA compiles an unstable sort of one
        # operand and a power of two of elements in a second, a stable or a
        # several-operand one of 32,768 in a quarter of a minute an
        # executable (PERF.md, PR 53). The packed words differ, so the order
        # is the stable one: by expert, then by token.
        A, bits = TILE_ALIGN, N.bit_length()  # a pair's index; N itself: no pair
        assert (E + 2) << bits < 2**31, (E, N)
        held = (counts + A - 1) // A * A  # a group's rows
        fill = jnp.where(
            jnp.arange(A - 1)[None, :] < (held - counts)[:, None], jnp.arange(E, dtype=jnp.int32)[:, None], E
        ).reshape(-1)
        packed = jnp.concatenate([keys << bits | jnp.arange(N, dtype=jnp.int32), fill << bits | N])
        n_sort = 1 << (packed.shape[0] - 1).bit_length()
        packed = jnp.pad(packed, (0, n_sort - packed.shape[0]), constant_values=(E + 1) << bits | N)
        # What the kernel's result has: a row a pair, the groups' fill (to a
        # whole sublane tile) and a tile of padding behind all.
        R = (N + fill.shape[0] + A - 1) // A * A + C
        pair = jnp.pad(lax.sort(packed, is_stable=False)[: R - C] & ((1 << bits) - 1), (0, C), constant_values=N)
        tok = jnp.where(pair < N, pair // k, 0)
        w_of = jnp.pad(w.reshape(N), (0, 1))  # a pair's weight, 0 behind them for no pair
        weights = lambda j: w_of[lax.dynamic_slice(pair, (j * C,), (C,))]
    else:
        # Assignments by expert: their tokens and weights in that order, a
        # tile of padding behind them.
        held = counts
        order = jnp.argsort(keys, stable=True).astype(jnp.int32)
        tok = jnp.pad(order // k, (0, C))
        ws = jnp.pad(w.reshape(N)[order], (0, C))
        weights = lambda j: lax.dynamic_slice(ws, (j * C,), (C,))
    # step -> (expert, first sorted row), for sum_e ceil(count_e / C) steps.
    first = jnp.cumsum(held) - held
    tiles = (counts + C - 1) // C
    tile_end = jnp.cumsum(tiles)
    steps = jnp.arange(E + N // C, dtype=jnp.int32)  # more than any routing takes
    step_e = jnp.minimum(jnp.sum(steps[:, None] >= tile_end, axis=1, dtype=jnp.int32), E - 1)
    step_row = first[step_e] + (steps - (tile_end[step_e] - tiles[step_e])) * C
    n_steps, n_here, n_rows = tile_end[-1], jnp.sum(counts), jnp.sum(held)

    def add_back(ys):
        """The sorted rows that count, weighed, onto their tokens: C a turn,
        in expert order. A row that counts nowhere weighs 0 and is SELECTED
        away, not multiplied: the kernel's ``ys`` is written only where a
        tile lay, and the rest of it is whatever the memory held."""

        def add_tile(j, out):
            wj = weights(j)[:, None]
            y = jnp.where(wj != 0, lax.dynamic_slice(ys, (j * C, 0), (C, D)) * wj, 0.0)
            return out.at[lax.dynamic_slice(tok, (j * C,), (C,))].add(y)

        return lax.fori_loop(0, (n_rows + C - 1) // C, add_tile, jnp.zeros((T, D), jnp.float32))

    if use_pallas:
        from mcpx.engine.kernels.routed_experts import prefill_expert_tiles

        ys = prefill_expert_tiles(
            x, *(experts.get(leaf) for leaf in EXPERT_LEAVES), step_e, step_row, tok, n_steps, layer,
            tile=C, act=functools.partial(activation, cfg), interpret=interpret,
        )
        return add_back(ys), n_steps * C

    def at_rung(R):
        """The form over the first ``R`` sorted rows, which hold every
        assignment that counts: what it gathers, zero-fills and adds back is
        ``R`` rows, not ``N``."""
        # A group's last tile runs up to C rows past it: into the next
        # group's rows, or the padding.
        xs = jnp.concatenate([x[tok[:R]], jnp.zeros((C, D), x.dtype)])

        def one_tile(i, ys):
            # Steps run in the order of their rows, so what a tile writes
            # past its group is overwritten by the group that owns those rows.
            rows = lax.dynamic_slice(xs, (step_row[i], 0), (C, D))
            y = _expert_rows(cfg, rows, experts, layer, step_e[i])
            return lax.dynamic_update_slice(ys, y, (step_row[i], 0))

        ys = lax.fori_loop(0, n_steps, one_tile, jnp.zeros((R + C, D), jnp.float32))
        if R == N:
            # Most pairs count: every pair has its row, and a token gathers
            # its k back (cheaper a row than adding them).
            place = jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(N, dtype=jnp.int32))
            return jnp.sum(ys[place].reshape(T, k, D) * w[:, :, None], axis=1)
        return add_back(ys)

    rungs = [N // share for share in GROUP_RUNGS]
    rung = jnp.sum(n_here > jnp.asarray(rungs[:-1]), dtype=jnp.int32)
    return lax.switch(rung, [functools.partial(at_rung, R) for R in rungs]), n_steps * C


def moe_forward(
    h: jax.Array,  # [B, S, D]; routed on, and multiplied unless ``rows`` is given
    router: jax.Array,  # [D, E] this layer's
    experts: dict,  # w_gate / w_up [L, E_held, D, F], w_down [L, E_held, F, D]
    layer: jax.Array,
    cfg: GemmaConfig,
    live: "jax.Array | None" = None,  # [B, S] bool; None = every slot
    bias: "jax.Array | None" = None,  # [E] this layer's, into the choice
    *,
    use_pallas: bool = False,  # the experts' products through a kernel call, not a jnp loop
    interpret: bool = False,
    rows: "jax.Array | None" = None,  # [B, S, Dl]: what the experts multiply (a latent of h)
    prefill: bool = False,  # the dense admission prefill's window: its kernel call under the ridge is named for it
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (this device's routed experts' part of the layer's output [B, S,
    D] as accumulated, float32, the counters of ``moe_stats_init`` for this layer, the experts
    chosen [B, S, k]). ``layer`` indexes the expert stacks: the SPARSE
    layer's number. With ``rows`` the router reads ``h`` and the experts
    read and write ``rows``' width: the output is [B, S, Dl]."""
    B, S, _ = h.shape
    T, E, k = B * S, cfg.n_experts_held, cfg.n_experts_per_tok
    chosen, w = route(h.reshape(T, -1), router, cfg, bias)
    D = h.shape[-1] if rows is None else rows.shape[-1]
    x = (h if rows is None else rows).reshape(T, D)
    local = chosen - cfg.expert_first
    here = (local >= 0) & (local < E)
    if live is not None:
        here &= live.reshape(T, 1)
    onehot = here[:, :, None] & (local[:, :, None] == jnp.arange(E, dtype=jnp.int32))
    counts = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)  # [E]
    touched = counts > 0
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    kernel_steps = jnp.zeros((), jnp.int32)
    if T > RIDGE_SLOTS:
        out, rows = _grouped_experts(
            cfg, x, experts, layer, local, here, w, counts, use_pallas=use_pallas, interpret=interpret
        )
        steps = rows // GROUP_TILE
        if use_pallas:
            kernel_steps = steps
    else:
        combine = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)  # [T, E]
        order = jnp.argsort(~touched, stable=True).astype(jnp.int32)  # touched first
        if use_pallas:
            from mcpx.engine.kernels.routed_experts import routed_experts

            out = routed_experts(
                x, combine, *(experts.get(k) for k in EXPERT_LEAVES), order, n_touched, layer,
                act=functools.partial(activation, cfg), interpret=interpret,
                name="prefill_expert_window" if prefill else "routed_experts",
            )
            kernel_steps = n_touched
        else:
            by_expert = combine.T  # [E, T]

            def one_expert(i, acc):
                e = order[i]
                y = _expert_rows(cfg, x, experts, layer, e)
                return acc + y * lax.dynamic_index_in_dim(by_expert, e, 0, keepdims=False)[:, None]

            out = lax.fori_loop(0, n_touched, one_expert, jnp.zeros((T, D), jnp.float32))
        rows, steps = n_touched * T, n_touched
    stats = jnp.concatenate([counts, jnp.stack([n_touched, rows, steps, kernel_steps])])
    return out.reshape(B, S, D), stats, chosen.reshape(B, S, k)
