"""Paged KV cache: host-side block allocator + device-side page pools.

vLLM-style paging re-designed for TPU (see PAPERS.md "Ragged Paged
Attention ... for TPU"): the device holds K/V page pools laid out
**kv-head-major, all layers in one array** — ``[K, L, N_pages, page_size,
head_dim]`` — so (a) the decode kernel's per-(batch, kv-head) grid step
DMAs one contiguous ``[page_size, head_dim]`` tile per page with no
in-kernel transposes, and (b) the decode loop can thread the pools through
``lax.scan`` as a CARRY and write each layer's chunk in place, whole pages
at a time, in this same shape (``paged_decode._write_kv_window``; like
``commit_prefill_to_pages`` below, every write through XLA is a scatter of
whole ``[page_size, head_dim]`` pages, so the pools never leave the layout
the kernel reads — a row-granular write made XLA relayout the whole pool
around it, PERF.md PR 25). The ``K`` axis shards over the mesh's ``model``
axis when divisible (GQA); MQA replicates KV, the standard MQA-TP layout.

The allocator is deliberately host-side, synchronous, single-writer (the
scheduler owns it): allocation is bookkeeping, not compute, and a single
writer makes the paged-KV races SURVEY.md §5 worries about structurally
impossible. Invariants are enforced and tested (alloc/free balance, no
double-free, no page aliasing).
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from mcpx.core.errors import EngineError
from mcpx.models.gemma.config import GemmaConfig
from mcpx.utils.ownership import owned_by


@dataclass
class PageStats:
    total_pages: int
    free_pages: int
    sequences: int

    @property
    def utilization(self) -> float:
        return 1.0 - self.free_pages / max(1, self.total_pages)


@owned_by("engine-worker")
class PageAllocator:
    """Free-list page allocator; page 0 is reserved as the null page.
    Single-writer by construction — the engine worker thread owns it, and
    the ``owned_by`` marks (class + mutators) let mcpxlint's
    thread-ownership pass prove no other thread can reach a mutation.

    It always hands out the LOWEST free ids, ascending (the free list is a
    min-heap: O(log n) a page taken or returned, whatever was freed and in
    what order), so a sequence's pages, and the pages of allocations made
    back to back (a catalogue head built in chunks, a row's tree node and
    its own pages), lie side by side in the pools wherever the free ids do.
    The latent kernels fetch such a run of a key block's pages in one copy
    (``kernels/paged_attention.page_run_flags``): the order is speed, never
    correctness, and nothing may rely on an id's value."""

    def __init__(self, n_pages: int, page_size: int, max_pages_per_seq: int) -> None:
        if n_pages < 2:
            raise EngineError("need at least 2 pages (page 0 is reserved)")
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.n_pages = n_pages
        self._free: list[int] = list(range(1, n_pages))  # min-heap; 0 reserved
        self._seq_pages: dict[int, list[int]] = {}

    # ------------------------------------------------------------------ api
    def can_allocate(self, n_tokens: int) -> bool:
        return len(self._free) >= self.pages_needed(n_tokens)

    def pages_needed(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    @owned_by("engine-worker")
    def allocate(self, seq_id: int, n_tokens: int) -> list[int]:
        """Allocate pages to hold ``n_tokens``; returns the page list."""
        if seq_id in self._seq_pages:
            raise EngineError(f"sequence {seq_id} already has pages")
        need = self.pages_needed(n_tokens)
        if need > self.max_pages_per_seq:
            raise EngineError(
                f"sequence needs {need} pages > max_pages_per_seq={self.max_pages_per_seq}"
            )
        if need > len(self._free):
            raise EngineError(f"out of KV pages: need {need}, free {len(self._free)}")
        pages = [heapq.heappop(self._free) for _ in range(need)]
        self._seq_pages[seq_id] = pages
        return list(pages)

    @owned_by("engine-worker")
    def extend(self, seq_id: int, n_tokens_total: int) -> list[int]:
        """Grow a sequence's page list to cover ``n_tokens_total``; returns
        the (possibly unchanged) full page list."""
        pages = self._seq_pages.get(seq_id)
        if pages is None:
            raise EngineError(f"unknown sequence {seq_id}")
        need = self.pages_needed(n_tokens_total)
        if need > self.max_pages_per_seq:
            raise EngineError(
                f"sequence {seq_id} exceeds max_pages_per_seq={self.max_pages_per_seq}"
            )
        while len(pages) < need:
            if not self._free:
                raise EngineError("out of KV pages during extend")
            pages.append(heapq.heappop(self._free))
        return list(pages)

    @owned_by("engine-worker")
    def split(self, src_id: int, dst_id: int, n_head_pages: int) -> list[int]:
        """Move ownership of ``src_id``'s FIRST ``n_head_pages`` pages to a
        new sequence ``dst_id``; returns them. No device work — page ids are
        bookkeeping — which is what lets the radix prefix cache split a
        cached KV run at a page boundary without touching HBM
        (engine/prefix_cache.py). The moved pages keep their ids, so page
        tables already naming them stay valid."""
        pages = self._seq_pages.get(src_id)
        if pages is None:
            raise EngineError(f"unknown sequence {src_id}")
        if dst_id in self._seq_pages:
            raise EngineError(f"sequence {dst_id} already has pages")
        if not 0 < n_head_pages < len(pages):
            raise EngineError(
                f"split of {len(pages)} pages at {n_head_pages} leaves an "
                "empty side (both sequences must keep at least one page)"
            )
        self._seq_pages[dst_id] = pages[:n_head_pages]
        self._seq_pages[src_id] = pages[n_head_pages:]
        return list(self._seq_pages[dst_id])

    @owned_by("engine-worker")
    def free(self, seq_id: int) -> None:
        pages = self._seq_pages.pop(seq_id, None)
        if pages is None:
            return
        for p in pages:
            if p <= 0 or p >= self.n_pages:
                raise EngineError(f"corrupt page id {p}")
            heapq.heappush(self._free, p)

    def pages_of(self, seq_id: int) -> list[int]:
        return list(self._seq_pages.get(seq_id, []))

    def stats(self) -> PageStats:
        return PageStats(
            total_pages=self.n_pages,
            free_pages=len(self._free),
            sequences=len(self._seq_pages),
        )

    def check_invariants(self) -> None:
        """Test hook: free list + allocated pages partition [1, n_pages)."""
        seen: set[int] = set()
        for p in self._free:
            if p in seen:
                raise EngineError(f"page {p} double-present in free list")
            seen.add(p)
        for seq, pages in self._seq_pages.items():
            for p in pages:
                if p in seen:
                    raise EngineError(f"page {p} aliased (seq {seq})")
                seen.add(p)
        if seen != set(range(1, self.n_pages)):
            raise EngineError("page leak: free+allocated != all pages")


# ------------------------------------------------------------------- device
def init_paged_kv(
    cfg: GemmaConfig, n_pages: int, page_size: int, dtype: str | None = None
) -> dict[str, jax.Array]:
    """Device page pools: ``[K, L, N_pages, page_size, head_dim]``. Under
    latent attention (``GemmaConfig.kv_widths``) K is 1 and a page holds ONE
    row a token: ``k`` the rotated key every head shares (its values, then
    zeros up to a lane width) and ``v`` the normed latent, which the absorbed
    kernel reads for the scores and for the values."""
    d = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.kv_pool_heads, cfg.n_attn_layers, n_pages, page_size)
    k_width, v_width = cfg.kv_widths
    return {"k": jnp.zeros(shape + (k_width,), d), "v": jnp.zeros(shape + (v_width,), d)}


def init_state_pool(cfg: GemmaConfig, n_slots: int, window: int, n_pages: int = 0) -> dict:
    """The per-row state beside the pages, of four kinds. First, what a Mamba
    layer keeps of a row, indexed by SLOT (slab row ``i`` owns slot ``i``), not by
    page. ``{}`` for a model with no such layer: an empty pytree adds nothing
    to a jitted call. Else ``{"ssm", "layers": one dict a Mamba layer, "n":
    [n_slots] int32}``: ``ssm`` ``[Mamba layers, n_slots, state, heads x
    head_dim]`` float32, the recurrent states (heads x head_dim merged and
    innermost: the kernel's lanes, a whole number of lane widths; a
    ``[.., heads, head_dim]`` view of the pool would be another tiling, a copy
    of the pool a forward; the layers in ONE array, which the compiler
    cannot stage through VMEM around a layer's call: ``kernels/ssm.py``); and
    a layer's small arrays, each its own: ``conv`` ``[n_slots, K - 1, C]``,
    the convolution's last inputs, and the slot's PENDING decode window of up
    to ``window`` tokens, which a forward leaves and the next applies as far
    as ``n`` says the row kept it (``models/gemma/ssm.py``): ``dt``
    ``[n_slots, window, heads]`` float32, ``pre`` and ``post`` ``[n_slots,
    window, C]``, the convolution's inputs and outputs. A forward updates
    each in place.

    A LINEAR-attention layer (``GemmaConfig.n_linear_layers``) is the pool's
    second kind: the same ``ssm`` array (state ``head_dim`` of the key on the
    sublanes, heads x ``head_dim`` of the value on the lanes), no ``conv``,
    and a pending window that holds the window's keys and values: ``dt``
    ``[n_slots, window, heads]`` float32 (1 on a live slot), ``k`` and ``v``
    ``[n_slots, window, heads, head_dim]``. Such a model's pool has one slot
    beyond the slab rows' (the caller's ``n_slots``): the declared shared
    head's END STATE, which a row that matches the head starts from.

    With block-selecting attention layers (``n_block_layers``) it also holds
    ``ksum`` ``[K, those layers, n_pages, head_dim]`` float32, a row a PAGE
    of the page pools: the sum of that page's keys (``models/gemma/sparse.py``).
    It rides here because this pytree is what the prefill, the suffix prefill
    and the segment of such a model hand on beside the two page pools; it is
    indexed by page, not by slot, and depends on its page's tokens alone, so
    whatever shares a page shares its row.

    A gated SHORT CONVOLUTION (``GemmaConfig.n_conv_layers``) is the pool's
    THIRD kind: NO ``ssm`` array, because the layer's whole state is its
    convolution's last ``conv_kernel - 1`` inputs. A layer's ``conv``
    ``[n_slots, K - 1, D]`` is the row's LIVE tail, at its last committed
    token, ``pre`` ``[n_slots, window, D]`` the inputs of the window its last
    forward left pending and ``n`` how many of them the row kept. And because
    that state is 16 KB a layer (float32, at 2,048 wide), it is ALSO kept at
    every page boundary: ``tails`` ``[conv layers, n_pages, K - 1, D]``
    float32, a row a PAGE as ``ksum``'s:
    layer ``c``'s inputs at the page's last ``K - 1`` slots, written by every
    program that fills a PROMPT page to its last slot, in the same program as
    the page's keys. A row that matches ``P`` tokens of the radix tree starts
    its suffix prefill from ``tails[:, page_table[row, P / page_size - 1]]``
    whatever node or split the match came from: split, pin, evict and the
    pending epoch move page ids, and the tails go with them
    (``GemmaConfig.page_state``; docs/engine.md "The state's rule").

    A Mamba-1 SELECTIVE SCAN (``GemmaConfig.n_scan_layers``) is the pool's
    FOURTH kind. Its walk over the layers is a ``lax.scan`` over each run of
    like layers, and a scan cannot index a tuple of dicts by its carried layer
    number: so there is no ``layers`` tuple, and EVERY array is stacked a
    layer, ``[J layers, n_slots, ...]``: ``ssm`` ``[.., state, inner]`` float32
    (the second kind's layout: the state's N on the sublanes, the channels on
    the lanes), ``conv`` ``[.., K - 1, inner]`` the convolution's last inputs,
    and the slot's PENDING window: ``dt`` ``[.., window, inner]``, ``pre`` and
    ``x`` ``[.., window, inner]`` the convolution's inputs and outputs, ``b``
    ``[.., window, state]``, all float32 (the mixer's own precision between
    its weight matrices: ``models/gemma/ssm.py``). The rule is the first
    kind's word for word (``models/gemma/ssm.py::selective_window``): a
    forward walks what ``n`` says the row kept of its pending window into
    ``ssm``, moves ``conv`` over it, and leaves its own window pending. No
    page and no radix node holds such a state: a row prefills whole."""
    if not cfg.n_recurrent_layers:
        return {}
    d = jnp.dtype(cfg.dtype)
    if cfg.scan_ffn:
        L, I, N = cfg.n_scan_layers, cfg.scan_inner, cfg.ssm_state_size
        f32 = jnp.float32
        return {
            "ssm": jnp.zeros((L, n_slots, N, I), f32),
            "conv": jnp.zeros((L, n_slots, cfg.conv_kernel - 1, I), f32),
            "dt": jnp.zeros((L, n_slots, window, I), f32),
            "pre": jnp.zeros((L, n_slots, window, I), f32),
            "x": jnp.zeros((L, n_slots, window, I), f32),
            "b": jnp.zeros((L, n_slots, window, N), f32),
            "n": jnp.zeros((n_slots,), jnp.int32),
        }
    if cfg.conv_ffn:
        K1, D, L = cfg.conv_kernel - 1, cfg.d_model, cfg.n_conv_layers
        f32 = jnp.float32  # (the mixer's own precision: ``models/gemma/ssm.py`` says why)
        return {
            "layers": tuple(
                {"conv": jnp.zeros((n_slots, K1, D), f32), "pre": jnp.zeros((n_slots, window, D), f32)}
                for _ in range(L)
            ),
            "n": jnp.zeros((n_slots,), jnp.int32),
            "tails": jnp.zeros((L, n_pages, K1, D), f32),
        }
    if cfg.mixer_ffn:
        H, hd, L = cfg.n_heads, cfg.head_dim, cfg.n_linear_layers
        pool = {
            "ssm": jnp.zeros((L, n_slots, hd, H * hd), jnp.float32),
            "layers": tuple(
                {
                    "dt": jnp.zeros((n_slots, window, H), jnp.float32),
                    "k": jnp.zeros((n_slots, window, H, hd), d),
                    "v": jnp.zeros((n_slots, window, H, hd), d),
                }
                for _ in range(L)
            ),
            "n": jnp.zeros((n_slots,), jnp.int32),
        }
        if cfg.n_block_layers:
            pool["ksum"] = jnp.zeros((cfg.n_kv_heads, cfg.n_block_layers, n_pages, hd), jnp.float32)
        return pool
    C = cfg.conv_width

    def layer():
        return {
            "conv": jnp.zeros((n_slots, cfg.conv_kernel - 1, C), d),
            "dt": jnp.zeros((n_slots, window, cfg.mamba_n_heads), jnp.float32),
            "pre": jnp.zeros((n_slots, window, C), d),
            "post": jnp.zeros((n_slots, window, C), d),
        }

    L = cfg.n_mamba_layers
    return {
        "ssm": jnp.zeros((L, n_slots, cfg.ssm_state_size, cfg.mamba_inner), jnp.float32),
        "layers": tuple(layer() for _ in range(L)),
        "n": jnp.zeros((n_slots,), jnp.int32),
    }


def write_prefill_state(state: dict, slots: jax.Array, finals: list) -> dict:
    """A prefill's states ``[(h [A, N, H, P], tail [A, K - 1, C])]`` a Mamba
    layer into ``slots`` [A] (a padding row's slot is out of range and
    dropped): the state AT each prompt's length, nothing pending. A short
    convolution's finals are ``(tail [A, K - 1, D], u)``: the tail alone goes
    to the slot (``commit_prefill_tails`` cuts the pages' from ``u``). A
    selective scan's finals are stacked as its pool is: ``(h [J layers, A, N,
    I], tail [J layers, A, K - 1, I])``."""
    if "layers" not in state:  # the fourth kind: every array stacked a layer
        h, tail = finals
        return {
            **state,
            "ssm": state["ssm"].at[:, slots].set(h, mode="drop"),
            "conv": state["conv"].at[:, slots].set(tail, mode="drop"),
            "dt": state["dt"].at[:, slots].set(0.0, mode="drop"),
            "n": state["n"].at[slots].set(0, mode="drop"),
        }
    if "ssm" not in state:
        layers = tuple(
            {**pool, "conv": pool["conv"].at[slots].set(tail.astype(pool["conv"].dtype), mode="drop")}
            for pool, (tail, _u) in zip(state["layers"], finals)
        )
        return {**state, "layers": layers, "n": state["n"].at[slots].set(0, mode="drop")}
    ssm, layers = state["ssm"], []
    if "conv" not in state["layers"][0]:  # linear layers: a state alone, no tail
        for j, (pool, h) in enumerate(zip(state["layers"], finals)):
            ssm = ssm.at[j, slots].set(h.reshape(h.shape[:2] + (-1,)), mode="drop")
            layers.append({**pool, "dt": pool["dt"].at[slots].set(0.0, mode="drop")})
        return {**state, "ssm": ssm, "layers": tuple(layers), "n": state["n"].at[slots].set(0, mode="drop")}
    for j, (pool, (h, tail)) in enumerate(zip(state["layers"], finals)):
        ssm = ssm.at[j, slots].set(h.reshape(h.shape[:2] + (-1,)), mode="drop")
        layers.append({
            **pool,
            "conv": pool["conv"].at[slots].set(tail.astype(pool["conv"].dtype), mode="drop"),
            "dt": pool["dt"].at[slots].set(0.0, mode="drop"),
        })
    return {"ssm": ssm, "layers": tuple(layers), "n": state["n"].at[slots].set(0, mode="drop")}


def commit_prefill_to_pages(
    paged: dict[str, jax.Array],
    dense: dict[str, jax.Array],
    page_table: jax.Array,
    seq_lens: jax.Array,
    page_size: int,
) -> dict[str, jax.Array]:
    """Scatter a dense prefill cache ``[L, B, T, K, hd]`` into the page pools.

    ``page_table`` is [B, Pmax] int32 (0 = null page). Chunks beyond a
    sequence's pages are routed to the reserved null page 0, which is never
    read (positions are masked by seq_lens at attention time).
    """
    L, B, T, K, _ = dense["k"].shape
    n_chunks = T // page_size
    if T % page_size:
        raise EngineError(f"prefill length {T} not a multiple of page_size {page_size}")

    def scatter(pool: jax.Array, dense_arr: jax.Array) -> jax.Array:
        # dense [L, B, T, K, hd] -> [K, L, B*n_chunks, page_size, hd]
        hd = dense_arr.shape[-1]  # the two pools' widths may differ (latent attention)
        chunks = dense_arr.reshape(L, B, n_chunks, page_size, K, hd)
        chunks = chunks.transpose(4, 0, 1, 2, 3, 5).reshape(
            K, L, B * n_chunks, page_size, hd
        )
        dest = page_table[:, :n_chunks].reshape(B * n_chunks)  # page id per chunk
        return pool.at[:, :, dest].set(chunks, mode="drop")

    return {"k": scatter(paged["k"], dense["k"]), "v": scatter(paged["v"], dense["v"])}


def commit_prefill_key_sums(
    ksum: jax.Array, dense_k: jax.Array, page_table: jax.Array, page_size: int
) -> jax.Array:
    """The key-sum pool ``[K, L, N, hd]`` with the row of every page a dense
    prefill wrote (``dense_k`` [L, B, T, K, hd]) set to that page's sum. A
    page past a prompt's end sums pad keys: its row is read only once the
    page is full, and the write that fills it sums it again."""
    L, B, T, K, hd = dense_k.shape
    n_chunks = T // page_size
    sums = dense_k.astype(jnp.float32).reshape(L, B, n_chunks, page_size, K, hd).sum(axis=3)
    sums = sums.transpose(3, 0, 1, 2, 4).reshape(K, L, B * n_chunks, hd)
    dest = page_table[:, :n_chunks].reshape(B * n_chunks)
    return ksum.at[:, :, dest].set(sums, mode="drop")


def commit_prefill_tails(
    tails: jax.Array, finals: list, page_table: jax.Array, page_size: int
) -> jax.Array:
    """The page-tail pool ``[C layers, N, K - 1, D]`` with the row of every
    page a dense prefill wrote set to that page's tail: layer ``c``'s
    convolution inputs ``u`` [B, T, D] (``finals[c][1]``) at the page's last
    ``K - 1`` slots. A page past a prompt's end, or its last, partial one,
    gets pad inputs: such a page never enters the radix tree (a row inserts
    whole pages of its prompt), so its row is never read."""
    K1 = tails.shape[2]
    u = jnp.stack([u for _tail, u in finals])  # [C, B, T, D]
    C, B, T, D = u.shape
    n_chunks = T // page_size
    cut = u.reshape(C, B, n_chunks, page_size, D)[:, :, :, page_size - K1 :]
    dest = page_table[:, :n_chunks].reshape(B * n_chunks)
    return tails.at[:, dest].set(
        cut.reshape(C, B * n_chunks, K1, D).astype(tails.dtype), mode="drop"
    )
