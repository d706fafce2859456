"""A Mamba-1 SELECTIVE SCAN (``GemmaConfig.scan_ffn``'s ``J`` layers) as a
kernel (Pallas TPU): the recurrence

  h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
  y_t[c]    = sum_n C_t[n] h_t[n, c]

WALKED a token at a time with the state resident. Its decay is a value a
(state, channel) pair, so a chunk has no matrix form (``kernels/ssm.py``
commits a Mamba-2 window as ``total * h + bt @ xs`` because a head has ONE
decay): there is nothing here for the MXU, 16 ``exp`` a channel a token for the
transcendental unit and half a dozen vector operations beside each.

One body (``_walk``), two call names, so a device trace tells them apart:

  ``selective_scan_prefill``  a prompt from an EMPTY state: grid (row, lane
      block of channels, time block); ``dt``, ``x`` and ``y`` stream in time
      blocks of ``TIME_BLOCK`` tokens, the state block ``[N, M_BLK]`` float32 is
      the call's own output block and stays in VMEM over a row's time blocks;
      a row is walked as far as its own length (a scalar prefetch), the rest
      of its ``y`` zeros, so a padding row of a cohort costs nothing.
  ``selective_scan_window``   a decode window against the state pool: grid
      (row, lane block); the pool of EVERY ``J`` layer ``[layers, slots, N,
      I]`` is aliased in place and the layer is a scalar prefetch (the walk
      over the layers is a ``lax.scan``: the layer is a traced number); a live
      row's state is read once, walked over what the row KEPT of its pending
      window (``dt`` 0 on the rest), written once, then walked over this
      window's slots into ``y`` alone: the window stays pending
      (``models/gemma/ssm.py::selective_window``). An idle row repeats a
      neighbour's block indices and moves nothing, as ``ssm_window``'s.

The layout: the state's N (16) on the sublanes, the channels on the lanes.
``A``, ``dt``, ``x`` and ``y`` are lane vectors; ``B_t`` and ``C_t`` are
wanted as SUBLANE vectors ``[N, 1]`` broadcast along the lanes, so the caller
hands ``B`` and ``C`` with time on the lanes (``[time / 128, N, 128]``) and a
step takes its column by a masked lane reduction. A loop iteration takes
``GROUP`` = 8 tokens: one aligned sublane tile of ``dt`` and ``x`` in, one of
``y`` out, nothing transposed.

Its bound is not the HBM: a prefill call streams ``dt``, ``x`` and ``y`` (3 x
``[T, I]`` float32) and evaluates ``T x N x I`` exponentials
(``benchmarks/chip/reader_files/selective_scan_roofline.py`` reads it against
the bytes alone, and says so).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 8  # tokens a loop iteration takes: a sublane tile of dt, x and y
TIME_BLOCK = 256  # tokens a grid step of the prefill form streams
M_BLOCK = 512  # channels a grid step holds: the state block is [N, M_BLOCK]


def _blocking(I: int) -> int:
    """``M_BLK``: the widest whole number of lane widths up to ``M_BLOCK``
    that divides the channels."""
    if I % LANES:
        return I  # narrower than a lane width (the CPU tests' sizes)
    return max(m for m in range(LANES, min(I, M_BLOCK) + 1, LANES) if I % m == 0)


def _walk(h, a, dt_ref, x_ref, bt_ref, ct_ref, y_ref, g0, g1, y_first: int):
    """The body: ``h`` [N, M_BLK] walked over the time groups ``g0 .. g1`` (of
    ``GROUP`` tokens) of the block's refs, ``dt`` / ``x`` [time, M_BLK] and
    ``bt`` / ``ct`` [time / 128, N, 128]; with a ``y_ref``, token ``t``'s
    read-out goes to its row ``t - y_first``. -> h after group ``g1 - 1``."""
    N, M = h.shape
    lane = lax.broadcasted_iota(jnp.int32, (N, LANES), 1)
    row = lax.broadcasted_iota(jnp.int32, (GROUP, M), 0)
    per_block = LANES // GROUP

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        dt8 = dt_ref[pl.ds(t0, GROUP), :]
        dtx8 = dt8 * x_ref[pl.ds(t0, GROUP), :]
        first = (g % per_block) * GROUP
        b_blk = bt_ref[g // per_block]
        c_blk = None if y_ref is None else ct_ref[g // per_block]
        y8 = jnp.zeros((GROUP, M), jnp.float32)
        for k in range(GROUP):
            here = lane == first + k
            b_t = jnp.sum(jnp.where(here, b_blk, 0.0), axis=1, keepdims=True)  # [N, 1]
            h = jnp.exp(dt8[k : k + 1, :] * a) * h + b_t * dtx8[k : k + 1, :]
            if y_ref is not None:
                c_t = jnp.sum(jnp.where(here, c_blk, 0.0), axis=1, keepdims=True)
                y8 = jnp.where(row == k, jnp.sum(h * c_t, axis=0, keepdims=True), y8)
        if y_ref is not None:
            y_ref[pl.ds(pl.multiple_of(t0 - y_first, GROUP), GROUP), :] = y8
        return h

    return lax.fori_loop(g0, g1, group, h)


def _prefill_kernel(len_ref, a_ref, dt_ref, x_ref, bt_ref, ct_ref, y_ref, h_ref, *, t_blk: int):
    b, tb = pl.program_id(0), pl.program_id(2)

    @pl.when(tb == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    live = jnp.clip(len_ref[b] - tb * t_blk, 0, t_blk)
    groups = pl.cdiv(live, GROUP)

    @pl.when(groups < t_blk // GROUP)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)  # (a pad slot's y is finite: what follows multiplies it)

    @pl.when(groups > 0)
    def _():
        a = -jnp.exp(a_ref[...])
        h_ref[...] = _walk(h_ref[...], a, dt_ref, x_ref, bt_ref, ct_ref, y_ref, 0, groups, 0)


def _time_on_lanes(a: jax.Array) -> jax.Array:
    """[B, T, N] (T a multiple of 128) -> [B, T / 128, N, 128]."""
    B, T, N = a.shape
    return jnp.transpose(a.reshape(B, T // LANES, LANES, N), (0, 1, 3, 2))


def _pad_time(a: jax.Array, T: int) -> jax.Array:
    return a if a.shape[1] == T else jnp.pad(a, ((0, 0), (0, T - a.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_prefill(
    dt: jax.Array,  # [B, T, I] float32, 0 at and past a row's length
    x: jax.Array,  # [B, T, I] float32
    b: jax.Array,  # [B, T, N] float32
    c: jax.Array,  # [B, T, N] float32
    a_log: jax.Array,  # [N, I] float32
    lens: jax.Array,  # [B] int32: each row's live tokens
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (y [B, T, I] float32, zeros at and past a row's length rounded up to
    a group; the state AT each row's length [B, N, I] float32, from zeros)."""
    B, T, I = dt.shape
    N = a_log.shape[0]
    m_blk = _blocking(I)
    t_blk = min(-(-T // GROUP) * GROUP, TIME_BLOCK)
    t_pad = -(-T // t_blk) * t_blk
    lanes_pad = -(-t_pad // LANES) * LANES
    per = max(t_blk // LANES, 1)  # lane blocks of B and C a time block reads
    dt, x = _pad_time(dt, t_pad), _pad_time(x, t_pad)
    bt, ct = (_time_on_lanes(_pad_time(a, lanes_pad)) for a in (b, c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, I // m_blk, t_pad // t_blk),
        in_specs=[
            pl.BlockSpec((N, m_blk), lambda b, j, t, _: (0, j)),
            pl.BlockSpec((None, t_blk, m_blk), lambda b, j, t, _: (b, t, j)),
            pl.BlockSpec((None, t_blk, m_blk), lambda b, j, t, _: (b, t, j)),
            pl.BlockSpec((None, per, N, LANES), lambda b, j, t, _: (b, t, 0, 0)),
            pl.BlockSpec((None, per, N, LANES), lambda b, j, t, _: (b, t, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, t_blk, m_blk), lambda b, j, t, _: (b, t, j)),
            pl.BlockSpec((None, N, m_blk), lambda b, j, t, _: (b, 0, j)),
        ],
    )
    y, h = pl.pallas_call(
        functools.partial(_prefill_kernel, t_blk=t_blk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, t_pad, I), jnp.float32),
            jax.ShapeDtypeStruct((B, N, I), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="selective_scan_prefill",
    )(lens.astype(jnp.int32), a_log.astype(jnp.float32), dt, x, bt, ct)
    return y[:, :T], h


def _window_kernel(
    layer_ref, slot_ref, row_ref, blk_ref, live_ref, any_ref,  # scalar prefetch (SMEM)
    h_ref,  # [N, M_BLK] the row's state block
    a_ref,  # [N, M_BLK]
    dt_ref, x_ref,  # [W + S, M_BLK]: the pending window's tokens, then this window's
    bt_ref, ct_ref,  # [1, N, 128]: time on the lanes
    h_out,  # [N, M_BLK] (the pool, aliased)
    y_ref,  # [S, M_BLK]
    *, pending: int, window: int,
):
    del layer_ref, slot_ref, row_ref, blk_ref  # the index maps'
    b = pl.program_id(0)

    @pl.when(live_ref[b] > 0)
    def _():
        a = -jnp.exp(a_ref[...])
        h = _walk(h_ref[...], a, dt_ref, x_ref, bt_ref, None, None, 0, pending // GROUP, 0)
        h_out[...] = h  # what the row kept is committed; its window is not
        _walk(
            h, a, dt_ref, x_ref, bt_ref, ct_ref, y_ref,
            pending // GROUP, (pending + window) // GROUP, pending,
        )

    @pl.when(live_ref[b] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(any_ref[0] == 0)
    def _():
        h_out[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_window(
    pool: jax.Array,  # [layers, n_slots, N, I] float32: the J layers' states
    layer: jax.Array,  # which of them this call moves (traced: a scan's carried number)
    slots: jax.Array,  # [B] int32, in range
    q_lens: jax.Array,  # [B] int32: 0 = an idle row
    p_dt: jax.Array,  # [B, W, I] float32: the pending window's, 0 on a token the row did not keep
    p_x: jax.Array,  # [B, W, I] float32
    p_b: jax.Array,  # [B, W, N] float32
    dt: jax.Array,  # [B, S, I] float32: this window's, 0 on a dead slot
    x: jax.Array,  # [B, S, I] float32
    b: jax.Array,  # [B, S, N] float32
    c: jax.Array,  # [B, S, N] float32
    a_log: jax.Array,  # [N, I] float32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (the pool with every live row's slot of ``layer`` moved over what it
    kept, ``y`` [B, S, I] float32 of this window's slots walked from there,
    zeros on an idle row). See the module docstring."""
    _, _, N, I = pool.shape
    B, W, _ = p_dt.shape
    S = dt.shape[1]
    m_blk = _blocking(I)
    n_j = I // m_blk
    w_pad, s_pad = (-(-n // GROUP) * GROUP for n in (W, S))
    if w_pad + s_pad > LANES:
        raise ValueError(f"a pending window of {W} and a window of {S} tokens pass one lane block")
    both = lambda p, w: jnp.concatenate([_pad_time(p, w_pad), _pad_time(w, s_pad)], axis=1)
    bt = _time_on_lanes(_pad_time(both(p_b, b), LANES))
    ct = _time_on_lanes(_pad_time(both(jnp.zeros_like(p_b), c), LANES))
    live = (q_lens > 0).astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    # An idle row's steps stand on the live row before it (on its last
    # block), or, ahead of the first live row, on that row's first block:
    # their block indices repeat a neighbour's, so nothing moves for them.
    before = lax.cummax(jnp.where(live > 0, rows, -1))
    row = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    blk = jnp.where(before >= 0, n_j - 1, 0).astype(jnp.int32)

    def block(b, j, live_ref, blk_ref):
        return jnp.where(live_ref[b] > 0, j, blk_ref[b])

    def state_map(b, j, layer_ref, slot_ref, row_ref, blk_ref, live_ref, _):
        return layer_ref[0], slot_ref[b], 0, block(b, j, live_ref, blk_ref)

    def lanes_map(b, j, layer_ref, slot_ref, row_ref, blk_ref, live_ref, _):  # dt, x
        return row_ref[b], 0, block(b, j, live_ref, blk_ref)

    def row_map(b, j, layer_ref, slot_ref, row_ref, *_):  # bt, ct
        return row_ref[b], 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, n_j),
        in_specs=[
            pl.BlockSpec((None, None, N, m_blk), state_map),
            pl.BlockSpec((N, m_blk), lambda b, j, *s: (0, block(b, j, s[4], s[3]))),
            pl.BlockSpec((None, w_pad + s_pad, m_blk), lanes_map),
            pl.BlockSpec((None, w_pad + s_pad, m_blk), lanes_map),
            pl.BlockSpec((None, 1, N, LANES), row_map),
            pl.BlockSpec((None, 1, N, LANES), row_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, N, m_blk), state_map),
            pl.BlockSpec((None, s_pad, m_blk), lambda b, j, *_: (b, 0, j)),
        ],
    )
    pool, y = pl.pallas_call(
        functools.partial(_window_kernel, pending=w_pad, window=s_pad),
        grid_spec=grid_spec,
        out_shape=[
            # held to the HBM (the aliased operand with it): left to choose, XLA
            # stages the WHOLE pool through fast memory around a scanned run of
            # layers, 68 MB each way (PERF.md section 6, PR 58)
            pltpu.HBM(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((B, s_pad, I), jnp.float32),
        ],
        # operand 6 (after the six prefetched scalars) is the pool
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan_window",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32)[row], row, blk, live,
        jnp.max(live).reshape(1),
        pool, a_log.astype(jnp.float32), both(p_dt, dt), both(p_x, x), bt, ct,
    )
    return pool, y[:, :S]
