"""A recurrent (state-space) layer's decode window against the state pool, as
ONE kernel call (Pallas TPU): a live row's state read once, moved by what the
row KEPT of its previous window, read out for this window's slots, written
once.

``models/gemma/ssm.py::mamba_window`` hands it the pool of EVERY such layer,
``[layers, n_slots, N, M]`` float32, and which layer this is (``M = heads x
head_dim`` on the lanes, the state's ``N`` on the sublanes: a head's decay is
then a lane vector and nothing in here is transposed), the rows' slots, and
the small tensors of the two windows:

  total [B, M]      exp(sum of the kept tokens' dt A), a head's value
                    repeated over its lanes: the old state's factor
  xs    [B, W, M]   exp(L_kept - L_s) dt_s x_s of the pending window's
                    tokens, 0 for a token the row did not keep
  bt    [B, G, N, W] the pending window's B, a group's [N, W]
  c     [B, G, S, N] this window's C, a group's [S, N]

and a grid step (row ``b``, lane block ``j`` of group ``g``) computes

  h'  = total * h + bt_g @ xs          [N, M_BLK]   the commit, float32
  hc  = c_g @ h'                       [S, M_BLK]   the window's read-out

both products at ``highest`` precision: the state is float32 and stays so
through them. ``h'`` goes back to the row's slot through the pool's alias;
``hc`` is what ``ssm.chunk_outputs`` decays to each slot's position and adds
the window's own tokens to. The caller keeps the window PENDING: what of it
the row keeps is known after the forward's verify, and the next call's
``total`` / ``xs`` carry exactly that.

An idle row (``q_lens`` 0) costs nothing and changes nothing: its grid steps
keep the block indices of the live row before it (the first live row's,
ahead of it), so nothing is fetched or written back for them, and their
bodies write only zeros to ``hc``. With no live row at all the one block the
grid holds is passed through unchanged.

The layers share ONE array so that the compiler leaves it where it is: a
layer's pool of its own that fits VMEM (33.5 MB at 8 slots of the sparse
hybrid cell) was staged through it whole around every call, 67 MB a layer a
forward whatever the live rows (PERF.md, PR 48); the layers' together do not
fit, and the call's alias keeps the array in place.

``_blocking`` cuts a group's lanes from the shapes alone: the widest whole
number of lane widths that divides them and keeps the state's four buffers
(in and out, double-buffered) under ``VMEM_BUDGET``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET = 8 * 2**20
LANES = 128
HIGHEST = lax.Precision.HIGHEST


def _blocking(group_lanes: int, N: int) -> int:
    """``M_BLK``: the lanes of one group's heads a grid step takes."""
    if group_lanes % LANES:
        return group_lanes  # narrower than a lane width (the CPU tests' sizes)
    best = LANES
    for n in range(1, group_lanes // LANES + 1):
        m_blk = n * LANES
        if group_lanes % m_blk == 0 and 4 * N * m_blk * 4 <= VMEM_BUDGET:
            best = m_blk
    return best


def _kernel(
    slot_ref, row_ref, blk_ref, live_ref, any_ref,  # scalar prefetch (SMEM): 4 x [B], [1]
    h_ref,  # [N, M_BLK] the row's state block
    total_ref,  # [1, M_BLK]
    xs_ref,  # [W, M_BLK]
    bt_ref,  # [N, W]
    c_ref,  # [S, N]
    h_out,  # [N, M_BLK] (the pool, aliased)
    hc_out,  # [S, M_BLK]
):
    del slot_ref, row_ref, blk_ref  # the index maps'
    b = pl.program_id(0)

    @pl.when(live_ref[b] > 0)
    def _():
        f32 = jnp.float32
        h = h_ref[...] * total_ref[...] + jnp.dot(
            bt_ref[...], xs_ref[...], precision=HIGHEST, preferred_element_type=f32
        )
        h_out[...] = h
        hc_out[...] = jnp.dot(c_ref[...], h, precision=HIGHEST, preferred_element_type=f32)

    @pl.when(live_ref[b] == 0)
    def _():
        hc_out[...] = jnp.zeros_like(hc_out)

    @pl.when(any_ref[0] == 0)
    def _():
        h_out[...] = h_ref[...]


def ssm_window(
    pool: jax.Array,  # [layers, n_slots, N, M] float32: the recurrent layers' states
    layer: int,  # which of them this call moves
    slots: jax.Array,  # [B] int32
    q_lens: jax.Array,  # [B] int32: 0 = an idle row
    total: jax.Array,  # [B, M] float32
    xs: jax.Array,  # [B, W, M] float32
    bt: jax.Array,  # [B, G, N, W] float32
    c: jax.Array,  # [B, G, S, N] float32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """-> (the pool with every live row's slot of ``layer`` moved, ``hc``
    [B, S, M] float32, zeros on an idle row). See the module docstring."""
    _, _, N, M = pool.shape
    B, W, _ = xs.shape
    G, S = c.shape[1], c.shape[2]
    m_blk = _blocking(M // G, N)
    per_group = (M // G) // m_blk
    n_j = G * per_group
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

    def state_map(b, j, slot_ref, row_ref, blk_ref, live_ref, _):
        return layer, slot_ref[b], 0, block(b, j, live_ref, blk_ref)

    def lanes_map(b, j, slot_ref, row_ref, blk_ref, live_ref, _):  # total, xs
        return row_ref[b], 0, block(b, j, live_ref, blk_ref)

    def group_map(b, j, slot_ref, row_ref, blk_ref, live_ref, _):  # bt, c: a group's
        return row_ref[b], block(b, j, live_ref, blk_ref) // per_group, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, n_j),
        in_specs=[
            pl.BlockSpec((None, None, N, m_blk), state_map),
            pl.BlockSpec((None, 1, m_blk), lanes_map),
            pl.BlockSpec((None, W, m_blk), lanes_map),
            pl.BlockSpec((None, None, N, W), group_map),
            pl.BlockSpec((None, None, S, N), group_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, N, m_blk), state_map),
            pl.BlockSpec((None, S, m_blk), lambda b, j, *_: (b, 0, j)),
        ],
    )
    pool, hc = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((B, S, M), jnp.float32),
        ],
        # operand 5 (after the five prefetched scalars) is the pool
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_window",
    )(
        slots.astype(jnp.int32)[row], row, blk, live, jnp.max(live).reshape(1),
        pool, total[:, None, :], xs, bt, c,
    )
    return pool, hc
