"""A sparse layer's routed experts for a window at or under the ridge, as ONE
kernel call (Pallas TPU): the touched experts' matrices streamed back to
back, each read once.

``models/gemma/moe.py::moe_forward`` routes, sorts the held experts touched
first (``order``, ``n_touched``) and hands both here as prefetched scalars
with the sparse ``layer``'s number. The grid is ``(steps, cdiv(F, F_BLK))``:
step ``i`` is expert ``order[i]``, and the block index maps pick its
``w_gate`` / ``w_up`` ``[D, F_BLK]`` and ``w_down`` ``[F_BLK, D]`` blocks
straight out of the stacks ``[L, E_held, D, F]`` / ``[L, E_held, F, D]``, so
the pipeline fetches step ``i + 1``'s blocks while step ``i`` multiplies and
no slice of a stack is ever materialised (the jnp loop's three dots a step
each slice their own matrix by a data-dependent index and each pay their own
start-up: 21.9 us a step for 12.4 MB on a v5e, 69% of the HBM's rate,
PERF.md PR 43).

A step multiplies every slot of the window, as the jnp loop does at these
widths: gate and up rounded to the rows' type, ``act(gate) * up`` computed
in float32 and rounded to the rows' type once, the down product accumulated
in float32 (over the ``F`` blocks in a float32 scratch where there are
several), times the expert's column of ``combine`` (a slot that did not
choose it: 0), added into the float32 ``[T, D]`` output block, which stays
in VMEM across the whole grid. The products, the precisions and the order of
the sum over experts are the loop's (``moe._expert_rows``). What may differ:
the float32 summation order over ``F`` blocks; a matmul's own accumulation
order, which is the compiler's in either form; and, with bfloat16 rows, where
inside ``act(gate) * up`` a rounding falls (XLA fuses that chain into a dot
as it sees fit; on the chip the two forms agree to 0.3% of the largest
output, PERF.md PR 43).

Steps: on a TPU the first grid dimension is DYNAMIC, ``max(n_touched, 1)``,
so an expert nobody chose costs nothing. The interpreter has no dynamic
grid: there the grid is ``E_held`` and a step past ``n_touched`` keeps the
last live step's block indices (an unchanged block is not fetched again)
and skips its compute. The body and the index maps are the same in both.

``_blocking`` cuts ``F`` from the shapes alone under ``VMEM_LIMIT``, which
the call asks Mosaic for explicitly: a whole expert a step where two of
them fit (mellum2 2,304 x 896 and trinity-mini 2,048 x 1,024: 12.4 / 12.6 MB
a step), a.x-k1's 7,168 x 2,048 in four blocks of 512 (22 MB a step).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The scoped VMEM the call asks for (Mosaic's default is 16 MiB; a v5e core
# has 128 MiB), and the part of it the blocking may count: the weight blocks
# in flight, the rows, the output block and the step's own tiles. The rest
# is left to what the compiler materialises beside them.
VMEM_LIMIT = 96 * 2**20
VMEM_BUDGET = 64 * 2**20
LANES = 128


def _vmem_bytes(f_blk: int, *, T: int, D: int, E: int, F: int, w_itemsize: int, x_itemsize: int) -> int:
    """Bytes of VMEM the call holds at this ``F`` block: the three weight
    blocks, double-buffered by the pipeline; the rows, ``combine`` and the
    float32 output block (two buffers each); a step's gate, up and product
    tiles in float32 and its down product; the float32 scratch that sums
    the down product where ``F`` takes several blocks."""
    weights = 2 * 3 * D * f_blk * w_itemsize  # (an expert with no gate has two: counted as three)
    rows = 2 * T * D * x_itemsize + 2 * T * max(E, LANES) * 4
    out = 2 * T * D * 4
    step = 3 * T * f_blk * 4 + T * D * 4
    down = T * D * 4 if f_blk < F else 0
    return weights + rows + out + step + down


def _blocking(T: int, D: int, F: int, E: int, w_itemsize: int, x_itemsize: int) -> int:
    """``F_BLK``: the columns of ``w_gate`` / ``w_up`` (rows of ``w_down``) a
    grid step takes, from the shapes alone: ``F`` over the fewest blocks
    that fit ``VMEM_BUDGET``, each a whole number of lane widths. One block
    is the whole expert, three contiguous reads; a cut ``F`` reads
    ``F_BLK``-wide column blocks, and where ``F_BLK`` does not divide ``F``
    the last one is masked past ``F``."""
    size = functools.partial(
        _vmem_bytes, T=T, D=D, E=E, F=F, w_itemsize=w_itemsize, x_itemsize=x_itemsize
    )
    for n in range(1, pl.cdiv(F, LANES) + 1):
        f_blk = F if n == 1 else pl.cdiv(pl.cdiv(F, n), LANES) * LANES
        if size(f_blk) <= VMEM_BUDGET:
            break
    return f_blk  # one lane width (or a narrower F) where nothing fits


def _kernel(
    order_ref, n_ref, layer_ref,  # scalar prefetch (SMEM): [E], [1], [1]
    x_ref,  # [T, D] the window's rows
    combine_ref,  # [T, E] float32: a slot's weight for an expert, 0 unchosen
    *refs,  # (w_gate where gated,) w_up [D, F_BLK] of expert order[i], w_down [F_BLK, D],
    # out [T, D] float32, resident across the grid, ([T, D] float32 scratch where F takes several blocks)
    act: Callable, F: int, f_blk: int, gated: bool,
):
    del layer_ref  # the index maps'
    w_gate_ref = refs[0] if gated else None
    w_up_ref, w_down_ref, out_ref, *scratch = refs[gated:]
    i, j = pl.program_id(0), pl.program_id(1)
    n_f = pl.cdiv(F, f_blk)

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        x, w_up, w_down = x_ref[...], w_up_ref[...], w_down_ref[...]
        dtype = jnp.promote_types(x.dtype, w_up.dtype)
        f32 = jnp.float32
        # Gate and up rounded to the rows' type, as moe._expert_rows' dots
        # round them; the elementwise chain between those and its own rounding
        # in float32, which is how XLA computes a bfloat16 chain on a core
        # with no bfloat16 vector unit (and Mosaic lowers no bfloat16 logistic).
        up = jnp.dot(x, w_up, preferred_element_type=f32).astype(dtype).astype(f32)
        if gated:
            gate = jnp.dot(x, w_gate_ref[...], preferred_element_type=f32).astype(dtype).astype(f32)
            a = (act(gate) * up).astype(dtype)  # [T, F_BLK]
        else:
            a = act(up).astype(dtype)  # an expert of two matrices: act(x U) V
        if F % f_blk:
            # The last block overhangs F: what was read past it is no weight.
            left = F - j * f_blk
            a = jnp.where(lax.broadcasted_iota(jnp.int32, a.shape, 1) < left, a, 0)
            w_down = jnp.where(lax.broadcasted_iota(jnp.int32, w_down.shape, 0) < left, w_down, 0)
        part = jnp.dot(a, w_down, preferred_element_type=f32)  # [T, D]
        # This expert's column of combine: one nonzero a row at most, so the
        # masked sum is that value exactly.
        combine = combine_ref[...]
        lanes = lax.broadcasted_iota(jnp.int32, combine.shape, 1)
        col = jnp.sum(jnp.where(lanes == order_ref[i], combine, 0.0), axis=1, keepdims=True)
        if n_f == 1:
            out_ref[...] += part * col
        else:
            (y_ref,) = scratch

            @pl.when(j == 0)
            def _():
                y_ref[...] = part

            @pl.when(j > 0)
            def _():
                y_ref[...] += part

            @pl.when(j == n_f - 1)
            def _():
                out_ref[...] += y_ref[...] * col


def routed_experts(
    x: jax.Array,  # [T, D]
    combine: jax.Array,  # [T, E] float32
    w_gate: "jax.Array | None",  # [L, E, D, F] (stays in HBM; a step's blocks are fetched); None: no gate
    w_up: jax.Array,  # [L, E, D, F]
    w_down: jax.Array,  # [L, E, F, D]
    order: jax.Array,  # [E] int32: the held experts, touched ones first
    n_touched: jax.Array,  # () int32
    layer: jax.Array,  # () int32: the sparse layer's row of the stacks
    *,
    act: Callable,
    interpret: bool = False,
) -> jax.Array:
    """``sum_i combine[:, e_i] * down_{e_i}(act(x gate_{e_i}) * (x up_{e_i}))``
    over ``e_i = order[i]``, ``i < n_touched``, in that order: float32
    [T, D]; without ``w_gate``, ``down(act(x up))``. See the module docstring."""
    T, D = x.shape
    _, E, _, F = w_up.shape
    gated = w_gate is not None
    f_blk = _blocking(T, D, F, E, w_up.dtype.itemsize, x.dtype.itemsize)
    n_f = pl.cdiv(F, f_blk)
    n_touched = jnp.asarray(n_touched, jnp.int32)

    def step(i, j, order_ref, n_ref, layer_ref):
        """(layer, expert, F block) of grid step (i, j); a step past
        ``n_touched`` keeps the last live step's last block."""
        n = n_ref[0]
        e = order_ref[jnp.minimum(i, jnp.maximum(n - 1, 0))]
        return layer_ref[0], e, jnp.where(i < n, j, n_f - 1)

    def w_in(i, j, *scalars):
        l, e, jf = step(i, j, *scalars)
        return l, e, 0, jf

    def w_out(i, j, *scalars):
        l, e, jf = step(i, j, *scalars)
        return l, e, jf, 0

    whole = lambda i, j, *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # A dynamic trip count on the chip; the interpreter has none.
        grid=(E if interpret else jnp.maximum(n_touched, 1), n_f),
        in_specs=[
            pl.BlockSpec((T, D), whole),
            pl.BlockSpec((T, E), whole),
            *[pl.BlockSpec((None, None, D, f_blk), w_in)] * (1 + gated),
            pl.BlockSpec((None, None, f_blk, D), w_out),
        ],
        out_specs=pl.BlockSpec((T, D), whole),
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)] if n_f > 1 else [],
    )
    return pl.pallas_call(
        functools.partial(_kernel, act=act, F=F, f_blk=f_blk, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        name="routed_experts",
    )(
        order.astype(jnp.int32),
        n_touched.reshape(1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        x, combine, *((w_gate,) if gated else ()), w_up, w_down,
    )
