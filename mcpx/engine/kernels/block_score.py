"""The pooled-key scores of a block-selecting attention layer's decode window,
as ONE kernel call (Pallas TPU): what ``models/gemma/sparse.pooled_scores``
computes in jnp, a (row, KV head) a program.

The caller (``engine/paged_decode._block_attend``) gathers the row's page sums
through its table and forms the pooled keys ``kc`` [B, K, J, hd] float32 (two
neighbouring pages' sums over ``2 x page``); the program multiplies them by the
window's queries of the KV group, ``S x G`` rows of one [S G, hd] x [hd, J]
product at ``highest`` precision (the keys are float32 sums), masks what a
slot's position does not see whole, takes each head's softmax over the pooled
keys and adds the group's heads: ``out[b, k, s, j]``, -inf where slot ``s``
does not see pooled key ``j``. The 5-wide max over a block's pooled keys, the
forced blocks and the top-k stay with the caller: [B, S, K, J] float32 is a
few hundred KB, and a sort is no kernel's work.

An idle row (``q_lens`` 0) multiplies nothing and writes -inf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = lax.Precision.HIGHEST


def _kernel(pos_ref, len_ref, q_ref, kc_ref, out_ref, *, S: int, G: int, stride: int):
    b = pl.program_id(0)
    J = kc_ref.shape[0]

    @pl.when(len_ref[b] > 0)
    def _():
        f32 = jnp.float32
        hd = q_ref.shape[-1]
        logits = lax.dot_general(
            q_ref[...].astype(f32), kc_ref[...], (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=f32,
        ) * (hd**-0.5)  # [S G, J]
        slot = lax.broadcasted_iota(jnp.int32, (S * G, J), 0) // G
        last = lax.broadcasted_iota(jnp.int32, (S * G, J), 1) * stride + (2 * stride - 1)
        seen = last <= pos_ref[b] + slot
        logits = jnp.where(seen, logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(logits - jnp.where(top > -jnp.inf, top, 0.0)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        # the group's heads added: a [S, S G] selector times p
        pick = (lax.broadcasted_iota(jnp.int32, (S, S * G), 1) // G
                == lax.broadcasted_iota(jnp.int32, (S, S * G), 0)).astype(f32)
        pooled = jnp.dot(pick, p, precision=HIGHEST, preferred_element_type=f32)  # [S, J]
        seen_s = (lax.broadcasted_iota(jnp.int32, (S, J), 1) * stride + (2 * stride - 1)
                  <= pos_ref[b] + lax.broadcasted_iota(jnp.int32, (S, J), 0))
        out_ref[...] = jnp.where(seen_s, pooled, -jnp.inf)

    @pl.when(len_ref[b] == 0)
    def _():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def block_score(
    q: jax.Array,  # [B, S, K, G, hd] the window's queries
    kc: jax.Array,  # [B, K, J, hd] float32: the rows' pooled keys
    positions: jax.Array,  # [B] the position of slot 0
    q_lens: jax.Array,  # [B] live slots (0: an idle row)
    *,
    stride: int,  # tokens between pooled keys (the page size); a pooled key spans two
    interpret: bool = False,
) -> jax.Array:
    """-> the pooled scores [B, S, K, J] float32. See the module docstring."""
    B, S, K, G, hd = q.shape
    J = kc.shape[2]
    rows = q.transpose(0, 2, 1, 3, 4).reshape(B, K, S * G, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((None, None, S * G, hd), lambda b, k, *_: (b, k, 0, 0)),
            pl.BlockSpec((None, None, J, hd), lambda b, k, *_: (b, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, S, J), lambda b, k, *_: (b, k, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, S=S, G=G, stride=stride),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, S, J), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="block_score",
    )(positions.astype(jnp.int32), q_lens.astype(jnp.int32), rows, kc)
    return out.transpose(0, 2, 1, 3)
