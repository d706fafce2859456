"""A block module used only by tests: mcpx's decoder block with both residual
branches halved, ``x + attn(x) / 2`` and ``x + mlp(x) / 2``. It is the proof
that the seam carries a block: the change lives in this one file, on both
sides of the comparison, and ``child.py`` and ``reference.py`` take it by name.

The program has no knob for a residual scale, so this block's program step is
the shared step run on weights with the scale folded into ``wo`` and
``w_down`` (exact in bfloat16: a power of two), through the optional
``step_functions`` part. The reference folds it itself, in its own float32
copy; leave that out and the comparison must fail (``test_block_seam.py``).
"""

import spec

_gemma = spec.load_block("gemma")
kernel_paths = _gemma.kernel_paths
model_config = _gemma.model_config
rehearsal_config = _gemma.rehearsal_config


def _halved(params):
    layers = dict(params["layers"])
    for leaf in ("wo", "w_down"):
        layers[leaf] = layers[leaf] * 0.5
    return {**params, "layers": layers}


def reference_logits(params, dims, tokens):
    import jax
    import jax.numpy as jnp

    as_f32 = jax.tree.map(lambda w: w.astype(jnp.float32), params)
    return _gemma.reference_logits(_halved(as_f32), dims, tokens)


def step_functions(model_cfg, dims, mesh, **shape):
    import reference

    prefill, decode = reference.step_functions(model_cfg, dims, mesh, **shape)
    return (lambda p, *rest: prefill(_halved(p), *rest)), (lambda p, *rest: decode(_halved(p), *rest))
