"""Checkpoint save/load (Orbax) with sharding-aware restore.

The reference persists nothing anywhere (SURVEY.md §5 checkpoint/resume:
"there are no writes at all"). Here model weights are Orbax checkpoints that
restore *directly onto the mesh* — each host/device materialises only its
shard, which is what makes 2B/7B loads fit HBM without a host-RAM spike.

Orbax is imported where a checkpoint is written or restored (``_orbax``),
not at module scope: a start on drawn weights or an ``.npz`` never reads it,
and it brings ~780 modules (google.cloud.logging, grpc, tensorstore) into a
process that imports this module for ``load_or_init`` alone. A start from an
Orbax checkpoint pays that import once, inside ``startup.weights``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding

from mcpx.core.errors import EngineError
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import Params, init_params
from mcpx.parallel.mesh import param_pspecs


def _check_shapes(params: Params, cfg: GemmaConfig, path: str) -> None:
    """Loaded tree must match the config's shapes exactly — a silent
    mismatch (e.g. a checkpoint trained on a different vocab) would either
    crash deep inside jit or, worse, broadcast."""
    expected = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    flat_e = jax.tree.leaves_with_path(expected)
    flat_p = {
        jax.tree_util.keystr(k): v for k, v in jax.tree.leaves_with_path(params)
    }
    problems = []
    expected_keys = set()
    for key, exp in flat_e:
        ks = jax.tree_util.keystr(key)
        expected_keys.add(ks)
        got = flat_p.get(ks)
        if got is None:
            problems.append(f"missing {ks}")
        elif tuple(got.shape) != tuple(exp.shape):
            problems.append(f"{ks}: shape {tuple(got.shape)} != {tuple(exp.shape)}")
    for ks in sorted(set(flat_p) - expected_keys):
        problems.append(f"unexpected {ks}")
    if problems:
        raise EngineError(f"checkpoint {path} does not fit model config: {problems[:4]}")


def _orbax(path: str):
    """``orbax.checkpoint``, imported at first use; ``path`` is the
    checkpoint that needed it, for the error of an install without it."""
    try:
        import orbax.checkpoint as ocp
    except ImportError as e:
        raise EngineError(
            f"checkpoint {path} needs the package orbax-checkpoint, which cannot be imported: {e}"
        ) from e
    return ocp


def save_checkpoint(path: str, params: Params) -> None:
    path = os.path.abspath(path)
    ocp = _orbax(path)
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path, params)


def load_checkpoint(
    path: str, cfg: GemmaConfig, mesh: Optional[Mesh] = None
) -> Params:
    """Restore params; when ``mesh`` is given, arrays are restored already
    sharded per ``param_pspecs`` (no full-replica host copy)."""
    path = os.path.abspath(path)
    if not os.path.exists(path):
        raise EngineError(f"checkpoint not found: {path}")
    if path.endswith(".npz"):
        # Single-file trained-planner checkpoint (models/train.py save_npz):
        # small enough to land fully on host, then shard onto the mesh.
        from mcpx.models.train import load_npz

        params = load_npz(path)
        _check_shapes(params, cfg, path)
        if mesh is not None:
            from mcpx.parallel.mesh import shard_pytree

            params = shard_pytree(params, param_pspecs(cfg, mesh), mesh)
        return params
    ocp = _orbax(path)
    with ocp.PyTreeCheckpointer() as ckptr:
        if mesh is None:
            return ckptr.restore(path)
        specs = param_pspecs(cfg, mesh)
        abstract = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        targets = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
            ),
            abstract,
            specs,
        )
        restore_args = ocp.checkpoint_utils.construct_restore_args(targets)
        return ckptr.restore(
            path, restore_args=restore_args
        )


def load_or_init(
    cfg: GemmaConfig,
    checkpoint_path: str = "",
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    quantize: str = "none",
) -> tuple[Params, str]:
    """Load a checkpoint if configured, else random-init (optionally onto the
    mesh). Returns (params, source) where source is "checkpoint" | "random".

    The random path draws every leaf already sharded over ``mesh`` (bits
    independent of the mesh). ``quantize="int8"`` (models/gemma/quant.py):
    the random path quantizes each leaf AT CREATION (full-precision tree
    never exists at once — the property that lets the 7B geometry
    initialise int8 on one 16 GB chip).
    The checkpoint path quantizes after restore, which transiently needs
    the full-precision footprint on the restoring topology; a single chip
    that can't hold it needs either a sharded restore across a mesh or an
    offline pre-quantized checkpoint (documented limitation)."""
    if checkpoint_path:
        params = load_checkpoint(checkpoint_path, cfg, mesh)
        if quantize == "int8":
            from mcpx.models.gemma.quant import quantize_params

            params = quantize_params(params)
            if mesh is not None:
                # Pin the quantized tree (int8 weights + scale leaves) to
                # quant_pspecs like the random-init branch does — leaving
                # the scale shardings to XLA inference lets them diverge
                # from the layout the serving jits were specced against.
                from mcpx.models.gemma.quant import quant_pspecs
                from mcpx.parallel.mesh import shard_pytree

                params = shard_pytree(params, quant_pspecs(cfg, mesh), mesh)
        return params, "checkpoint"
    leaf_transform = None
    if quantize == "int8":
        from mcpx.models.gemma.quant import leaf_quantizer

        leaf_transform = leaf_quantizer
    # Every leaf is drawn already sharded per param_pspecs (init_params): no
    # device holds the whole tree, nor a whole leaf that the specs split.
    params = init_params(
        cfg, jax.random.PRNGKey(seed), leaf_transform=leaf_transform, mesh=mesh
    )
    if mesh is not None and quantize == "int8":
        # The quantizer ran on the sharded leaf, so the int8 weights and
        # their scales already sit where GSPMD put them; pinning them to
        # quant_pspecs moves at most the small scale leaves.
        from mcpx.models.gemma.quant import quant_pspecs
        from mcpx.parallel.mesh import shard_pytree

        params = shard_pytree(params, quant_pspecs(cfg, mesh), mesh)
    return params, "random"


def bytes_per_device(params: Params) -> dict[str, int]:
    """Bytes of ``params`` that each device holds, from the placed tree's
    addressable shards (a replicated leaf counts once on every device)."""
    held: dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            dev = str(shard.device)
            held[dev] = held.get(dev, 0) + shard.data.nbytes
    return held
