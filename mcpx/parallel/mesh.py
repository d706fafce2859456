"""Device mesh + sharding layout for the inference engine.

The distributed backend is XLA collectives over ICI, driven entirely by
sharding annotations on a named ``Mesh(("data", "model"))`` — no hand-written
transport (SURVEY.md §2.3: the reference has no distributed backend at all;
ours is GSPMD). Axis layout for a v5e-8:

  - ``model`` (TP): attention heads and the MLP hidden dim are sharded;
    activations all-reduce (psum) after ``wo`` and ``w_down`` — XLA inserts
    these from the annotations. The embedding is sharded on vocab, so logits
    materialise vocab-sharded and the sampler's argmax/top-k runs sharded.
  - ``data`` (DP): the request batch splits across replicas; KV caches are
    sharded on batch over ``data`` and on KV heads over ``model`` when the
    head count divides (MQA keeps KV replicated on ``model`` — the standard
    MQA-TP layout).

Divisibility-aware: any weight axis that doesn't divide the mesh axis is
replicated rather than erroring, so the same code serves 1-chip CI, the
8-device virtual CPU mesh, and a v5e-8.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mcpx.core.errors import ConfigError
from mcpx.models.gemma.config import GemmaConfig

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"  # sequence/context parallelism (ring attention)
DCN_DATA_AXIS = "dcn_data"  # cross-slice data parallelism (multi-host DCN)


def make_mesh(
    data: int = 1,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Named device mesh. The ``seq`` axis (between data and model, so ring
    ppermute hops ride neighbouring ICI links) is only materialised when >1,
    keeping the common 2-axis layout for the serving engine."""
    devices = list(devices if devices is not None else jax.devices())
    if data * seq * model > len(devices):
        raise ConfigError(
            f"mesh {data}x{seq}x{model} needs {data * seq * model} devices, "
            f"have {len(devices)}"
        )
    if seq > 1:
        grid = np.asarray(devices[: data * seq * model]).reshape(data, seq, model)
        return Mesh(grid, (DATA_AXIS, SEQ_AXIS, MODEL_AXIS))
    grid = np.asarray(devices[: data * model]).reshape(data, model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def make_hybrid_mesh(
    dcn_data: int,
    data: int = 1,
    model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Multi-slice mesh ``(dcn_data, data, model)`` — the standard hybrid
    recipe (docs/DISTRIBUTION.md): pure data parallelism across slices over
    DCN, TP (and ICI data parallelism) within each slice. The OUTER axis
    must correspond to slice boundaries, which holds when ``devices`` is
    process-ordered — ``jax.devices()`` already is, and a real multi-host
    deployment can pass ``mesh_utils.create_hybrid_device_mesh``'s device
    array flattened. Gradient all-reduces across ``dcn_data`` are the only
    cross-slice collectives XLA inserts for this layout: per-slice grads
    reduce over ICI first (``data``/``model``), then one DCN all-reduce —
    exactly the hierarchy the hardware wants, and GSPMD derives it from the
    sharding annotations alone (no hand-written transport; the reference's
    analogue would be NCCL/MPI process groups)."""
    devices = list(devices if devices is not None else jax.devices())
    need = dcn_data * data * model
    if need > len(devices):
        raise ConfigError(
            f"hybrid mesh {dcn_data}x{data}x{model} needs {need} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:need]).reshape(dcn_data, data, model)
    return Mesh(grid, (DCN_DATA_AXIS, DATA_AXIS, MODEL_AXIS))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Every data-parallel axis present in ``mesh`` (outer-first), for
    sharding a batch dimension: ``("dcn_data", "data")`` on a hybrid mesh,
    ``("data",)`` on the serving mesh."""
    return tuple(
        a for a in (DCN_DATA_AXIS, DATA_AXIS) if mesh.shape.get(a, 1) > 1
    )


def _axis(mesh: Mesh, axis: str, dim: int) -> Optional[str]:
    """Shard ``dim`` over ``axis`` only when it divides evenly."""
    size = mesh.shape[axis]
    return axis if size > 1 and dim % size == 0 else None


def param_pspecs(cfg: GemmaConfig, mesh: Mesh) -> dict[str, Any]:
    """PartitionSpec pytree matching ``init_params`` output."""
    m = lambda dim: _axis(mesh, MODEL_AXIS, dim)
    whole = lambda rank: P(*(None,) * rank)
    if cfg.hybrid:
        return _hybrid_pspecs(cfg, m, whole)
    attention = {
        "pre_attn_norm": P(None, None),
        "pre_mlp_norm": P(None, None),
        "wq": P(None, None, m(cfg.n_heads), None),
        "wk": P(None, None, m(cfg.n_kv_heads), None),
        "wv": P(None, None, m(cfg.n_kv_heads), None),
        "wo": P(None, m(cfg.n_heads), None, None),
    }
    if cfg.latent:
        # The bottlenecks and their norms whole on every device; the per-head
        # expansions and Wo split by head, as the absorbed kernel's queries.
        attention = {
            "pre_attn_norm": whole(2), "pre_mlp_norm": whole(2),
            "w_dq": whole(3), "q_lora_norm": whole(2), "w_dkv": whole(3), "kv_lora_norm": whole(2),
            "w_uq": P(None, None, m(cfg.n_heads), None),
            "w_ukv": P(None, None, m(cfg.n_heads), None),
            "wo": P(None, m(cfg.n_heads), None, None),
        }
        if cfg.index_topk:
            # Index heads split like the query expansion; the key's and the
            # weights' projections whole.
            attention.update(
                w_qi=P(None, None, m(cfg.index_n_heads), None), w_ki=whole(3),
                ki_norm=whole(2), ki_norm_bias=whole(2), w_wi=whole(3),
            )
    # What a block beyond the default adds stays whole on every device.
    if cfg.qk_norm:
        attention.update(q_norm=whole(2), k_norm=whole(2))
    if cfg.attn_gate:
        attention["w_attn_gate"] = whole(4)
    if cfg.post_norms:
        attention.update(post_attn_norm=whole(2), post_mlp_norm=whole(2))
    dense_ff = {
        "w_gate": P(None, None, m(cfg.d_ff)),
        "w_up": P(None, None, m(cfg.d_ff)),
        "w_down": P(None, m(cfg.d_ff), None),
    }
    specs = {
        "embed": P(m(cfg.vocab_size), None),
        "layers": {**attention, **dense_ff},
        "final_norm": P(None),
    }
    if cfg.n_experts:
        # The experts this device holds stay whole on every device of the
        # mesh: which experts a device holds is the configuration's
        # (expert_first / experts_held), not yet an axis of the mesh
        # (ROADMAP M4 keeps the expert axis and its exchange).
        specs["layers"].update(router=whole(3), w_gate=whole(4), w_up=whole(4), w_down=whole(4))
        if cfg.router_bias_scale:
            specs["layers"]["router_bias"] = whole(2)
        if cfg.d_shared_expert:
            specs["layers"].update(shared_gate=whole(3), shared_up=whole(3), shared_down=whole(3))
        if cfg.n_dense_layers:
            specs["dense_layers"] = {**attention, **dense_ff}
    if not cfg.tie_embeddings:
        specs["head"] = P(None, m(cfg.vocab_size))
    return specs


def _hybrid_pspecs(cfg: GemmaConfig, m, whole) -> dict[str, Any]:
    """``param_pspecs`` of a ``layer_pattern`` model: three stacks. The
    attention's heads split over ``model`` as everywhere; the Mamba leaves,
    the router, the latent projections, the shared expert and the experts
    held stay whole on every device (the cut's deployment: data-parallel
    mixers, and which experts a device holds is the configuration's). An
    ``L`` / ``S`` pattern's two stacks are whole on every device: its one cell
    runs on one chip."""
    if cfg.conv_ffn:
        # Whole on every device: its one cell runs on one chip (the attention's
        # heads lie ``kv_pack`` to a pool row, which a split by head would cut).
        specs = {"embed": P(m(cfg.vocab_size), None), "final_norm": P(None)}
        if cfg.n_conv_layers:
            specs["conv_layers"] = {"norm": whole(2), "w_in": whole(3), "conv_w": whole(3), "w_out": whole(3)}
        if cfg.n_attn_layers:
            specs["attn_layers"] = {
                "norm": whole(2), "wq": whole(3), "wk": whole(3), "wv": whole(3), "wo": whole(3),
                "q_norm": whole(2), "k_norm": whole(2),
            }
        ffn = {"pre_mlp_norm": whole(2)}
        if cfg.n_layers > cfg.n_sparse_layers:
            specs["dense_layers"] = {**ffn, "w_gate": whole(3), "w_up": whole(3), "w_down": whole(3)}
        if cfg.n_sparse_layers:
            specs["layers"] = {**ffn, "router": whole(3), "w_gate": whole(4), "w_up": whole(4), "w_down": whole(4)}
            if cfg.router_bias_scale:
                specs["layers"]["router_bias"] = whole(2)
        if not cfg.tie_embeddings:
            specs["head"] = P(None, m(cfg.vocab_size))
        return specs
    if cfg.scan_ffn:
        # Whole on every device: its one cell runs on one chip, and ONE KV head
        # has nothing to split.
        ffn = {"norm": whole(2), "mlp_norm": whole(2), "w_gate": whole(3), "w_up": whole(3), "w_down": whole(3)}
        specs = {"embed": P(m(cfg.vocab_size), None), "final_norm": P(None)}
        if cfg.n_scan_layers:
            specs["scan_layers"] = {
                **ffn, "w_in": whole(3), "conv_w": whole(3), "conv_b": whole(2), "w_x": whole(3),
                "dt_norm": whole(2), "b_norm": whole(2), "c_norm": whole(2), "w_dt": whole(3),
                "dt_bias": whole(2), "A_log": whole(3), "D_skip": whole(2), "w_out": whole(3),
            }
        if cfg.n_attn_layers:
            specs["attn_layers"] = {**ffn, "wq": whole(3), "wk": whole(3), "wv": whole(3), "wo": whole(3)}
        if not cfg.tie_embeddings:
            specs["head"] = P(None, m(cfg.vocab_size))
        return specs
    if cfg.mixer_ffn:
        both = {
            "norm": whole(2), "mlp_norm": whole(2), "wq": whole(3), "wk": whole(3), "wv": whole(3),
            "wo": whole(3), "w_gate": whole(3), "w_up": whole(3), "w_down": whole(3),
        }
        if cfg.attn_gate:
            both["w_attn_gate"] = whole(3)
        specs = {"embed": P(m(cfg.vocab_size), None), "final_norm": P(None)}
        if cfg.n_linear_layers:
            specs["linear_layers"] = {**both, "o_norm": whole(2)}
            if cfg.qk_norm:
                specs["linear_layers"].update(q_norm=whole(2), k_norm=whole(2))
        if cfg.n_block_layers:
            specs["block_layers"] = dict(both)
        if not cfg.tie_embeddings:
            specs["head"] = P(None, m(cfg.vocab_size))
        return specs
    specs = {
        "embed": P(m(cfg.vocab_size), None),
        "final_norm": P(None),
        "mamba_layers": {
            "norm": whole(2), "w_in": whole(3), "conv_w": whole(3), "conv_b": whole(2),
            "dt_bias": whole(2), "A_log": whole(2), "D_skip": whole(2), "gate_norm": whole(2),
            "w_out": whole(3),
        },
        "attn_layers": {
            "norm": whole(2),
            # heads merged with head_dim, head-major: a split of the merged
            # axis is a split by head where the heads divide
            "wq": P(None, None, m(cfg.n_heads)),
            "wk": P(None, None, m(cfg.n_kv_heads)),
            "wv": P(None, None, m(cfg.n_kv_heads)),
            "wo": P(None, m(cfg.n_heads), None),
        },
        "layers": {
            "norm": whole(2), "router": whole(3), "shared_up": whole(3), "shared_down": whole(3),
            "w_up": whole(4), "w_down": whole(4),
        },
    }
    if cfg.router_bias_scale:
        specs["layers"]["router_bias"] = whole(2)
    if cfg.moe_latent_size:
        specs["layers"].update(latent_down=whole(3), latent_up=whole(3))
    if not cfg.tie_embeddings:
        specs["head"] = P(None, m(cfg.vocab_size))
    return specs


def kv_cache_pspecs(cfg: GemmaConfig, mesh: Mesh, batch: int) -> dict[str, Any]:
    b = _axis(mesh, DATA_AXIS, batch)
    k = _axis(mesh, MODEL_AXIS, cfg.n_kv_heads)
    spec = P(None, b, None, k, None)  # [L, B, S, K, hd]
    return {"k": spec, "v": spec}


def data_pspec(mesh: Mesh, batch: int) -> P:
    return P(_axis(mesh, DATA_AXIS, batch))


def replicated(mesh: Mesh) -> P:
    return P()


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a pytree on the mesh according to a spec pytree."""
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)), tree, specs
    )
