"""The benchmark's one child process: the real served path at a
configuration's dimensions, on loopback.

``mcpx serve`` can only build the presets of ``GemmaConfig.named``; the
benchmark's configurations are other models' published widths. So this
process assembles the same server from the seams that exist:
``InferenceEngine(config, model_cfg=...)`` -> ``LLMPlanner(engine,
config.planner)`` -> ``build_control_plane(config, planner=...)`` ->
``build_app`` — the aiohttp app ``mcpx serve`` runs, unchanged. Nothing in
``mcpx/`` is patched. The model config comes from the configuration's block
module (``models/<module>.py``, named by the configuration file): this file
knows no model.

One benchmark-only route is added to the app: ``POST /bench/reference``
runs the program's model step, at the slab's shape, against the plain
reference (``reference.compare_with_engine_step``) on the engine's own
weights and mesh, outside the measured window. Only this process holds the chip, so
only it can.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-file", required=True, help="configs/<config>.json")
    ap.add_argument("--mcpx-config", required=True, help="MCPXConfig JSON the parent wrote")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.time()
    sys.path.insert(0, ROOT)

    import spec
    from aiohttp import web

    from mcpx.core.config import MCPXConfig
    from mcpx.engine.engine import InferenceEngine
    from mcpx.planner.llm import LLMPlanner
    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane
    from mcpx.telemetry.tracing import configure_logging

    configure_logging()
    with open(args.config_file) as f:
        config = json.load(f)
    block = spec.load_block(config.get("module"), HERE)
    cfg = MCPXConfig.from_file(args.mcpx_config)
    t_imported = time.time()

    import jax

    platform = jax.default_backend()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if platform != want:
        raise SystemExit(f"child: JAX backend is {platform!r}, not {want!r}")
    n_dev = len(jax.devices())
    if not args.rehearse_cpu and n_dev < int(config["chips"]):
        raise SystemExit(f"child: {n_dev} device(s), the configuration needs {config['chips']}")

    from mcpx.models.tokenizer import make_tokenizer

    vocab = make_tokenizer(cfg.model.vocab).vocab_size
    # The published keys are checked in a rehearsal too; only the size differs.
    model_cfg = block.model_config(spec.model_keys(config), vocab)
    if args.rehearse_cpu:
        model_cfg = block.rehearsal_config(vocab)  # the same block at CPU size
    dims = dataclasses.asdict(model_cfg)  # what the block's reference reads
    engine = InferenceEngine(cfg, model_cfg=model_cfg)
    planner = LLMPlanner(engine, cfg.planner)
    cp = build_control_plane(cfg, planner=planner)
    # build_control_plane made its own Metrics; the engine made its own too
    # (the factory passes one registry to both only on its from_config path).
    # Counters the benchmark reads (compiles, resets) live on the engine's.
    app = build_app(cp)

    async def reference_handler(request: web.Request) -> web.Response:
        body = await request.json()
        if engine.state != "ready":
            return web.json_response({"error": f"engine {engine.state}"}, status=409)
        from reference import compare_with_engine_step

        def _run():
            return compare_with_engine_step(
                block, engine._params,  # mcpx: ignore[thread-ownership] - read-only use after 'ready': the worker binds _params once, in _setup
                model_cfg, dims, engine._mesh,
                seed=int(body.get("seed", 0)),
                interpret=bool(cfg.engine.interpret),
                page_size=cfg.engine.kv_page_size,
                rows=cfg.engine.max_batch_size,
                pages_per_row=cfg.engine.max_pages_per_seq,
                prefill_len=cfg.engine.warmup_max_len,
                control=str(body.get("control") or ""),
            )

        t0 = time.time()
        out = await asyncio.to_thread(_run)
        out["seconds"] = time.time() - t0
        return web.json_response(out)

    async def marks_handler(request: web.Request) -> web.Response:
        return web.json_response(
            {"t_start": t_start, "t_imported": t_imported, "t_app_built": t_built,
             "kernel_paths": block.kernel_paths,
             "engine_metrics": engine.metrics.render().decode()}
        )

    app.router.add_post("/bench/reference", reference_handler)
    app.router.add_get("/bench/marks", marks_handler)
    t_built = time.time()
    web.run_app(app, host="127.0.0.1", port=args.port, print=None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
