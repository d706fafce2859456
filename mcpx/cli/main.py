"""CLI: ``python -m mcpx.cli`` — serve the control plane, manage registries.

Replaces the reference's bare ``uvicorn.run`` dev block
(``control_plane.py:155-157``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from mcpx.core.config import MCPXConfig


def _load_config(args: argparse.Namespace) -> MCPXConfig:
    if args.config:
        cfg = MCPXConfig.from_file(args.config)
    else:
        cfg = MCPXConfig.from_env()
    if args.registry_file:
        cfg.registry.backend = "file"
        cfg.registry.file_path = args.registry_file
    if args.planner:
        cfg.planner.kind = args.planner
    return cfg


def cmd_serve(args: argparse.Namespace) -> int:
    import os

    from aiohttp import web

    from mcpx.server.app import build_app
    from mcpx.server.factory import build_control_plane
    from mcpx.telemetry.tracing import configure_logging

    # Every log line carries the active request's trace_id/span_id
    # (tracing spine); MCPX_LOG_JSON=1 or --log-json switches to one JSON
    # object per line for log pipelines.
    configure_logging(
        json_logs=bool(args.log_json or os.environ.get("MCPX_LOG_JSON") == "1")
    )
    cfg = _load_config(args)
    if args.port:
        cfg.server.port = args.port
    if args.chaos:
        # Chaos injection (docs/resilience.md): wrap the transport in the
        # seeded fault injector described by the profile file.
        cfg.resilience.chaos_profile = args.chaos
    cp = build_control_plane(cfg)
    app = build_app(cp)
    web.run_app(app, host=cfg.server.host, port=cfg.server.port)
    return 0


def _http_json(url: str, timeout_s: float = 10.0):
    """GET ``url`` → parsed JSON. Sync CLI context — urllib is fine here
    (no event loop to block) and saves an aiohttp session for one call."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        try:
            detail = json.loads(e.read().decode()).get("error", "")
        except Exception:  # mcpx: ignore[broad-except] - error body is best-effort detail; the HTTPError itself is re-raised below
            detail = ""
        raise RuntimeError(f"{url}: HTTP {e.code} {detail}".strip()) from e
    except (urllib.error.URLError, OSError) as e:
        raise RuntimeError(f"{url}: {e}") from e


def cmd_trace(args: argparse.Namespace) -> int:
    """Inspect/export the server's retained traces (tracing spine,
    docs/observability.md). ``list`` prints ring summaries; ``dump`` writes
    one trace as Chrome trace-event JSON that loads in Perfetto
    (ui.perfetto.dev) or chrome://tracing."""
    base = args.url.rstrip("/")
    try:
        if args.action == "list":
            out = _http_json(f"{base}/traces")
            print(json.dumps(out, indent=2))
            return 0
        # dump: explicit --id, else the newest retained trace.
        trace_id = args.id
        if not trace_id:
            traces = _http_json(f"{base}/traces").get("traces", [])
            if not traces:
                print(json.dumps({"error": "no traces retained on the server"}))
                return 1
            trace_id = traces[0]["trace_id"]
        chrome = _http_json(f"{base}/traces/{trace_id}?format=chrome")
        out_path = args.out or f"trace_{trace_id}.json"
        with open(out_path, "w") as f:
            json.dump(chrome, f)
        print(
            json.dumps(
                {
                    "trace_id": trace_id,
                    "wrote": out_path,
                    "events": len(chrome.get("traceEvents", [])),
                    "open_with": "https://ui.perfetto.dev (Open trace file)",
                }
            )
        )
        return 0
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}))
        return 1


def cmd_debug(args: argparse.Namespace) -> int:
    """Flight recorder tooling (mcpx/telemetry/flight.py,
    docs/observability.md). ``bundle`` fetches one diagnostic bundle from
    a running server — ``--id``, or the newest captured — validates its
    schema, and writes it to a local file; the round trip the acceptance
    tests gate on."""
    from mcpx.telemetry.flight import _bundle_trace_ids, validate_bundle

    base = args.url.rstrip("/")
    try:
        status = _http_json(f"{base}/debug/anomalies")
        if args.action == "list":
            print(json.dumps(status, indent=2))
            return 0
        # bundle: explicit --id, else the newest captured bundle.
        if not status.get("enabled"):
            print(json.dumps({"error": "flight recorder disabled on the server"}))
            return 1
        bundle_id = args.id
        if not bundle_id:
            bundles = status.get("bundles", [])
            if not bundles:
                print(json.dumps({"error": "no bundles captured on the server"}))
                return 1
            bundle_id = bundles[-1]["bundle_id"]
        bundle = _http_json(f"{base}/debug/anomalies/{bundle_id}")
        problems = validate_bundle(bundle)
        out_path = args.out or f"bundle_{bundle_id}.json"
        with open(out_path, "w") as f:
            json.dump(bundle, f, indent=2)
        print(
            json.dumps(
                {
                    "bundle_id": bundle_id,
                    "wrote": out_path,
                    "valid": not problems,
                    **({"problems": problems} if problems else {}),
                    "trigger": bundle.get("trigger"),
                    "window_snapshots": len(bundle.get("window") or []),
                    "trace_ids": _bundle_trace_ids(bundle)[:8],
                }
            )
        )
        return 0 if not problems else 1
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}))
        return 1


def cmd_explain(args: argparse.Namespace) -> int:
    """Decision-provenance explanation for one trace from a running server
    (mcpx/telemetry/provenance.py, docs/observability.md "Decision
    provenance & /explain"): fetches GET /explain/{trace_id}, validates the
    schema, prints the human-readable narrative followed by the structured
    JSON. ``--id`` optional: defaults to the newest retained trace, so
    ``mcpx explain`` right after a failed request explains THAT request."""
    from mcpx.telemetry.provenance import validate_explanation

    base = args.url.rstrip("/")
    try:
        trace_id = args.trace_id
        if not trace_id:
            traces = _http_json(f"{base}/traces").get("traces", [])
            if not traces:
                print(json.dumps({"error": "no traces retained on the server"}))
                return 1
            trace_id = traces[0]["trace_id"]
        out = _http_json(f"{base}/explain/{trace_id}")
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}))
        return 1
    problems = validate_explanation(out)
    for line in out.get("narrative", []):
        print(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    if problems:
        print(json.dumps({"error": "invalid explanation", "problems": problems}))
        return 1
    return 0


def cmd_usage(args: argparse.Namespace) -> int:
    """Per-tenant usage ledger from a running server (mcpx/telemetry/
    ledger.py, docs/observability.md "Cost ledger & SLO budgets"):
    itemized cost aggregates per tenant + recent bills — the CLI half of
    the GET /usage round trip the acceptance tests gate on."""
    base = args.url.rstrip("/")
    try:
        out = _http_json(f"{base}/usage")
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}))
        return 1
    if not out.get("enabled"):
        print(json.dumps({"error": "cost ledger disabled on the server"}))
        return 1
    if args.tenant:
        acct = out.get("tenants", {}).get(args.tenant)
        out = {
            "enabled": True,
            "tenant": args.tenant,
            "totals": acct,
            "recent": [
                b for b in out.get("recent", []) if b.get("tenant") == args.tenant
            ],
        }
        if acct is None:
            out["error"] = f"no usage recorded for tenant '{args.tenant}'"
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    return 0


def cmd_slo(args: argparse.Namespace) -> int:
    """SLO error-budget state from a running server (mcpx/telemetry/
    slo.py): per-objective burn rates and budget remaining, global + per
    tenant. Exit 3 when any global objective is breaching (fast burn at
    or over the page threshold) so scripts can gate on budget health."""
    base = args.url.rstrip("/")
    try:
        out = _http_json(f"{base}/slo")
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}))
        return 1
    if not out.get("enabled"):
        print(json.dumps({"error": "SLO engine disabled on the server"}))
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    breaching = bool(out.get("global", {}).get("breaching"))
    return 3 if breaching else 0


def cmd_validate(args: argparse.Namespace) -> int:
    """Validate a plan JSON file against the DAG schema."""
    from mcpx.core.dag import Plan, PlanValidationError

    if args.file == "-":
        text = sys.stdin.read()
    else:
        try:
            with open(args.file) as f:
                text = f.read()
        except OSError as e:
            print(json.dumps({"valid": False, "problems": [f"cannot read {args.file}: {e}"]}))
            return 1
    try:
        plan = Plan.from_json(text)
    except PlanValidationError as e:
        print(json.dumps({"valid": False, "problems": e.problems}, indent=2))
        return 1
    print(
        json.dumps(
            {"valid": True, "generations": plan.topological_generations()}, indent=2
        )
    )
    return 0


def cmd_gen_registry(args: argparse.Namespace) -> int:
    """Generate a synthetic N-service registry file (benchmarks)."""
    from mcpx.utils.synth import synth_registry

    records = synth_registry(args.n, seed=args.seed)
    with open(args.out, "w") as f:
        json.dump([r.to_dict() for r in records], f, indent=2)
    print(f"wrote {len(records)} services to {args.out}")
    return 0


def cmd_train_planner(args: argparse.Namespace) -> int:
    """Train the in-tree planner model on the synthetic workload corpus and
    write a committable single-file .npz checkpoint (models/train.py)."""
    import time

    if args.platform == "cpu":
        # Must run BEFORE the jax-importing modules below: a chip belongs
        # to one process at a time, and a "CPU" training run that let jax
        # pick its backend would take it from whatever is serving there.
        from mcpx.utils.backend import force_virtual_cpu

        force_virtual_cpu(1)

    from mcpx.models.corpus import CorpusConfig, build_corpus_sync
    from mcpx.models.gemma.config import GemmaConfig
    from mcpx.models.tokenizer import make_tokenizer
    from mcpx.models.train import TrainConfig, load_npz, save_npz, train

    tok = make_tokenizer(args.vocab)
    ccfg = CorpusConfig(
        n_examples=args.examples,
        registry_size=args.registry,
        seed=args.seed,
        intent_seed=args.intent_seed,
    )
    t0 = time.time()
    corpus = build_corpus_sync(tok, ccfg)
    print(
        f"corpus: {corpus.tokens.shape[0]} rows (dropped {corpus.n_dropped}, "
        f"filtered {corpus.n_filtered}, teacher coverage "
        f"{corpus.teacher_coverage:.3f}) in {time.time() - t0:.1f}s"
    )
    cfg = GemmaConfig.named(args.size, vocab_size=tok.vocab_size)
    tcfg = TrainConfig(
        steps=args.steps, batch_size=args.batch, lr=args.lr, seed=args.seed
    )
    init = None
    if args.init:
        import jax
        import jax.numpy as jnp

        # Warm start (fine-tune): e.g. extend intent coverage over the same
        # registry with --intent-seed, at a lower --lr.
        init = jax.tree.map(lambda a: a.astype(jnp.float32), load_npz(args.init))
    t0 = time.time()
    params, report = train(
        cfg, corpus, tcfg, init=init, log_fn=lambda m: print(m, flush=True)
    )
    print(f"trained {args.steps} steps in {time.time() - t0:.0f}s: {report}")
    save_npz(args.out, params)
    print(f"wrote {args.out}")
    return 0


def cmd_eval_planner(args: argparse.Namespace) -> int:
    """Serve a planner checkpoint through the real stack (engine +
    grammar-constrained decode + retrieval shortlist) and print its
    plan-quality metrics as one JSON line (``planner/evaluate.py``)."""
    if args.platform == "cpu":
        from mcpx.utils.backend import force_virtual_cpu

        force_virtual_cpu(1)

    from mcpx.planner.evaluate import evaluate_planner

    out = asyncio.run(
        evaluate_planner(
            checkpoint=args.checkpoint,
            size=args.size,
            vocab=args.vocab,
            registry_size=args.registry,
            registry_seed=args.registry_seed,
            n_intents=args.intents,
            seed=args.seed,
            constrain_names=args.constrain_names,
            quantize=args.quantize,
        )
    )
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v for k, v in out.items()}))
    return 0


def cmd_lint(args: argparse.Namespace) -> int:
    """Run mcpxlint (mcpx/analysis/) over the given paths and diff against
    the committed baseline. Non-zero exit on any new finding or stale
    baseline entry — the same check tests/test_mcpxlint.py gates tier-1 on."""
    from mcpx.analysis.cli import run_lint

    return run_lint(
        args.paths,
        baseline=args.baseline,
        update_baseline=args.update_baseline,
        fmt=args.format,
        rules=args.rule or None,
        changed=args.changed,
        fix=args.fix,
        fix_dry_run=args.dry_run,
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="mcpx")
    parser.add_argument("--config", help="JSON config file")
    parser.add_argument("--registry-file", help="service registry JSON file")
    parser.add_argument("--planner", choices=["llm", "heuristic", "mock"])
    sub = parser.add_subparsers(dest="command", required=True)

    p_serve = sub.add_parser("serve", help="run the control-plane server")
    p_serve.add_argument("--port", type=int, default=0)
    p_serve.add_argument(
        "--log-json", action="store_true",
        help="one JSON object per log line (trace_id/span_id fields included)",
    )
    p_serve.add_argument(
        "--chaos", default="", metavar="PROFILE_JSON",
        help="serve through a seeded fault-injecting transport described by "
        "this chaos profile file (docs/resilience.md)",
    )
    p_serve.set_defaults(func=cmd_serve)

    p_trace = sub.add_parser(
        "trace", help="inspect/export request traces from a running server"
    )
    p_trace.add_argument("action", choices=["list", "dump"])
    p_trace.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="server base URL (default: %(default)s)",
    )
    p_trace.add_argument(
        "--id", default="",
        help="trace id to dump (default: the newest retained trace)",
    )
    p_trace.add_argument(
        "--out", default="",
        help="output path for dump (default: trace_<id>.json)",
    )
    p_trace.set_defaults(func=cmd_trace)

    p_debug = sub.add_parser(
        "debug",
        help="flight-recorder tooling: list detector state, fetch anomaly bundles",
    )
    p_debug.add_argument("action", choices=["list", "bundle"])
    p_debug.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="server base URL (default: %(default)s)",
    )
    p_debug.add_argument(
        "--id", default="",
        help="bundle id to fetch (default: the newest captured bundle)",
    )
    p_debug.add_argument(
        "--out", default="",
        help="output path for bundle (default: bundle_<id>.json)",
    )
    p_debug.set_defaults(func=cmd_debug)

    p_explain = sub.add_parser(
        "explain",
        help="decision-provenance narrative for one trace from a running server",
    )
    p_explain.add_argument(
        "trace_id", nargs="?", default="",
        help="trace id to explain (default: the newest retained trace)",
    )
    p_explain.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="server base URL (default: %(default)s)",
    )
    p_explain.add_argument(
        "--out", default="", help="also write the explanation JSON to this path"
    )
    p_explain.set_defaults(func=cmd_explain)

    p_usage = sub.add_parser(
        "usage", help="per-tenant usage ledger from a running server"
    )
    p_usage.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="server base URL (default: %(default)s)",
    )
    p_usage.add_argument(
        "--tenant", default="",
        help="show one tenant's totals + recent bills only",
    )
    p_usage.add_argument(
        "--out", default="", help="also write the report to this path"
    )
    p_usage.set_defaults(func=cmd_usage)

    p_slo = sub.add_parser(
        "slo", help="SLO error-budget state from a running server"
    )
    p_slo.add_argument(
        "--url", default="http://127.0.0.1:8000",
        help="server base URL (default: %(default)s)",
    )
    p_slo.add_argument(
        "--out", default="", help="also write the report to this path"
    )
    p_slo.set_defaults(func=cmd_slo)

    p_val = sub.add_parser("validate", help="validate a plan JSON file")
    p_val.add_argument("file", help="path or - for stdin")
    p_val.set_defaults(func=cmd_validate)

    p_gen = sub.add_parser("gen-registry", help="generate a synthetic registry")
    p_gen.add_argument("n", type=int)
    p_gen.add_argument("--out", default="registry.json")
    p_gen.add_argument("--seed", type=int, default=0)
    p_gen.set_defaults(func=cmd_gen_registry)

    p_train = sub.add_parser(
        "train-planner", help="train the in-tree planner model (synthetic corpus)"
    )
    p_train.add_argument("--out", default="mcpx/models/checkpoints/planner_test_bpe.npz")
    p_train.add_argument("--size", default="test")
    p_train.add_argument("--vocab", default="bpe")
    p_train.add_argument("--examples", type=int, default=4096)
    p_train.add_argument("--registry", type=int, default=1000)
    p_train.add_argument("--steps", type=int, default=2500)
    p_train.add_argument("--batch", type=int, default=24)
    p_train.add_argument("--lr", type=float, default=3e-3)
    p_train.add_argument("--seed", type=int, default=0)
    p_train.add_argument("--intent-seed", type=int, default=None,
                         help="fresh intent draws over the same registry")
    p_train.add_argument("--init", default="",
                         help="warm-start from an existing .npz checkpoint")
    p_train.add_argument("--platform", choices=["cpu", "auto"], default="cpu",
                         help="cpu (default): pin to host CPU — never takes "
                         "the chip; auto: whatever jax picks")
    p_train.set_defaults(func=cmd_train_planner)

    p_eval = sub.add_parser(
        "eval-planner", help="score a planner checkpoint's plan quality"
    )
    p_eval.add_argument("--checkpoint", default="mcpx/models/checkpoints/planner_test_bpe.npz")
    p_eval.add_argument("--size", default="test")
    p_eval.add_argument("--vocab", default="bpe")
    p_eval.add_argument("--registry", type=int, default=1000)
    p_eval.add_argument("--registry-seed", type=int, default=0)
    p_eval.add_argument("--intents", type=int, default=48)
    p_eval.add_argument("--seed", type=int, default=1234)
    p_eval.add_argument("--quantize", choices=["none", "int8"], default="none",
                        help="serve the checkpoint weight-only quantized "
                        "(models/gemma/quant.py) — reproduces the README's "
                        "int8 plan-quality claim")
    p_eval.add_argument("--constrain-names", choices=["registry", "shortlist"],
                        default="registry",
                        help="grammar tier: registry-wide name trie (serving "
                        "default) or shortlist-only (tightest constraint)")
    p_eval.add_argument("--platform", choices=["cpu", "auto"], default="auto",
                        help="cpu: pin to host CPU (never takes the "
                        "chip); auto (default): whatever jax picks")
    p_eval.set_defaults(func=cmd_eval_planner)

    p_lint = sub.add_parser(
        "lint", help="static analysis (mcpxlint): async-safety + TPU hot-path rules"
    )
    p_lint.add_argument("paths", nargs="+", help="files or directories to scan")
    p_lint.add_argument(
        "--baseline",
        default="mcpxlint.baseline.json",
        help="baseline file of grandfathered findings (default: %(default)s)",
    )
    p_lint.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline from the current findings and exit 0",
    )
    p_lint.add_argument(
        "--format", choices=["text", "json", "sarif"], default="text",
        help="report format (json includes run telemetry for CI; sarif is "
        "SARIF 2.1.0 for code-scanning/editor tooling)",
    )
    p_lint.add_argument(
        "--rule", action="append", metavar="RULE_ID",
        help="run only this rule (repeatable; default: all)",
    )
    p_lint.add_argument(
        "--changed", action="store_true",
        help="report only files modified vs HEAD (staged/unstaged/"
        "untracked); interprocedural passes still see the full path set",
    )
    p_lint.add_argument(
        "--fix", action="store_true",
        help="rewrite mechanical findings in place (unused/duplicate "
        "suppression ids, blank-line runs) and exit 0",
    )
    p_lint.add_argument(
        "--dry-run", action="store_true",
        help="with --fix: print the unified diff without writing files",
    )
    p_lint.set_defaults(func=cmd_lint)

    args = parser.parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    raise SystemExit(main())
