"""Grammar build cost vs registry scale (VERDICT r4 weak #5).

The sparse DFA×trie product's 30M-visit budget bounds build cost by
*assumption*; this probe bounds it by *measurement*: for registry sizes
1k→100k it times the constrained-grammar build on each committed vocab,
reports the compact-table footprint, and records which fallback tier the
planner's ladder (keys→no-keys→shape-only) would actually land on — the
registry-name guarantee is only as real as the tier that compiles.

Host-only (grammar construction never touches the device); one JSON line
per (vocab, size), so a results table is a paste of stdout.

Usage: [SIZES=1000,10000] python benchmarks/grammar_scale.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mcpx.models.tokenizer import make_tokenizer  # noqa: E402
from mcpx.planner.grammar import build_plan_grammar  # noqa: E402
from mcpx.utils.synth import synth_registry  # noqa: E402


def _table_mb(g) -> float:
    total = 0
    for name in ("ctrans", "cmask", "active_ids", "eos_cols"):
        arr = getattr(g, name, None)
        if arr is not None:
            total += arr.size * arr.itemsize
    return total / 1e6


def probe(vocab: str, n: int) -> dict:
    tok = make_tokenizer(vocab)
    records = synth_registry(n, seed=0)
    names = [r.name for r in records]
    keys = sorted(
        {k for r in records for k in (*r.input_schema, *r.output_schema)}
    )
    out: dict = {"vocab": vocab, "n_services": n, "n_keys": len(keys)}
    # The planner's fallback ladder, timed tier by tier.
    for tier, kw in (
        ("keys", dict(service_names=names, input_keys=keys)),
        ("names_only", dict(service_names=names)),
        ("shape_only", dict()),
    ):
        t0 = time.perf_counter()
        try:
            g = build_plan_grammar(tok, **kw)
            out[tier] = {
                "build_s": round(time.perf_counter() - t0, 3),
                "n_states": int(g.ctrans.shape[0]),
                "n_cols": int(g.ctrans.shape[1]),
                "table_mb": round(_table_mb(g), 2),
            }
            if "tier" not in out:
                out["tier"] = tier  # what the planner would serve with
        except ValueError as e:
            out[tier] = {"build_s": round(time.perf_counter() - t0, 3),
                         "error": str(e)[:100]}
    return out


def main() -> None:
    sizes = [int(s) for s in os.environ.get(
        "SIZES", "1000,3000,10000,30000,100000").split(",")]
    for vocab in ("byte", "bpe"):
        for n in sizes:
            print(json.dumps(probe(vocab, n)), flush=True)


if __name__ == "__main__":
    main()
