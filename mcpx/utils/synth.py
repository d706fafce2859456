"""Synthetic registries and workloads for tests and benchmarks.

Generates deterministic N-service registries whose schemas chain (each
service's outputs feed plausible downstream inputs), mirroring the baseline
ladder's 3/10/100/1k-service registries (BASELINE.json configs).
"""

from __future__ import annotations

import random

from mcpx.registry.base import ServiceRecord

_DOMAINS = [
    "auth", "user", "order", "billing", "catalog", "search", "inventory",
    "shipping", "payment", "fraud", "notify", "report", "analytics", "geo",
    "translate", "summarize", "extract", "rank", "recommend", "audit",
]
_VERBS = ["fetch", "validate", "enrich", "score", "transform", "merge", "route", "sync"]
_KEYS = [
    "query", "user_id", "order_id", "document", "text", "items", "amount",
    "address", "score", "status", "report", "features", "vector", "summary",
]

_OOD_VERBS = ["Get", "Set", "Sync", "Push", "Resolve", "Compute", "Reconcile", "Emit"]
_OOD_NOUNS = [
    "Invoice", "Customer", "Ledger", "Shipment", "Session", "Voucher",
    "Manifest", "Quota", "Dunning", "Waybill", "Escrow", "Tranche",
    "Chargeback", "Remittance", "Accrual", "Folio", "Consignment", "Lien",
    "Novation", "Subrogation",
]
_OOD_KEYS = [
    "invoiceId", "custRef", "ledgerRow", "sku", "sessionKey", "waybillNo",
    "escrowAcct", "trancheId", "folioRef", "accrualTs", "manifestHash",
    "quotaCeil", "dunningStage", "lienPos",
]


def _build_registry(
    n: int,
    seed: int,
    local: bool,
    *,
    primary: list[str],
    secondary: list[str],
    keys: list[str],
    name_fmt: str,
    description_fmt: str,
    interleaved_draws: bool = False,
) -> list[ServiceRecord]:
    """One record-construction loop for every naming universe: the in- and
    out-of-distribution registries must keep IDENTICAL chaining structure
    (key-sample sizes, cost ranges, fallback rate) or a comparison on
    the OOD registry stops isolating tokenizer fit from workload shape.

    RNG draw order is a compatibility surface: the committed BPE vocab,
    checkpoint, and every pinned "registry seed N" protocol artifact depend
    on the exact historical sequences. The two registries historically drew
    in DIFFERENT orders (in-dist: both counts, then both samples; OOD:
    count/sample interleaved) — ``interleaved_draws`` reproduces each
    byte-for-byte rather than silently regenerating different registries
    under the same protocol label."""
    rng = random.Random(seed)
    records: list[ServiceRecord] = []
    for i in range(n):
        a = primary[i % len(primary)]
        b = secondary[(i // len(primary)) % len(secondary)]
        name = name_fmt.format(a=a, b=b, i=i)
        if interleaved_draws:
            input_keys = rng.sample(keys, rng.randint(1, 3))
            output_keys = rng.sample(keys, rng.randint(1, 2))
        else:
            n_in = rng.randint(1, 3)
            n_out = rng.randint(1, 2)
            input_keys = rng.sample(keys, n_in)
            output_keys = rng.sample(keys, n_out)
        scheme = "local" if local else "http"
        records.append(
            ServiceRecord(
                name=name,
                endpoint=f"{scheme}://{name}",
                description=description_fmt.format(a=a, b=b),
                input_schema={k: "str" for k in input_keys},
                output_schema={k: "str" for k in output_keys},
                cost_profile={
                    "latency_ms": round(rng.uniform(5, 80), 1),
                    "cost": round(rng.uniform(0.1, 2.0), 2),
                },
                fallbacks=[f"{scheme}://{name}-fb"] if rng.random() < 0.3 else [],
                tags=[a, b],
            )
        )
    return records


def synth_registry(n: int, seed: int = 0, local: bool = True) -> list[ServiceRecord]:
    return _build_registry(
        n,
        seed,
        local,
        primary=_DOMAINS,
        secondary=_VERBS,
        keys=_KEYS,
        name_fmt="{a}-{b}-{i:04d}",
        description_fmt="{b}s {a} data for downstream composition",
    )


def synth_registry_ood(n: int, seed: int = 0, local: bool = True) -> list[ServiceRecord]:
    """An OUT-of-distribution registry: camelCase product-style naming with
    a token universe disjoint from ``synth_registry``'s — the workload the
    committed BPE vocab was NOT fitted to (its ~6-8x compression is
    registry-fitted; `tests/test_bpe.py` pins the 1.6-2.1x OOD floor).
    No caller since PR 30 (ROADMAP, Design debts). Same chaining structure as ``synth_registry`` (shared
    ``_build_registry`` loop — the structural parity is by construction)."""
    return _build_registry(
        n,
        seed,
        local,
        primary=_OOD_NOUNS,
        secondary=_OOD_VERBS,
        keys=_OOD_KEYS,
        name_fmt="{b}{a}Svc{i:04d}",
        description_fmt="{b}s the {a} aggregate for composition",
        interleaved_draws=True,
    )


def intent_for(records: list[ServiceRecord], rng: random.Random, n_services: int = 3) -> str:
    """An intent whose tokens mention a few concrete services' domains."""
    picks = rng.sample(records, min(n_services, len(records)))
    words = []
    for r in picks:
        words.extend(r.tags)
    return "please " + " then ".join(f"{w}" for w in dict.fromkeys(words))
