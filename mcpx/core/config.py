"""Typed configuration, loadable from defaults, a JSON file, or env vars.

The reference configures itself with three ``os.getenv`` calls *at import
time* (reference ``control_plane.py:17-19``) and eagerly connects to Postgres
in a constructor (``control_plane.py:48``, bug B8). Here configuration is a
plain dataclass tree with no import-time side effects, validated explicitly by
``MCPXConfig.validate()`` at startup; backends are constructed from it by the
application factory, never at import.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional

from mcpx.core.errors import ConfigError


@dataclass
class ServerConfig:
    host: str = "0.0.0.0"
    port: int = 8000
    # Max concurrent in-flight /plan_and_execute requests before 429.
    max_concurrency: int = 1024
    request_timeout_s: float = 120.0
    # Where POST /profile/start writes jax.profiler traces (TensorBoard /
    # Perfetto format) when the request doesn't name a directory.
    profile_dir: str = "/tmp/mcpx-profile"


@dataclass
class RegistryConfig:
    # "memory" | "file" | "redis"
    backend: str = "memory"
    file_path: str = ""
    redis_url: str = ""
    # Key prefix kept for reference compatibility (control_plane.py:20).
    prefix: str = "mcp:service:"


@dataclass
class ModelConfig:
    # Named Gemma-architecture size: "test" | "2b" | "7b" (models/gemma/config.py)
    size: str = "test"
    checkpoint_path: str = ""
    dtype: str = "bfloat16"
    vocab: str = "byte"  # in-tree byte-level tokenizer (no external files)
    max_seq_len: int = 2048
    # Weight-only serving quantization (models/gemma/quant.py):
    # "none" | "int8". int8 halves HBM bytes-at-rest and the decode
    # weight-streaming bill; puts the 7B geometry on a single 16 GB v5e.
    quantize: str = "none"


@dataclass
class SpeculativeConfig:
    """Grammar-aware speculative decoding in the heterogeneous slab
    (mcpx/engine/speculative.py, docs/engine.md): a single-model recurrent
    drafter proposes ``k`` tokens per row per step, pre-filtered through the
    row's stacked grammar DFA so constrained rows never draft an
    inadmissible token, then the whole slab verifies in ONE batched
    ``[rows, k+1]`` forward (fixed window — jit shapes stay static and the
    compile count is independent of per-row acceptance). Off by default:
    with ``enabled=false`` the decode path is byte-identical to the legacy
    heterogeneous segment (parity-tested), matching the repo's
    config-gated-subsystem convention. Takes effect only under
    ``engine.hetero_batch`` (the grammar pre-filter indexes the stacked
    per-row DFA tables); enabled without it, the engine warns and serves
    the legacy path."""

    enabled: bool = False
    # Draft tokens proposed per verify forward (the window is k+1 wide:
    # current token + k drafts). Clamped at runtime when page capacity
    # cannot spare the window's garbage-write slack (logged once). The
    # default comes from a CPU reading at the test model's size (the spec
    # segment cost ~1.4x a legacy step at k=2, ~1.9x at k=4, ~3.6x at k=8,
    # against a mean accepted prefix that saturates well before 8). It has
    # never run on the chip: no cell enables it and every non-default block
    # refuses it, so what it nets there is not measured (ROADMAP D4).
    k: int = 4
    # Draft source for positions the DFA does not force:
    #   "recurrent" — the recurrent drafter head (embedding-EWMA hidden
    #                 state scored against the model's tied unembedding;
    #                 Recurrent Drafter, PAPERS.md) proposes for
    #                 constrained branch points AND free rows (unmasked).
    #   "grammar"   — DFA-forced successors only: constrained rows draft
    #                 exactly the single-successor chains (generalised
    #                 fast-forward through the verify window); free rows
    #                 never draft. Zero drafter compute; the ablation
    #                 baseline for the recurrent head.
    draft: str = "recurrent"


@dataclass
class KVTierConfig:
    """Tiered KV cache (mcpx/engine/spill.py + cache_governor.py,
    docs/engine.md "Tiered KV & cache governance"): a host-RAM spill tier
    under the radix prefix cache, per-tenant cache governance, and a
    warm-restart snapshot. Off by default: with ``enabled=false`` (and no
    ``snapshot_path``) eviction is exactly the pre-tier destructive path —
    byte-identical pass-through, no tier or governor state touched."""

    enabled: bool = False
    # Pinned-host byte budget for spilled KV runs. On overrun the tier
    # first reclaims LRU spilled leaves, then degrades to destructive
    # eviction (counted, never silent).
    host_mb: float = 256.0
    # Device<->host copy-bandwidth budget per admission cycle, in TOKENS
    # (both directions share it). Spills past the budget degrade to
    # destructive eviction; readmits past it shrink the match (the request
    # prefills instead) — spill can never stall admission. 0 = unlimited.
    copy_tokens_per_cycle: int = 4096
    # Per-tenant weighted-fair cache quotas (the scheduler's WFQ idea at
    # the cache layer): an over-quota tenant's inserts evict/spill its OWN
    # coldest subtrees first, and cross-tenant eviction prefers tenants
    # over their fair share (deficit-weighted LRU). Weights default to 1.0
    # per observed tenant; name->weight overrides here.
    governor: bool = True
    tenant_weights: dict = field(default_factory=dict)
    # Warm-restart snapshot: on clean ``aclose()`` the resident prefix
    # heads (token ids + KV bytes, host-budget-bounded) and governor state
    # serialize here (versioned manifest + sidecar .npz); the next engine
    # restores them as host-tier residents, re-admitted by the standard
    # async page copy on first match. Corrupt/stale snapshots are
    # detected, logged and skipped — never fatal. "" disables. Requires
    # ``enabled`` (restored heads live in the host tier).
    snapshot_path: str = ""
    # Seeded fault profile for the spill tier (JSON file or inline JSON):
    # {"seed": 7, "host_alloc_fail_p": 0.1, "copy_delay_p": 0.2,
    #  "copy_delay_s": 0.05, "snapshot_corrupt": false} — exercised by
    # the KV-tier tests; "" disables.
    chaos_profile: str = ""


@dataclass
class EngineConfig:
    # Mesh axis sizes. 0 = auto: cover every visible device (TP over the
    # largest head-dividing factor, keeping a data axis >= 2 when possible —
    # 2x4 on a v5e-8 with 8-head Gemma-2B). Explicit values are clamped to
    # the device count.
    data_axis: int = 0
    model_axis: int = 0
    kv_page_size: int = 16  # tokens per KV page
    max_pages_per_seq: int = 128
    max_batch_size: int = 32
    max_prefill_tokens: int = 4096
    # Model forwards per decode TICK: the unit a decode segment's length
    # is counted in (a segment is a whole number of ticks, at least one;
    # the speculative segment is always exactly one). Between segments the
    # worker admits newly-arrived requests into free slab rows (continuous
    # batching) and answers finished ones, so a shorter segment means a
    # lower p50 under load and a longer one fewer host round-trips per
    # token. With speculation each forward covers up to speculate_k
    # tokens.
    decode_steps_per_tick: int = 4
    # Fused multi-step decode dispatch (ISSUE 15): how many decode ticks
    # may fold into ONE jitted dispatch. decode_steps_per_tick *
    # steps_per_dispatch model forwards is the CEILING of a segment's
    # length (one executable; per-row done masks are DATA, so finished
    # rows idle safely and the loop still exits early when the whole slab
    # drains); host-side bookkeeping (harvest, admission, gauge publish)
    # runs once per segment. The length SERVED is chosen at each dispatch
    # by the pacer (ISSUE 31, engine/pacing.py::segment_forwards) and
    # passed to that one executable as an operand: the fewest whole ticks
    # whose device time covers the worker's own work for a segment three
    # times over, this ceiling until the
    # worker has measured a forward's period and its own costs. Why not
    # always the ceiling: a plan is charged whole segments (one waiting
    # behind the segment in flight, then as many as its tokens need, its
    # reply leaving only at a segment's end), and on the chip the worker
    # needs 22-67 ms of host work a segment where 16 forwards last
    # 138-305 ms (ledger, PR 30: engine.host_ms_per_forward 1.91 / 1.40 /
    # 4.19 ms, step.forward_period_ms 8.65 / 16.06 / 19.07 ms), so the
    # full window was four times longer than the host needs.
    # 1 = per-tick cadence always. Tradeoff of a high ceiling: while no
    # estimate exists a new arrival waits up to one full window for
    # admission, and retirement lags by pipeline_depth-1 segments
    # (docs/engine.md "Ragged kernel & fused decode
    # dispatch"). The speculative segment is NOT multiplied: its
    # iterations are unrolled without early exit (pool-aliasing
    # constraint) and each already amortises dispatch over a [rows, K+1]
    # window, so a longer unroll would pay full verify compute on the
    # drain tail for nothing.
    steps_per_dispatch: int = 4
    # Decode segments kept in flight before the worker blocks on the oldest
    # one's done-flags. 1 = fetch the segment just dispatched (no overlap).
    # 2 = fetch the PREVIOUS segment's flags while the current one computes,
    # hiding the blocking host<-device fetch. Retirement lags admission by
    # depth-1 segments.
    pipeline_depth: int = 2
    # Heterogeneous continuous batching: temperature, the constrained flag
    # and the grammar become PER-ROW state (device vectors + stacked DFA
    # tables indexed by a per-row dfa_id), so any pending request admits
    # into any free row in strict queue order — no slab-wide compatibility
    # gate, no drain-to-switch. Off (default) keeps the homogeneous slab:
    # one (constrained, temperature, grammar) triple per slab, incompatible
    # requests wait for a drain softened by fairness_timeout_s. Both modes'
    # executables coexist, so the flag may be flipped on a LIVE engine: the
    # slab latches its admission mode whenever it refills from empty, so a
    # mid-occupancy flip simply pauses admission until the old-mode rows
    # drain (rows admitted under one mode carry that mode's page-slack
    # geometry and always decode under it).
    hetero_batch: bool = False
    # Stacked-DFA slots under hetero_batch: how many DISTINCT grammars can
    # be resident in the slab at once (slot 0 is the trivial all-accept DFA
    # for unconstrained rows, so hetero_grammar_slots-1 constrained
    # grammars fit). The slot count is a STATIC shape — executables never
    # recompile as grammars come and go; a request whose grammar finds no
    # free slot waits for one (rare: the planner shares grammars per
    # registry version).
    hetero_grammar_slots: int = 4
    # Once the head of the pending line has waited this long behind an
    # incompatible slab (different grammar/temperature), stop admitting new
    # rows so the slab drains and the head can run. Under hetero_batch the
    # slab never drains to switch, but the same timeout bounds the one
    # config-shaped wait left: a request whose grammar finds no free
    # stacked slot stops admissions behind it once over-age, so resident
    # rows retire and free a slot instead of later arrivals starving it.
    fairness_timeout_s: float = 0.5
    # Admission hysteresis: while the slab is busy, hold off prefilling a
    # new cohort until at least this many rows are free (0 = auto:
    # max_batch_size/4). Staggered retirements otherwise trigger a storm of
    # small-cohort prefills, each costing as much wall time as several
    # decode segments — prefill is compute-bound, decode is weight-bound.
    admit_min_free: int = 0
    # ...but never hold a pending request longer than this waiting for a
    # fuller cohort (an idle slab always admits immediately).
    admit_max_wait_s: float = 0.15
    max_decode_len: int = 512
    # Sampling defaults: temperature matches the reference planner call,
    # control_plane.py:72.
    temperature: float = 0.2
    top_k: int = 0  # 0 = full softmax sampling / greedy if temperature==0
    use_pallas: bool = True
    interpret: bool = False  # run Pallas kernels in interpret mode (CPU CI)
    # Grammar fast-forward speculation: chunk width of the multi-token decode
    # forward (1 sampled token + up to speculate_k-1 DFA-forced tokens per
    # model call). Forced tokens (states with exactly one legal byte — JSON
    # structure like '{"steps":[') need no sampling, only KV population, so
    # this is exact, not probabilistic. <=1 disables (single-token loop).
    speculate_k: int = 8
    # Draft speculation for the chunk positions grammar fast-forward can't
    # force (multi-successor trie states — name branch points, key lists —
    # and free strings on fallback grammars): "prompt" proposes the
    # continuation after the last (prev, cur) bigram match in the row's own
    # prompt (plans echo shortlist names and schema keys verbatim), verified
    # per-position against masked-greedy argmax over the grammar's compact
    # columns — exact under greedy decode (temperature 0), auto-disabled
    # otherwise (probabilistic acceptance is not implemented). "off" keeps
    # forced-token fast-forward only. VERDICT r4 next #6.
    draft_mode: str = "prompt"
    # Grammar-aware speculative decoding in the HETEROGENEOUS slab: a
    # recurrent drafter proposes k tokens per row, pre-filtered through the
    # per-row stacked grammar DFA, verified in one fixed-shape [rows, k+1]
    # forward with per-row greedy/stochastic accept rules. Off = the legacy
    # hetero segment, byte-identical (see SpeculativeConfig).
    speculative: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    # Row buckets an admission cohort is padded up to. Few buckets = few XLA
    # compiles (each (B, T) pair is one prefill executable a route, each B one
    # admit and one admit-merge). Padding rows are nearly free in a DECODE
    # forward, which is weight-load-bound on a TPU; a PREFILL past ~240 tokens
    # a weight pass is compute-bound and costs by the slot. Empty = the
    # engine's own table (``engine.cohort_buckets``): {1, 8, max_batch_size}
    # and its halves down to 8, and down to 4 where short whole prompts land
    # (the 128 prefill bucket, no matched prefix) and where short suffixes do
    # (the 64 bucket behind a matched prefix). A list given here holds at
    # every prefill bucket and on both routes.
    batch_buckets: list = field(default_factory=list)
    # Execute one batch per (B, T) bucket at startup so no compile lands in
    # the serving path. Off by default: tests construct many engines.
    warmup_compile: bool = False
    # DFA tables are padded to a multiple of this many states before entering
    # the jitted decode as arguments; one pad bucket = one compiled decode
    # executable shared by every grammar that fits it (the warmup-compiled
    # shape covers registry tries up to ~2k services on the byte vocab).
    # Auto-shrunk for huge subword vocabs where dense padding costs HBM.
    grammar_state_budget: int = 16384
    # Largest prompt bucket the startup warmup compiles for.
    warmup_max_len: int = 1024
    # Radix-tree prefix KV cache (engine/prefix_cache.py, docs/engine.md
    # "Prefix KV reuse"): every admitted prompt is matched against a radix
    # tree of resident KV page runs, the matched head is pinned and only
    # the unmatched suffix prefilled (per-row start offsets — one
    # executable), and the page-aligned prompt is inserted back so the
    # next sharer (same planner header, same shortlist block, a warm
    # replan extending the original prompt) re-prefills none of it.
    # Admission is prefix-locality-aware: cohort admits group by shared-
    # prefix depth, EDF/age-guarded (scheduler/locality.py). Off =
    # byte-identical pre-radix pass-through (no matching, no insertion,
    # no reorder).
    prefix_cache: bool = True
    # Max radix-tree nodes resident (each node = one cached KV run).
    # Eviction drops refcount-0 LRU leaf subtrees over this cap, over the
    # token budget (auto: half the page pool), or under allocation
    # pressure; 0 disables caching-by-eviction (everything unpinned is
    # reclaimed immediately).
    prefix_cache_entries: int = 512
    # Tiered KV cache: host-RAM spill under the radix prefix cache,
    # per-tenant governance, warm-restart snapshot (see KVTierConfig).
    kv_tier: KVTierConfig = field(default_factory=KVTierConfig)


@dataclass
class RetrievalConfig:
    enabled: bool = True
    embed_dim: int = 256
    top_k: int = 8
    # Where shortlist scoring runs: "host" (numpy), "device" (jit dot+top_k),
    # or "auto" — host below `device_threshold` rows. At small N the dot
    # product is microseconds on CPU, while a per-request device dispatch
    # must queue BEHIND multi-second decode batches on a busy chip, which
    # both inflates /plan latency and fragments engine batching.
    compute: str = "auto"
    device_threshold: int = 65536
    # "residual" (default): coverage-greedy shortlist — greedily pick
    # services covering still-unmatched intent words, fill the rest by
    # similarity; fixes the multi-clause coverage ceiling (r4: 0.74 oracle
    # coverage with plain top-k). "topk": plain embedding similarity.
    shortlist_mode: str = "residual"
    # Refresh the HBM table when the registry version changes.
    auto_refresh: bool = True
    # Optional .npz snapshot to load at startup (rebuildable from registry).
    snapshot_path: str = ""


@dataclass
class FlightConfig:
    """Flight recorder & anomaly observatory (mcpx/telemetry/flight.py,
    docs/observability.md "Flight recorder & anomaly bundles"): an
    always-on bounded ring of periodic signal snapshots (queue depth,
    accept rates, prefix/tier scoreboards, compile counters, breaker
    states, shed rates, streaming latency quantiles) with SPC-style
    EWMA+MAD anomaly detectors that, on trip, capture a versioned
    diagnostic bundle (tail-sampled traces, /costs snapshot, the flight
    window around the trigger, breaker/governor state, recent log tail)
    written atomically OFF the event loop and served via
    ``GET /debug/anomalies`` + ``mcpx debug bundle``. Off by default:
    with ``enabled=false`` no sampling task runs, no detector state
    exists, and the serving path is byte-identical (parity-tested)."""

    enabled: bool = False
    # Snapshot period of the recorder's sampling loop.
    interval_s: float = 1.0
    # Snapshots retained in the in-memory flight ring (oldest evicted):
    # 512 x 1 s ~ 8.5 minutes of history around any trigger.
    ring_size: int = 512
    # Decode-loop host profiler (engine worker thread): per-iteration
    # phase timers — admit / locality-sort / prefix-match / dispatch /
    # poll / harvest / spill-copy drain / host-bookkeeping / idle —
    # aggregated into streaming histograms and surfaced in
    # ``queue_stats()["worker_profile"]``, the per-segment attributes of
    # engine.segment spans (which the chip benchmark reads) and the
    # flight ring. ``tracing.enabled`` brings the profiler along; with
    # both off the worker loop takes no clock reads for it (pass-through).
    profile_worker: bool = False
    # Run the SPC detectors over the sampled series (enabled only).
    detectors: bool = True
    # EWMA smoothing for each signal's running mean and mean-absolute-
    # deviation (the MAD-style band scale).
    ewma_alpha: float = 0.3
    # Band half-width in deviations: a sample outside mean +/- k*MAD (in
    # the detector's alarm direction) counts as out-of-band.
    band_k: float = 5.0
    # Samples a detector must see before it arms (baseline warmup).
    min_samples: int = 10
    # Consecutive out-of-band samples required to trip, and consecutive
    # in-band samples required to re-arm after an excursion ends — one
    # noisy sample neither trips nor resets an active anomaly.
    hysteresis: int = 3
    # Minimum seconds between bundle captures per detector; trips inside
    # the window are counted (suppressed_trips) but capture no bundle.
    cooldown_s: float = 30.0
    # Where diagnostic bundles are written (atomic tmp+rename, off-loop).
    bundle_dir: str = "/tmp/mcpx-bundles"
    # Newest bundles kept on disk; older ones pruned at each write.
    max_bundles: int = 8
    # Log lines retained in the recorder's in-memory tail (bundled).
    log_tail: int = 200


@dataclass
class LedgerConfig:
    """Per-request cost ledger & per-tenant usage attribution
    (mcpx/telemetry/ledger.py, docs/observability.md "Cost ledger & SLO
    budgets"): every admitted request accumulates an itemized bill
    (queue waits, prefill/decode walls and tokens, apportioned FLOPs/HBM
    bytes, KV page·seconds, prefix tokens saved, tool attempts), attached
    to the root span and rolled up per tenant at GET /usage. Off by
    default: with ``enabled=false`` no bill exists anywhere on the
    serving path — token outputs, queue_stats and the metrics exposition
    (modulo the registered-but-empty mcpx_ledger_* families) are
    byte-identical (parity-tested)."""

    enabled: bool = False
    # Distinct tenants tracked before new names fold into "other" — the
    # cache governor's fold-at-64 discipline; bounds both the usage map
    # and the mcpx_ledger_* label space.
    max_tenants: int = 64
    # Finalized bills retained in the in-memory ring served by GET /usage
    # (oldest evicted; 0 disables the ring, aggregates still accumulate).
    recent: int = 256


@dataclass
class ProvenanceConfig:
    """Decision-provenance spine (mcpx/telemetry/provenance.py,
    docs/observability.md "Decision provenance & /explain"): a typed
    ``DecisionRecord`` — layer, choice, alternatives considered,
    per-factor score contributions, triggering signal values — emitted at
    every consequential choice point (scheduler admission + ladder tier,
    plan origin, cluster routing winner, breaker/hedge/budget/replan
    resilience events, prefix-cache & tier events) and attached to the
    span tree under the PR 4 tail-sampling rules, rendered at
    ``GET /explain/{trace_id}`` + ``mcpx explain`` as structured JSON and
    a human-readable narrative. Off by default: with ``enabled=false`` no
    recorder is activated anywhere on the serving path — token outputs,
    queue_stats and span trees are byte-identical (parity-tested). The
    cluster routing-decision ring and failover journal are always-on
    accounting (they replace the old single ``last_decision`` dict); only
    the per-request decision spans + mcpx_provenance_records_total are
    gated here."""

    enabled: bool = False
    # Decision records attached per trace before further emits are
    # dropped (counted in the root span's provenance_dropped attr) — a
    # replan storm must not balloon a retained trace without bound.
    max_records_per_trace: int = 64
    # Recent routing decisions retained in the cluster ring served by
    # GET /cluster (each entry carries the requesting trace_id).
    route_ring: int = 128
    # Routing/failover lifecycle events (routed / affinity_hit / resteer /
    # kill / rejoin / drain) retained in the pool's bounded journal.
    journal_size: int = 512
    # Per-replica signal-ring length (scoreboard snapshots behind the
    # pool, one ring per replica, fed by the scoreboard refresh task).
    replica_ring: int = 128


@dataclass
class SLOConfig:
    """SLO error-budget engine (mcpx/telemetry/slo.py): declarative
    objectives over the serving path, multi-window multi-burn-rate
    tracking, budget state per tenant + global at GET /slo. Off by
    default (no tracker, no per-request observe)."""

    enabled: bool = False
    # Objectives as a list of {"name", "kind", "target"[, "threshold_ms"]}
    # dicts; kind in latency|availability|plan_quality. Empty = the
    # defaults (slo.DEFAULT_OBJECTIVES): p99<1s @ 99%, availability
    # 99.9%, primary-tier plan share 90%.
    objectives: list = field(default_factory=list)
    # Burn windows, seconds, ascending: the first two are the FAST pair
    # (multi-window AND for the fast-burn signal), the last is the budget
    # period. Defaults: 5m / 1h / 6h / 3d.
    windows_s: list = field(
        default_factory=lambda: [300.0, 3600.0, 21600.0, 259200.0]
    )
    # Event-count bucket granularity; windows are sums of bucket tails.
    bucket_s: float = 60.0
    # Fast-burn page threshold: burn >= this in BOTH fast windows trips
    # the flight recorder's slo_burn detector and (when
    # scheduler.burn_aware) engages the degradation ladder. 14.4 spends a
    # 3d budget in ~5h — the SRE-workbook page number.
    fast_burn_threshold: float = 14.4
    # Distinct tenants tracked before folding into "other".
    max_tenants: int = 64


@dataclass
class TelemetryConfig:
    enabled: bool = True
    # EWMA smoothing for per-service latency/error-rate.
    ewma_alpha: float = 0.2
    # Redis mirror (reference README.md:43-44 "Prometheus -> Redis"): when a
    # URL is set, each replica exports its local stats snapshot and imports
    # every peer's, so replicas plan with shared live telemetry.
    redis_url: str = ""
    mirror_interval_s: float = 2.0
    # Per-executable XLA cost accounting + retrace sentinel
    # (mcpx/telemetry/costs.py, docs/observability.md): every jitted engine
    # executable's calls are signature-tracked (dispatch itself stays the
    # untouched jit fast path); compiles increment
    # mcpx_engine_compiles_total{executable} and log the signature delta,
    # cost_analysis() is harvested lazily at read time (GET /costs, traced
    # spans, warmup tail), engine spans carry achieved-FLOP/s rooflines.
    # Off = the jitted callables are served unwrapped (byte-identical
    # pass-through; no sentinel, no /costs executable data).
    cost_accounting: bool = True
    # Flight recorder + anomaly detectors + worker-loop profiler
    # (mcpx/telemetry/flight.py; see FlightConfig).
    flight: FlightConfig = field(default_factory=FlightConfig)
    # Per-request cost ledger + per-tenant usage attribution
    # (mcpx/telemetry/ledger.py; see LedgerConfig).
    ledger: LedgerConfig = field(default_factory=LedgerConfig)
    # Decision-provenance spine: per-request "why" records + GET /explain
    # (mcpx/telemetry/provenance.py; see ProvenanceConfig).
    provenance: ProvenanceConfig = field(default_factory=ProvenanceConfig)
    # Replan when a node's observed error-rate breaches this threshold.
    replan_error_rate: float = 0.5
    # or when latency exceeds this multiple of the registry's cost profile.
    replan_latency_factor: float = 4.0
    max_replans: int = 2


@dataclass
class OrchestratorConfig:
    default_retries: int = 1
    default_timeout_s: float = 5.0  # reference per-node timeout, control_plane.py:109
    retry_backoff_s: float = 0.05
    retry_backoff_multiplier: float = 2.0
    max_node_concurrency: int = 256


@dataclass
class PlannerConfig:
    # "llm" | "heuristic" | "mock"
    kind: str = "heuristic"
    max_plan_retries: int = 2
    shortlist_top_k: int = 8
    max_prompt_tokens: int = 1536
    plan_cache_size: int = 4096
    # Optional second cache tier shared across replicas and restarts
    # (server/plan_cache.py): "" disables. Keys embed the registry version,
    # so registry changes invalidate implicitly.
    plan_cache_redis_url: str = ""
    plan_cache_redis_ttl_s: float = 600.0
    explain: bool = True
    # Trie-constrain the grammar's service-name positions (VERDICT r1 #2):
    #   "registry"  — one grammar over ALL registry names per registry
    #                 version; every concurrent plan shares tables + decode
    #                 executable (best batching; the default).
    #   "shortlist" — per-(version, shortlist) grammar; tightest constraint
    #                 but distinct shortlists split engine batches.
    #   "off"       — shape-only grammar (names free-form; round-1 behavior).
    constrain_names: str = "registry"
    # Trie-constrain the "in" key positions to the union of the registry's
    # input/output schema keys ("registry") or leave them free strings
    # ("off"). Constrained is the default: plans should only reference keys
    # some service actually produces or consumes, it is what keeps the
    # grammar compact on big subword vocabs, and key tries make most key
    # characters FORCED — roughly doubling grammar fast-forward speculation
    # (free-string keys sample every character). Set "off" if callers pass
    # payload keys outside any schema.
    constrain_input_keys: str = "registry"
    # Typed-dataflow grammar for the "shortlist" tier: each step's "in"
    # list accepts only the named service's own input keys and its "next"
    # list only services one of its outputs feeds — incoherent edges stop
    # being REPRESENTABLE at decode time (grammar.py typed construction).
    # Only applies when constrain_names="shortlist" (per-service step
    # bodies multiply grammar states by the candidate count; a
    # registry-wide typed grammar would trip the table budget).
    constrain_dataflow: bool = True
    # Drop LLM-emitted edges a->b where no output key of a's service is an
    # input key of b's service (per the registry's schemas) — after the
    # planner has rewired the keys that DO overlap to read a's result
    # (LLMPlanner._normalize_dataflow). A pruned edge is not a no-op: the
    # executor would have made b wait for a and skip b on a's failure. The
    # default drops it anyway because the planner's teacher distribution
    # defines edges as dataflow, so a no-data edge from the model is an
    # imitation error that serializes — and failure-couples — services that
    # share nothing. Set False if your LLM plans intentionally use edges as
    # control-flow-only ordering. Applies only to LLM-authored plans; graphs
    # submitted to /execute are never modified.
    prune_dataflow_free_edges: bool = True


@dataclass
class SchedulerConfig:
    """SLO-aware admission control & scheduling for /plan (mcpx/scheduler/).

    Off by default: with ``enabled=false`` the server's /plan path is
    byte-identical to the pre-scheduler pass-through (no extra headers, no
    ``planner`` response field, no scheduling state touched)."""

    enabled: bool = False
    # The per-request /plan latency objective the ladder defends (the
    # BASELINE target is p50 < 150 ms at 100 plans/s).
    slo_ms: float = 150.0
    # Deadline assumed for requests that send no deadline header; <= 0
    # means "no deadline" (such requests are never deadline-shed).
    default_deadline_ms: float = 2000.0
    # Concurrent /plan executions dispatched past the fair queue. Sized to
    # the engine's continuous-batching appetite, not aiohttp's (that is
    # server.max_concurrency, which still applies upstream).
    max_parallel: int = 64
    # Queue cap: beyond this, new arrivals shed immediately (429).
    max_queue_depth: int = 512
    # Token-bucket rate limit in requests/s over all tenants; 0 disables.
    rate_limit: float = 0.0
    burst: int = 32
    # Headers carrying per-request scheduling identity. Tenant defaults to
    # "default" when absent — single-tenant deployments need no headers.
    tenant_header: str = "X-MCPX-Tenant"
    deadline_header: str = "X-MCPX-Deadline-Ms"
    priority_header: str = "X-MCPX-Priority"
    # EWMA smoothing for queue-wait / service-time estimators.
    ewma_alpha: float = 0.2
    # Degradation ladder hysteresis: engage the shortlist planner when the
    # queue-wait EWMA exceeds slo_ms * degrade_threshold; restore LLM
    # serving when it falls below slo_ms * recover_threshold AND the
    # ladder has held at least degrade_min_hold_s.
    degrade_threshold: float = 0.5
    recover_threshold: float = 0.25
    degrade_min_hold_s: float = 2.0
    # Floor for the 429 Retry-After estimate.
    shed_retry_after_s: float = 1.0
    # Burn-aware degradation (requires slo.enabled): the ladder also
    # consults the SLO error-budget engine — while the global fast-burn
    # signal is at/over slo.fast_burn_threshold, grants route to the
    # degraded tier even before the queue-wait EWMA crosses its own
    # threshold, so overload sheds burn-aware instead of blind. Off by
    # default: the ladder is exactly the pre-SLO queue-wait controller.
    burn_aware: bool = False


@dataclass
class ResilienceConfig:
    """Fault-domain resilience (mcpx/resilience/): per-endpoint circuit
    breakers, request deadline-budget propagation, and hedged attempts —
    consulted by the executor's attempt chain. Off by default: with
    ``enabled=false`` the executor's attempt chain is byte-identical to the
    pre-resilience pass-through (no breaker consults, no budget, no hedges;
    the /execute deadline header is not even read)."""

    enabled: bool = False
    # --- circuit breakers (one state machine per endpoint URL) -----------
    # Rolling outcome window per endpoint; the error-rate trip reads it.
    breaker_window: int = 20
    # Error-rate trip: >= this failure share over the window trips the
    # breaker open — once at least breaker_min_samples outcomes are in.
    breaker_error_threshold: float = 0.5
    breaker_min_samples: int = 5
    # Hard trip regardless of the window: this many consecutive failures.
    breaker_consecutive_failures: int = 5
    # How long an open breaker refuses traffic before probing (half-open).
    breaker_open_s: float = 5.0
    # Half-open: each arrival probes the endpoint with this probability;
    # the rest keep falling back, so one recovering endpoint never takes a
    # thundering herd of probes at once.
    breaker_half_open_probe_p: float = 0.3
    # --- deadline-budget propagation (/execute) --------------------------
    # Header carrying the caller's deadline in ms (same name the scheduler
    # uses for /plan). Parsed only while resilience is enabled.
    deadline_header: str = "X-MCPX-Deadline-Ms"
    # Budget assumed when /execute sends no header; <= 0 = no budget
    # (attempts run on per-node timeouts alone, pre-resilience behavior).
    default_execute_deadline_ms: float = 0.0
    # An attempt is not worth dispatching with less than this left — the
    # budget is declared exhausted instead (the node fails with a distinct
    # deadline-budget error rather than overshooting the SLO).
    min_attempt_s: float = 0.005
    # --- hedged attempts -------------------------------------------------
    hedge_enabled: bool = True
    # Launch the speculative duplicate after hedge_latency_factor x the
    # service's EWMA latency (TelemetryStore), floored by hedge_min_delay_s.
    # No telemetry yet (fewer than hedge_min_calls observations) = no hedge:
    # cold services never double their own traffic on a guess.
    hedge_latency_factor: float = 2.0
    hedge_min_delay_s: float = 0.02
    hedge_min_calls: int = 3
    # Hedge budget: speculative duplicates may never exceed this fraction
    # of primary attempts — hedging is a tail-latency tool, not a traffic
    # multiplier.
    hedge_max_fraction: float = 0.1
    # --- chaos injection -------------------------------------------------
    # JSON fault profile (docs/resilience.md schema); when set the factory
    # wraps the transport in a seeded ChaosTransport (`mcpx serve --chaos`).
    # Independent of `enabled`, so the SAME fault profile can be served
    # with resilience on and off.
    chaos_profile: str = ""


@dataclass
class TracingConfig:
    """End-to-end request tracing (mcpx/telemetry/tracing.py): the span
    spine every request carries from HTTP ingress to response. Disabled is
    a TRUE no-op — no root span, no contextvar, no engine-side span work on
    the decode hot path (GenerateRequest.span stays None)."""

    enabled: bool = True
    # Head sampling: probability a completed trace is retained in the ring.
    # Error and SLO-breach traces are retained regardless (tail sampling).
    sample_rate: float = 1.0
    # Completed traces kept in memory (GET /traces; oldest evicted first).
    ring_size: int = 256
    # Tail sampling: always keep traces whose request errored…
    keep_errors: bool = True
    # …and traces slower end-to-end than this many ms (0 disables).
    slo_breach_ms: float = 0.0
    # Attach exemplar trace ids to latency histograms (rendered only in the
    # OpenMetrics exposition; plain Prometheus text ignores them).
    exemplars: bool = True


@dataclass
class ClusterConfig:
    """Multi-replica engine pool (mcpx/cluster/): N ``InferenceEngine``
    replicas behind one engine-shaped facade, with a scored routing
    pipeline (queue/ETA baseline, prefix-locality affinity, cost/burn-aware
    placement) and replica lifecycle (spawn/warm/drain/kill/rejoin). Off by
    default: with ``enabled=false`` the factory builds the single bare
    engine exactly as before — byte-identical pass-through."""

    enabled: bool = False
    # Engine replicas the pool spawns at startup.
    replicas: int = 2
    # --- routing pipeline ------------------------------------------------
    # Prefix-locality affinity: rendezvous hash over the radix prefix of
    # the rendered prompt ids, so repeat traffic lands on the replica whose
    # tree already holds its KV (grammar-slot residency breaks ties).
    affinity: bool = True
    # Leading prompt tokens forming the affinity key, truncated down to a
    # KV-page boundary so the key is stable across small suffix edits.
    affinity_prefix_tokens: int = 64
    # Weight of the affinity bonus against the queue/ETA baseline score.
    affinity_weight: float = 1.0
    # Load-imbalance escape hatch: the affinity bonus is dropped once the
    # preferred replica's queue depth exceeds ratio x (min depth + 1).
    imbalance_ratio: float = 4.0
    # Cost/burn-aware placement: steer fast-burning tenants (SLO budget
    # burn + ledger spend share) toward the pool's degraded tail so
    # healthy replicas keep serving budget-healthy traffic.
    burn_aware: bool = False
    # --- scoreboard ------------------------------------------------------
    # Off-request-path health refresh cadence (queue depth/ETA, service
    # EWMA, error rate) feeding routing, GET /cluster and mcpx_cluster_*.
    scoreboard_interval_s: float = 0.5
    # Rolling per-replica outcome window behind the breaker-adjacent
    # error rate on the scoreboard.
    error_window: int = 32
    # --- lifecycle -------------------------------------------------------
    # Drain: stop routing, wait up to this long for in-flight rows, close.
    drain_timeout_s: float = 10.0
    # Warm-up path: per-replica warm-restart KV snapshots land at
    # <dir>/replica-<i>.json; a rejoining replica restores its manifest
    # before taking traffic. Requires engine.kv_tier.enabled.
    warm_snapshot_dir: str = ""
    # --- registry sharding ----------------------------------------------
    # Partition the retrieval embedding table row-wise with shard-local
    # top-k merged host-side (100k-service registries stop fitting one
    # replica's HBM comfortably).
    shard_registry: bool = False
    # Shard count; 0 = one shard per replica.
    registry_shards: int = 0


@dataclass
class MCPXConfig:
    server: ServerConfig = field(default_factory=ServerConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    registry: RegistryConfig = field(default_factory=RegistryConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    retrieval: RetrievalConfig = field(default_factory=RetrievalConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    orchestrator: OrchestratorConfig = field(default_factory=OrchestratorConfig)
    planner: PlannerConfig = field(default_factory=PlannerConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)

    # ------------------------------------------------------------------ load
    @classmethod
    def from_dict(cls, obj: dict[str, Any]) -> "MCPXConfig":
        cfg = cls()
        for section_name, section_obj in obj.items():
            if not hasattr(cfg, section_name):
                raise ConfigError(f"unknown config section '{section_name}'")
            section = getattr(cfg, section_name)
            if not isinstance(section_obj, dict):
                raise ConfigError(f"config section '{section_name}' must be an object")
            fields_by_name = {f.name: f for f in dataclasses.fields(section)}
            for k, v in section_obj.items():
                if k not in fields_by_name:
                    raise ConfigError(f"unknown key '{section_name}.{k}'")
                sub = getattr(section, k)
                if dataclasses.is_dataclass(sub):
                    if not isinstance(v, dict):
                        # e.g. `"speculative": true` — the enable flag lives
                        # INSIDE the nested object; a raw scalar here would
                        # otherwise survive until validate() blows up with
                        # an AttributeError instead of the ConfigError the
                        # rest of the loader contracts.
                        raise ConfigError(
                            f"config key '{section_name}.{k}' must be an "
                            f"object (e.g. {{\"enabled\": true}})"
                        )
                    # Nested subsystem config (engine.speculative): one more
                    # level of the same key-checked, string-coerced loading.
                    sub_fields = {f.name: f for f in dataclasses.fields(sub)}
                    for sk, sv in v.items():
                        if sk not in sub_fields:
                            raise ConfigError(
                                f"unknown key '{section_name}.{k}.{sk}'"
                            )
                        if isinstance(sv, str):
                            try:
                                sv = _coerce(sv, sub_fields[sk].type)
                            except (TypeError, ValueError) as e:
                                raise ConfigError(
                                    f"bad value for {section_name}.{k}.{sk}={sv!r}: {e}"
                                ) from e
                        setattr(sub, sk, sv)
                    continue
                if isinstance(v, str):
                    try:
                        v = _coerce(v, fields_by_name[k].type)
                    except (TypeError, ValueError) as e:
                        raise ConfigError(f"bad value for {section_name}.{k}={v!r}: {e}") from e
                setattr(section, k, v)
        cfg.validate()
        return cfg

    @classmethod
    def from_file(cls, path: str) -> "MCPXConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    @classmethod
    def from_env(cls, env: Optional[dict[str, str]] = None) -> "MCPXConfig":
        """Environment overrides use ``MCPX_<SECTION>_<KEY>`` naming; the
        reference's ``REDIS_URL`` (control_plane.py:17) is honoured too."""
        env = dict(os.environ if env is None else env)
        cfg = cls()
        if env.get("REDIS_URL"):
            cfg.registry.redis_url = env["REDIS_URL"]
        for section_field in dataclasses.fields(cfg):
            section = getattr(cfg, section_field.name)
            for f in dataclasses.fields(section):
                sub = getattr(section, f.name)
                if dataclasses.is_dataclass(sub):
                    # Nested subsystem config: MCPX_<SECTION>_<FIELD>_<SUB>
                    # (e.g. MCPX_ENGINE_SPECULATIVE_ENABLED=1).
                    for sf in dataclasses.fields(sub):
                        key = (
                            f"MCPX_{section_field.name.upper()}_"
                            f"{f.name.upper()}_{sf.name.upper()}"
                        )
                        if key in env:
                            try:
                                setattr(sub, sf.name, _coerce(env[key], sf.type))
                            except (TypeError, ValueError) as e:
                                raise ConfigError(
                                    f"bad value for {key}={env[key]!r}: {e}"
                                ) from e
                    continue
                key = f"MCPX_{section_field.name.upper()}_{f.name.upper()}"
                if key in env:
                    try:
                        setattr(section, f.name, _coerce(env[key], f.type))
                    except (TypeError, ValueError) as e:
                        raise ConfigError(f"bad value for {key}={env[key]!r}: {e}") from e
        cfg.validate()
        return cfg

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    # -------------------------------------------------------------- validate
    def validate(self) -> None:
        problems: list[str] = []
        if self.registry.backend not in ("memory", "file", "redis"):
            problems.append(f"registry.backend '{self.registry.backend}' not in memory|file|redis")
        if self.registry.backend == "file" and not self.registry.file_path:
            problems.append("registry.backend=file requires registry.file_path")
        if self.registry.backend == "redis" and not self.registry.redis_url:
            problems.append("registry.backend=redis requires registry.redis_url")
        if self.model.quantize not in ("none", "int8"):
            problems.append(
                f"model.quantize '{self.model.quantize}' not in none|int8"
            )
        if self.planner.kind not in ("llm", "heuristic", "mock"):
            problems.append(f"planner.kind '{self.planner.kind}' not in llm|heuristic|mock")
        if self.planner.constrain_names not in ("registry", "shortlist", "off"):
            problems.append(
                f"planner.constrain_names '{self.planner.constrain_names}' "
                "not in registry|shortlist|off"
            )
        if self.planner.constrain_input_keys not in ("registry", "off"):
            problems.append(
                f"planner.constrain_input_keys '{self.planner.constrain_input_keys}' "
                "not in registry|off"
            )
        if self.engine.kv_page_size <= 0 or self.engine.kv_page_size & (self.engine.kv_page_size - 1):
            problems.append("engine.kv_page_size must be a positive power of two")
        if self.engine.data_axis < 0 or self.engine.model_axis < 0:
            problems.append("engine mesh axes must be >= 0 (0 = auto)")
        if self.engine.max_batch_size < 1:
            problems.append("engine.max_batch_size must be >= 1")
        if self.engine.pipeline_depth < 1:
            problems.append("engine.pipeline_depth must be >= 1")
        if self.engine.hetero_grammar_slots < 2:
            problems.append(
                "engine.hetero_grammar_slots must be >= 2 (slot 0 is the "
                "trivial DFA; at least one constrained grammar must fit)"
            )
        if self.engine.max_decode_len > 32767:
            # planner/grammar.py DIST_SUCC_MAX: the budget mask compares the
            # remaining budget with an int16 table that saturates there.
            problems.append("engine.max_decode_len must be <= 32767")
        if self.engine.decode_steps_per_tick < 1:
            problems.append("engine.decode_steps_per_tick must be >= 1")
        if not 1 <= self.engine.steps_per_dispatch <= 64:
            # The fused window multiplies the while-loop segment's iters
            # static; 64 windows of the default 4-forward tick is already
            # a 256-forward dispatch — past any plausible admission-latency
            # budget, and a typo guard for ms-vs-count confusions.
            problems.append("engine.steps_per_dispatch must be in [1, 64]")
        if not 0.0 < self.telemetry.ewma_alpha <= 1.0:
            problems.append("telemetry.ewma_alpha must be in (0, 1]")
        fl = self.telemetry.flight
        if fl.interval_s <= 0:
            problems.append("telemetry.flight.interval_s must be > 0")
        if fl.ring_size < 8:
            problems.append("telemetry.flight.ring_size must be >= 8")
        if not 0.0 < fl.ewma_alpha <= 1.0:
            problems.append("telemetry.flight.ewma_alpha must be in (0, 1]")
        if fl.band_k <= 0:
            problems.append("telemetry.flight.band_k must be > 0")
        if fl.min_samples < 2:
            problems.append("telemetry.flight.min_samples must be >= 2")
        if fl.hysteresis < 1:
            problems.append("telemetry.flight.hysteresis must be >= 1")
        if fl.cooldown_s < 0:
            problems.append("telemetry.flight.cooldown_s must be >= 0")
        if fl.max_bundles < 1:
            problems.append("telemetry.flight.max_bundles must be >= 1")
        if fl.enabled and not fl.bundle_dir:
            problems.append(
                "telemetry.flight.bundle_dir must be set while the "
                "recorder is enabled (bundles need somewhere to land)"
            )
        lg = self.telemetry.ledger
        if lg.max_tenants < 1:
            problems.append("telemetry.ledger.max_tenants must be >= 1")
        if lg.recent < 0:
            problems.append("telemetry.ledger.recent must be >= 0")
        pv = self.telemetry.provenance
        if pv.max_records_per_trace < 1:
            problems.append(
                "telemetry.provenance.max_records_per_trace must be >= 1"
            )
        if pv.route_ring < 1:
            problems.append("telemetry.provenance.route_ring must be >= 1")
        if pv.journal_size < 1:
            problems.append("telemetry.provenance.journal_size must be >= 1")
        if pv.replica_ring < 1:
            problems.append("telemetry.provenance.replica_ring must be >= 1")
        so = self.slo
        if not isinstance(so.windows_s, list) or len(so.windows_s) < 2:
            problems.append("slo.windows_s must list >= 2 window lengths")
        elif any(
            not isinstance(w, (int, float)) or w <= 0 for w in so.windows_s
        ) or list(so.windows_s) != sorted(so.windows_s):
            problems.append("slo.windows_s must be positive and ascending")
        if so.bucket_s <= 0:
            problems.append("slo.bucket_s must be > 0")
        if so.fast_burn_threshold <= 0:
            problems.append("slo.fast_burn_threshold must be > 0")
        if so.max_tenants < 1:
            problems.append("slo.max_tenants must be >= 1")
        if not isinstance(so.objectives, list):
            problems.append("slo.objectives must be a list of objective objects")
        else:
            for i, spec in enumerate(so.objectives):
                if not isinstance(spec, dict):
                    problems.append(f"slo.objectives[{i}] must be an object")
                    continue
                kind = spec.get("kind")
                if kind not in ("latency", "availability", "plan_quality"):
                    problems.append(
                        f"slo.objectives[{i}].kind {kind!r} not in "
                        "latency|availability|plan_quality"
                    )
                if not spec.get("name"):
                    problems.append(f"slo.objectives[{i}] needs a name")
                tgt = spec.get("target")
                if not isinstance(tgt, (int, float)) or not 0.0 < tgt < 1.0:
                    problems.append(
                        f"slo.objectives[{i}].target must be in (0, 1)"
                    )
                if kind == "latency" and not (
                    isinstance(spec.get("threshold_ms"), (int, float))
                    and spec["threshold_ms"] > 0
                ):
                    problems.append(
                        f"slo.objectives[{i}] (latency) needs threshold_ms > 0"
                    )
        if self.scheduler.burn_aware and not so.enabled:
            problems.append(
                "scheduler.burn_aware requires slo.enabled (the ladder "
                "consults the error-budget engine's burn state)"
            )
        if self.retrieval.top_k < 1:
            problems.append("retrieval.top_k must be >= 1")
        kt = self.engine.kv_tier
        if kt.host_mb < 0:
            problems.append("engine.kv_tier.host_mb must be >= 0")
        if kt.copy_tokens_per_cycle < 0:
            problems.append(
                "engine.kv_tier.copy_tokens_per_cycle must be >= 0 (0 = unlimited)"
            )
        if kt.snapshot_path and not kt.enabled:
            problems.append(
                "engine.kv_tier.snapshot_path requires engine.kv_tier.enabled "
                "(restored heads live in the host spill tier)"
            )
        if not isinstance(kt.tenant_weights, dict) or any(
            not isinstance(v, (int, float)) or v <= 0
            for v in kt.tenant_weights.values()
        ):
            problems.append(
                "engine.kv_tier.tenant_weights must map tenant -> positive weight"
            )
        if self.engine.draft_mode not in ("prompt", "off"):
            problems.append(
                f"engine.draft_mode '{self.engine.draft_mode}' not in prompt|off"
            )
        if not 1 <= self.engine.speculative.k <= 64:
            # The upper bound is a float32 guard, not a tuning opinion: the
            # drafter's closed-form state advance renormalises with
            # decay^-i = 2^i per window position, which overflows to inf
            # past i ~ 127 and would silently NaN the drafter (outputs stay
            # correct — verification rules — but acceptance collapses).
            # Useful k saturates far below this anyway (see SpeculativeConfig.k).
            problems.append("engine.speculative.k must be in [1, 64]")
        if self.engine.speculative.draft not in ("recurrent", "grammar"):
            problems.append(
                f"engine.speculative.draft '{self.engine.speculative.draft}' "
                "not in recurrent|grammar"
            )
        s = self.scheduler
        if s.slo_ms <= 0:
            problems.append("scheduler.slo_ms must be > 0")
        if s.max_parallel < 1:
            problems.append("scheduler.max_parallel must be >= 1")
        if s.max_queue_depth < 1:
            problems.append("scheduler.max_queue_depth must be >= 1")
        if s.rate_limit < 0:
            problems.append("scheduler.rate_limit must be >= 0 (0 = unlimited)")
        if s.rate_limit > 0 and s.burst < 1:
            problems.append("scheduler.burst must be >= 1 when rate_limit is set")
        if not 0.0 < s.ewma_alpha <= 1.0:
            problems.append("scheduler.ewma_alpha must be in (0, 1]")
        if not 0.0 < s.recover_threshold < s.degrade_threshold:
            problems.append(
                "scheduler thresholds must satisfy 0 < recover_threshold "
                f"({s.recover_threshold}) < degrade_threshold ({s.degrade_threshold})"
            )
        r = self.resilience
        if r.breaker_window < 1:
            problems.append("resilience.breaker_window must be >= 1")
        if not 0.0 < r.breaker_error_threshold <= 1.0:
            problems.append("resilience.breaker_error_threshold must be in (0, 1]")
        if r.breaker_min_samples < 1:
            problems.append("resilience.breaker_min_samples must be >= 1")
        if r.breaker_consecutive_failures < 1:
            problems.append("resilience.breaker_consecutive_failures must be >= 1")
        if r.breaker_open_s <= 0:
            problems.append("resilience.breaker_open_s must be > 0")
        if not 0.0 < r.breaker_half_open_probe_p <= 1.0:
            problems.append("resilience.breaker_half_open_probe_p must be in (0, 1]")
        if r.min_attempt_s < 0:
            problems.append("resilience.min_attempt_s must be >= 0")
        if r.hedge_latency_factor <= 0:
            problems.append("resilience.hedge_latency_factor must be > 0")
        if not 0.0 <= r.hedge_max_fraction <= 1.0:
            problems.append("resilience.hedge_max_fraction must be in [0, 1]")
        t = self.tracing
        if not 0.0 <= t.sample_rate <= 1.0:
            problems.append("tracing.sample_rate must be in [0, 1]")
        if t.ring_size < 1:
            problems.append("tracing.ring_size must be >= 1")
        if t.slo_breach_ms < 0:
            problems.append("tracing.slo_breach_ms must be >= 0 (0 = off)")
        if self.retrieval.shortlist_mode not in ("residual", "topk"):
            problems.append(
                f"retrieval.shortlist_mode '{self.retrieval.shortlist_mode}' "
                "not in residual|topk"
            )
        cl = self.cluster
        if cl.replicas < 1:
            problems.append("cluster.replicas must be >= 1")
        if cl.affinity_prefix_tokens < 1:
            problems.append("cluster.affinity_prefix_tokens must be >= 1")
        if cl.affinity_weight < 0:
            problems.append("cluster.affinity_weight must be >= 0")
        if cl.imbalance_ratio < 1.0:
            problems.append("cluster.imbalance_ratio must be >= 1")
        if cl.scoreboard_interval_s <= 0:
            problems.append("cluster.scoreboard_interval_s must be > 0")
        if cl.error_window < 1:
            problems.append("cluster.error_window must be >= 1")
        if cl.drain_timeout_s < 0:
            problems.append("cluster.drain_timeout_s must be >= 0")
        if cl.registry_shards < 0:
            problems.append("cluster.registry_shards must be >= 0 (0 = one per replica)")
        if cl.enabled and self.planner.kind != "llm":
            problems.append(
                "cluster.enabled requires planner.kind=llm (the pool owns "
                "inference-engine replicas; heuristic/mock planners have none)"
            )
        if cl.burn_aware and not so.enabled:
            problems.append(
                "cluster.burn_aware requires slo.enabled (placement reads "
                "the error-budget engine's burn state)"
            )
        if cl.warm_snapshot_dir and not kt.enabled:
            problems.append(
                "cluster.warm_snapshot_dir requires engine.kv_tier.enabled "
                "(replica warm-up restores manifests into the host spill tier)"
            )
        if problems:
            raise ConfigError("; ".join(problems))


def _coerce(value: str, typ: Any) -> Any:
    t = str(typ)
    if "bool" in t:
        v = value.strip().lower()
        if v in ("1", "true", "yes", "on"):
            return True
        if v in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"not a boolean: {value!r}")
    if "int" in t:
        return int(value)
    if "float" in t:
        return float(value)
    return value
