"""InferenceEngine: continuously-batched, grammar-constrained generation on TPU.

The reference's "engine" is a blocking HTTPS call to OpenAI (reference
``control_plane.py:69-73``, bug B6). This engine is the north star's
replacement: an in-process serving stack where

  - requests funnel through a thread-safe queue into a dedicated worker
    thread that owns a persistent **slab** of ``max_batch_size`` decode rows;
  - decode runs in bounded **segments** (one jitted ``lax.while_loop``
    each, of whole ticks of ``decode_steps_per_tick`` model forwards: as
    many as the pacer asks for at that dispatch, at most the configured
    window ``decode_steps_per_tick x steps_per_dispatch``; the length is
    an operand of ONE executable, ``engine/pacing.py::segment_forwards``
    chooses it from the forward period, prefill chain and host costs the
    worker measures); between
    segments the worker admits newly-arrived requests into free rows
    (prefill → commit-to-pages → first sample → merge) and retires finished
    rows — *continuous batching*: a request never waits for a previous
    batch to run to completion, only for the next segment boundary
    (SURVEY.md §3.3; the p50 lever VERDICT r2 ranked #1);
  - the worker is **pipelined** (``pipeline_depth``): it dispatches the
    next segment BEFORE fetching the previous one's done-flags, so the
    blocking host←device fetch rides on top of compute the device is
    already doing. The dispatch is **just in time** (``engine/pacing.py``):
    with a segment in flight and a slab row free, the worker holds the next
    segment, waits on its queue and admits each arrival, and dispatches
    shortly before the predicted ready time of the one in flight, so a
    request that arrives during a segment joins the next one instead of
    the one after, and the device's queue still never empties. Slab-row
    mutation happens on device via a jitted merge scatter; the host never
    materialises full state. Per-row generation counters keep lagged
    done-flags from retiring a re-admitted row;
  - within a segment, grammar masking, speculation fast-forward, sampling
    and KV writes all happen on-device with zero host round-trips per
    token; pools are donated so decode updates in place;
  - with ``EngineConfig.hetero_batch`` the slab is **heterogeneous**:
    temperature, the constrained flag and the grammar are per-row device
    state (stacked DFA tables indexed by a per-row ``dfa_id``; per-row
    greedy/stochastic selection in ``sample_rows``), so any request admits
    into any free row in strict queue order — no slab-wide compatibility
    triple, no drain-to-switch (docs/engine.md);
  - the engine is **multi-chip by default**: the mesh covers every visible
    device (TP over ``model`` for heads/MLP/vocab, DP over ``data`` for the
    slab rows), params restore sharded, and the paged KV pools carry a
    ``NamedSharding`` (KV heads over ``model`` when divisible — GQA; MQA
    replicates KV, the standard MQA-TP layout). Collectives are XLA-inserted
    over ICI from the annotations (SURVEY.md §2.3);
  - the KV page allocator and all slab row state run host-side,
    single-writer, in the worker thread (no allocator races by
    construction, SURVEY.md §5).

Startup (mesh build, weight load, warmup compiles) is an explicit,
observable phase: ``state`` moves cold → warming → ready and ``/healthz``
reports it (SURVEY.md §3.4).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import math
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import ConfigError, EngineError
from mcpx.engine.kv_cache import (
    PageAllocator, commit_prefill_key_sums, commit_prefill_tails, commit_prefill_to_pages, init_paged_kv,
    init_state_pool,
    write_prefill_state,
)
from mcpx.engine.pacing import SegmentPacer, hold_until
from mcpx.engine.paged_decode import decode_chunk_paged, keep_window
from mcpx.models.gemma.moe import (
    BLOCK_STATS, FORWARD_STATS, INDEX_STATS, LATENT_STATS, LAYER_STATS, forward_weight_bytes,
    moe_stats_init,
)
from mcpx.engine.prefix_cache import PrefixNode, RadixPrefixCache
from mcpx.engine.sampling import accept_rows, sample, sample_rows, sample_window_rows
from mcpx.engine.speculative import advance_drafter_state, draft_window
from mcpx.models.gemma.config import GemmaConfig
from mcpx.models.gemma.model import init_kv_cache, prefill
from mcpx.models.gemma.params import bytes_per_device, load_or_init
from mcpx.models.tokenizer import make_tokenizer
from mcpx.planner.grammar import (
    PlanGrammar,
    build_plan_grammar,
    build_trivial_grammar,
    stacked_tables,
    stacked_spec_tables,
)
from mcpx.scheduler.admission import ewma_update
from mcpx.scheduler.locality import locality_order
from mcpx.telemetry import ledger as ledger_mod
from mcpx.telemetry import tracing
from mcpx.telemetry.costs import CostRegistry, device_peaks
from mcpx.telemetry.flight import SEGMENT_PARTS, WorkerProfiler
from mcpx.telemetry.metrics import Metrics
from mcpx.telemetry.startup import StartupTimeline
from mcpx.utils.ownership import owned_by

log = logging.getLogger("mcpx.engine")


@dataclasses.dataclass
class GenerateRequest:  # mcpx: request-payload
    prompt_ids: list[int]
    max_new_tokens: int
    constrained: bool
    temperature: float
    future: "asyncio.Future[GenerateResult]"
    loop: asyncio.AbstractEventLoop
    enqueued_at: float
    # Grammar to constrain with (None = the engine's generic plan grammar).
    # Requests sharing a grammar OBJECT can share the slab; the planner
    # caches grammars per registry version so this is the common case.
    grammar: Optional[PlanGrammar] = None
    # The first `shared_prefix_len` prompt ids are identical across many
    # requests (the planner's fixed prompt header): the engine prefills them
    # ONCE into read-only KV pages shared by every row's page table, and
    # per-request prefill covers only the suffix. 0 disables. With the
    # radix prefix cache this is a cold-start HINT (the declared head is
    # pre-built into the tree before the first cohort so even that cohort
    # shares it); matching itself is per-request against the whole tree.
    shared_prefix_len: int = 0
    # EDF deadline (time.monotonic timestamp) from the serving scheduler:
    # the locality-aware admission sort must never regroup a request whose
    # deadline cannot afford the wait (scheduler/locality.py). None = no
    # deadline (reorderable freely within the fairness-age bound).
    deadline_at: Optional[float] = None
    # Cache-governance identity (scheduler grant -> PlanContext ->
    # GenerateRequest): radix-tree insertions are charged to this tenant,
    # whose weighted-fair quota bounds its resident KV (cache_governor.py).
    # Inert ("default") when governance is off or no scheduler runs.
    tenant: str = "default"
    # Tracing parent (telemetry/tracing.Span) for engine-side attribution:
    # the worker thread hangs queue-wait / prefill / per-segment decode
    # child spans off it via explicit parent.child(t0=..., t1=...) calls —
    # no contextvar crosses the thread boundary. None (tracing disabled or
    # request unsampled) keeps the decode hot path entirely span-free.
    span: Optional[Any] = None
    # When the worker's _drain_queue moved this request into its pending
    # line (time.monotonic; stamped for requests that carry a span only):
    # enqueued_at..seen_at is the engine.queue_wait span's ``unseen_ms``.
    seen_at: float = 0.0
    # The engine as ``generate`` found it at enqueue: the newest segment's
    # ready stamp (0.0 = none yet; engine.queue_wait's ``since_ready_ms``)
    # and the number of segments dispatched so far (engine.decode's
    # ``missed_dispatches``). Two plain reads of worker-written scalars.
    ready_seen: float = 0.0
    dispatch_seen: int = 0

    def prefix_key(self, page_size: int) -> Optional[tuple]:
        """Page-aligned shared prefix as the cache key (None = no sharing).
        Alignment truncates — trailing unaligned prefix ids simply join the
        suffix — and at least one token must remain in the suffix (the
        engine samples from the suffix prefill's last logit)."""
        n = min(self.shared_prefix_len, len(self.prompt_ids) - 1)
        n = (n // page_size) * page_size
        if n < page_size:
            return None
        return tuple(self.prompt_ids[:n])


@dataclasses.dataclass
class _PinPrefixOp:
    """Worker-queue control op: pin the deepest resident radix node whose
    path prefixes ``ids`` (a ``/plan_and_execute`` holding its plan's
    prompt KV warm across tool execution); resolves ``future`` with the
    node handle, or None when nothing is resident. Single-writer: the
    worker thread applies it between segments."""

    ids: list[int]
    future: "asyncio.Future[Optional[PrefixNode]]"
    loop: asyncio.AbstractEventLoop


@dataclasses.dataclass
class _UnpinPrefixOp:
    """Worker-queue control op: release a ``_PinPrefixOp`` pin."""

    node: PrefixNode


@dataclasses.dataclass
class _WarmGrammarOp:
    """Worker-queue control op: compile the grammar-shaped executables for
    ``grammar`` (``InferenceEngine.warm_grammar``); resolves ``future``
    with None, or with the failure."""

    grammar: PlanGrammar
    future: "asyncio.Future[None]"
    loop: asyncio.AbstractEventLoop


@dataclasses.dataclass
class GenerateResult:
    token_ids: list[int]
    text: str
    prompt_tokens: int
    generated_tokens: int
    queue_ms: float
    prefill_ms: float
    decode_ms: float
    # Engine portion of the request's cost-ledger bill (telemetry/ledger.py):
    # a FRESH dict built by the worker at retirement — handed across the
    # thread boundary by value, folded into the contextvar bill back on the
    # request task (generate()). None while telemetry.ledger is off, so the
    # disabled path carries no billing state at all.
    bill: Optional[dict] = None
    # The ready stamp (time.monotonic) of the segment whose harvest
    # delivered this: ``generate`` measures engine.generate's
    # ``deliver_ms`` from it to the coroutine's resumption.
    ready_at: float = 0.0


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise EngineError(f"length {n} exceeds largest bucket {buckets[-1]}")


# Where the automatic cohort buckets go below 8 rows, halved down to
# _SMALL_COHORT_FLOOR: at ONE prefill bucket a route, _SMALL_COHORT_T's, the
# one where that route's short cohorts land: whole prompts of 65-128 tokens at
# 128 slots, suffixes behind a matched prefix at 64 (behind a shared catalogue
# head every admission is a suffix of 20-40 tokens). A prefill past ~240
# tokens a weight pass is compute-bound on a v5e and costs by the slot: a
# cohort of 2-4 through 8 x 128 slots pays twice what 4 x 128 does, and 8 x 64
# against 4 x 64 reads 35 against 22 ms of an unrolled stack. Every bucket is
# executables to trace, compile or load at EVERY start, though: a prefill
# executable a (rows, slots, route), 0.6 s of a warm start for a scanned dense
# stack and 3.4 s for an unrolled sparse one, and for a NEW row bucket an
# admit, an admit-merge and the registry grammar's admit besides (the 4-row
# ones are there once, for both routes). So a cohort with ONE matched row and
# mates of 65-128 tokens still rides in 8 rows (PERF.md PR 57: +1 to +2.6% of
# the rate at the distinct cells, +8.5% of a warm start where the stack is
# unrolled and sparse); that, a whole prompt under 65 tokens, a row past 128
# slots or a 2-row bucket is what an explicit ``engine.batch_buckets`` is for
# (a.x-k1's [1, 2, 4] at the 1,024 bucket costs 63 executables; the catalogue
# cells warm six prefill buckets on two routes).
_SMALL_COHORT_FLOOR = 4
_SMALL_COHORT_T = {False: 128, True: 64}  # by route: whole prompts, suffixes


def cohort_buckets(rows: int, explicit: tuple[int, ...], T: int, suffix: bool) -> tuple[int, ...]:
    """The ONE table of which admission-cohort sizes exist: the row buckets of
    a ``rows``-row slab at prefill bucket ``T`` on the whole-prompt route or,
    ``suffix``, behind a matched prefix. Warm-up compiles exactly these and
    admission rounds a cohort up among exactly these, so no cohort asks for
    an executable the start did not build. An ``explicit`` list
    (``engine.batch_buckets``) holds at every ``T`` on both routes; ``rows``
    itself always exists, so a fully gathered burst has a bucket."""
    if explicit:
        sizes = set(explicit)
    else:
        floor = _SMALL_COHORT_FLOOR if T == _SMALL_COHORT_T[suffix] else 8
        sizes = {1, 8, rows}
        q = rows
        while q >= 2 * floor:
            q //= 2
            sizes.add(q)
    return tuple(sorted({b for b in sizes if b < rows} | {rows}))


@dataclasses.dataclass
class _PlanTrack:
    """A traced slab row's wall, placed against the segments' ready stamps:
    what engine.decode says of a plan besides its tokens. Times are
    ``time.monotonic``, the spans' clock."""

    admit_t0: float  # its admission's start on the host = engine.queue_wait's end
    admit_t1: float  # that admission's end: prefill chain and first sample enqueued
    first_seq: int = 0  # the first segment dispatched with the row resident
    t_device: float = 0.0  # when the device could start it: max(dispatch, previous ready stamp)
    segments: int = 0  # segments harvested with the row resident
    live_forwards: int = 0  # sum of the row's device counter over them
    ridden_forwards: int = 0  # sum of their forwards


@owned_by("engine-worker")
class _Slab:
    """Host-side state of the persistent decode batch. Single writer (the
    engine worker thread, enforced by mcpxlint's thread-ownership pass via
    the class-level ``owned_by``); the race-detection analogue SURVEY.md §5
    asks for is discharged structurally, exactly like the page allocator.

    Invariant between worker iterations: every row with a live request has
    ``done=False``; every free row has ``req=None, done=True`` and a zeroed
    page-table row (decode writes for free rows land on the reserved null
    page 0, which no live sequence ever reads).
    """

    def __init__(
        self,
        B: int,
        steps: int,
        pmax: int,
        pad_id: int,
        prompt_cap: int = 0,
        draft_dim: int = 1,
    ) -> None:
        self.B = B
        self.steps = steps
        self.pad_id = pad_id
        self.req: list[Optional[GenerateRequest]] = [None] * B
        self.sid: list[Optional[tuple]] = [None] * B
        # Radix prefix nodes this row pins (engine/prefix_cache.py): the
        # deepest matched node plus the node inserted for the row's own
        # page-aligned prompt remainder. refs released at clear_row.
        self.prefix: list[tuple] = [()] * B
        # Matched-prefix tokens per row (admission-time): the
        # engine.prefill span's prefix_matched_tokens/prefix_hit attrs.
        self.prefix_toks = np.zeros((B,), np.int32)
        # Per-row generation counter, bumped at admission. In-flight segment
        # outputs carry a snapshot: a done-flag from a segment dispatched
        # BEFORE the row was re-admitted must never retire the row's NEW
        # request (the pipelined worker reads flags D segments late).
        self.gen = np.zeros((B,), np.int64)
        self.cur = np.full((B,), pad_id, np.int32)
        self.pos = np.zeros((B,), np.int32)
        self.st = np.zeros((B,), np.int32)
        self.emitted = np.zeros((B,), np.int32)
        self.done = np.ones((B,), bool)
        self.budgets = np.zeros((B,), np.int32)
        self.out_buf = np.full((B, steps), pad_id, np.int32)
        self.page_table = np.zeros((B, pmax), np.int32)
        # Prompt-lookup draft state: each row's prompt (suffix) tokens stay
        # device-resident so the decode segment can propose continuations
        # after a bigram match (EngineConfig.draft_mode). ``prev`` is the
        # token before ``cur`` — the other half of the match bigram. Host
        # mirrors hold clear values only (authoritative copies live in
        # slab.dev, written by the admit merge, like cur/st).
        self.prompt_cap = max(1, prompt_cap)
        self.prompt_toks = np.full((B, self.prompt_cap), pad_id, np.int32)
        self.prompt_lens = np.zeros((B,), np.int32)
        self.prev = np.full((B,), pad_id, np.int32)
        self.queue_ms = np.zeros((B,), np.float64)
        self.prefill_ms = np.zeros((B,), np.float64)
        self.t_decode0 = np.zeros((B,), np.float64)
        # Per-row sampling config (heterogeneous batching): host mirrors of
        # the device vectors the hetero segment reads — temperature, the
        # constrained flag, and the stacked-DFA slot index (0 = trivial
        # all-accept DFA for unconstrained rows). Scattered by the merges
        # like every other row field; inert when hetero_batch is off.
        self.temp = np.zeros((B,), np.float32)
        self.cons = np.zeros((B,), bool)
        self.dfa = np.zeros((B,), np.int32)
        # Where a TRACED row's plan stands against the segments' ready
        # stamps (``_PlanTrack``: engine.decode's placement attributes):
        # made at admission only when a span rides the request, advanced
        # at each harvest that carried the row, dropped with the row. The
        # untraced hot path never touches it.
        self.track: list[Optional[_PlanTrack]] = [None] * B
        # Per-row cost-ledger accumulators (telemetry/ledger.py), written
        # ONLY while telemetry.ledger is enabled (engine._ledger_on) —
        # ledger-off leaves every array untouched, the pass-through
        # contract. Cleared with the row; the retirement bill reads them.
        self.bill_flops = np.zeros((B,), np.float64)   # apportioned XLA flops
        self.bill_bytes = np.zeros((B,), np.float64)   # apportioned HBM bytes
        self.bill_fwd = np.zeros((B,), np.int64)       # forwards while resident
        self.bill_spec = np.zeros((B,), np.int64)      # accepted spec tokens
        self.bill_copy = np.zeros((B,), np.int64)      # readmit copy tokens
        self.bill_pages = np.zeros((B,), np.int32)     # row-private KV pages
        self.suffix_toks = np.zeros((B,), np.int32)    # suffix tokens prefilled
        self.admit_t = np.zeros((B,), np.float64)      # admission timestamp
        # Recurrent drafter hidden state (grammar-aware speculative
        # decoding, engine/speculative.py): an embedding-EWMA over the
        # row's emitted tokens, [B, d_model]. Host mirror holds clear
        # values only (zeros — a fresh row's drafter starts cold); the
        # authoritative copy lives in slab.dev, advanced by the spec
        # segment by each row's accepted count. Inert when speculation is
        # off (scattered but never read, like temp/cons/dfa under
        # hetero_batch=off).
        self.hstate = np.zeros((B, max(1, draft_dim)), np.float32)
        # Sampling config shared by every resident row (reset when empty) —
        # the HOMOGENEOUS slab's compatibility triple (hetero_batch=off).
        self.constrained = True
        self.temperature = 0.0
        self.grammar: Optional[PlanGrammar] = None
        # Rows whose request carries a tracing span (GenerateRequest.span).
        # Zero = the common disabled/unsampled case: every per-segment
        # tracing branch in the worker collapses to one int comparison and
        # the decode hot path allocates nothing for tracing.
        self.n_traced = 0
        # The batching mode the CURRENT occupancy was admitted under,
        # latched whenever the slab refills from empty: rows admitted under
        # one mode carry that mode's page-slack geometry, so a live
        # EngineConfig.hetero_batch flip takes effect only at the next
        # empty-slab admission — never mid-occupancy (admission pauses
        # until the old-mode rows drain).
        self.hetero = False
        # Speculative-decoding latch, same refill-from-empty discipline:
        # rows admitted under speculation carry the [K+1]-wide window's
        # page-slack geometry and always decode through the spec segment;
        # a live EngineConfig.speculative flip pauses admission until they
        # drain (flip-safe by construction, like the hetero latch above).
        # spec_k/spec_draft are the LATCHED window width and draft mode —
        # dispatch must read these, never the live config: a mid-drain
        # enabled/k/draft change would otherwise retrace an unwarmed
        # executable (K and draft are static args) under rows admitted
        # with the old window's page slack.
        self.spec = False
        self.spec_k = 0
        self.spec_draft = "recurrent"
        # Device-resident copy of (cur, pos, st, emitted, done, budgets,
        # page_table, out_buf) between segments — None only at startup and
        # after a failure reset (host arrays are then authoritative). All
        # row mutation (admission, retirement pt-zeroing) happens ON DEVICE
        # via the jitted merge scatter; the host only ever reads back the
        # small flag vectors + out_buf of a LAGGED segment.
        self.dev: Optional[tuple] = None

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.req)

    def free_rows(self) -> list[int]:
        return [i for i, r in enumerate(self.req) if r is None]

    def compatible(self, r: GenerateRequest) -> bool:
        return (
            r.constrained == self.constrained
            and r.temperature == self.temperature
            and (not r.constrained or r.grammar is self.grammar)
        )

    def clear_row(self, i: int) -> None:
        r = self.req[i]
        if r is not None and r.span is not None:
            self.n_traced -= 1
        self.req[i] = None
        self.track[i] = None
        self.sid[i] = None
        self.done[i] = True
        self.cur[i] = self.pad_id
        self.pos[i] = 0
        self.st[i] = 0
        self.emitted[i] = 0
        self.budgets[i] = 0
        self.prompt_toks[i, :] = self.pad_id
        self.prompt_lens[i] = 0
        self.prev[i] = self.pad_id
        self.temp[i] = 0.0
        self.cons[i] = False
        self.dfa[i] = 0
        self.hstate[i, :] = 0.0
        self.gen[i] += 1
        self.page_table[i, :] = 0
        for node in self.prefix[i]:
            node.refs -= 1
        self.prefix[i] = ()
        self.prefix_toks[i] = 0
        self.bill_flops[i] = 0.0
        self.bill_bytes[i] = 0.0
        self.bill_fwd[i] = 0
        self.bill_spec[i] = 0
        self.bill_copy[i] = 0
        self.bill_pages[i] = 0
        self.suffix_toks[i] = 0
        self.admit_t[i] = 0.0


# Legal lifecycle transitions: the single source of truth for the engine
# state machine. ``_transition`` is the only mutator outside aclose(), which
# forces the terminal "closed" from any state.
_ENGINE_STATES: dict[str, tuple[str, ...]] = {
    "cold": ("warming",),
    "warming": ("ready", "failed", "closed"),
    "ready": ("closed",),
    "failed": ("closed",),
    "closed": (),
}


class InferenceEngine:
    def __init__(
        self,
        config: Optional[MCPXConfig] = None,
        model_cfg: Optional[GemmaConfig] = None,
        mesh=None,
        metrics: Optional[Metrics] = None,
    ) -> None:
        self.metrics = metrics or Metrics()
        # The start-up timeline (telemetry/startup.py), from the process's
        # start where this is its first engine: ``startup.import`` ends and
        # ``startup.build`` opens HERE, before anything below is built, and
        # the worker's ``_setup`` closes it. Served by /healthz's ``startup``
        # block and GET /traces/startup; ``ControlPlane.startup`` appends its
        # own phase and finishes it.
        self.startup = StartupTimeline(self.metrics)
        self.config = config or MCPXConfig()
        ecfg = self.config.engine
        self.tokenizer = make_tokenizer(self.config.model.vocab)
        self.model_cfg = model_cfg or GemmaConfig.named(
            self.config.model.size,
            max_seq_len=self.config.model.max_seq_len,
            vocab_size=self.tokenizer.vocab_size,
        )
        mc = self.model_cfg
        if not mc.is_default_block:
            # What was written against the default block and has not been
            # carried over to another: an error at construction, not a wrong
            # answer later.
            unsupported = {
                "model.quantize=int8 (no quantizer for router, expert or head leaves)":
                    self.config.model.quantize != "none",
                "engine.speculative (the drafter scores against a tied embedding)":
                    ecfg.speculative.enabled,
            }
            asked = [what for what, on in unsupported.items() if on]
            if asked:
                raise ConfigError(
                    f"this model's block departs from the default one, which {asked} requires"
                )
        if mc.hybrid:
            # A recurrent state beside the pages: what would have to carry
            # it and does not is refused here, by name.
            unsupported = {
                "engine.hetero_batch (the stacked-grammar segments do not carry the "
                "recurrent state)": ecfg.hetero_batch,
                "engine.kv_tier (a spilled or snapshotted page run has no state slot)":
                    ecfg.kv_tier.enabled or bool(ecfg.kv_tier.snapshot_path),
            }
            asked = [what for what, on in unsupported.items() if on]
            if asked:
                raise ConfigError(
                    f"this model keeps a recurrent state a row (layer_pattern), which {asked} "
                    "does not carry"
                )
        if mc.n_block_layers and ecfg.kv_page_size != mc.pool_stride:
            raise ConfigError(
                f"this model pools its keys every {mc.pool_stride} tokens (pool_stride): a page "
                f"holds one stride, engine.kv_page_size={ecfg.kv_page_size} does not"
            )
        # Which counters the segment returns beside its state (a sparse
        # feed-forward's, or a mixer + feed-forward pattern's own; windowed
        # attention); a default block returns none.
        self._segment_stats = (bool(mc.n_experts) or mc.dense_pattern, mc.layer_windows() is not None)
        self.grammar: PlanGrammar = build_plan_grammar(self.tokenizer)
        # Resolved kernel route, decided at construction so a COLD engine
        # can already answer pallas_paths()/queue_stats(). Mosaic tiles the
        # last (lane) dim at 128, so a head dim that doesn't align cannot
        # run the kernel compiled. On a TPU that is an error, as is asking
        # for the interpreter there: a chip never serves through a quiet
        # route around its kernel. Off-TPU (the CPU tests) the same
        # geometry takes the jnp reference unless interpret mode is on.
        # Asking for the backend initialises it, here on the constructing
        # thread (seconds on a TPU); the worker then finds it ready.
        if ecfg.use_pallas and jax.default_backend() == "tpu":
            if ecfg.interpret:
                raise ConfigError(
                    "engine.interpret=true on a TPU backend: the Pallas "
                    "interpreter is the CPU tests' route; on a TPU the "
                    "kernel is compiled by Mosaic"
                )
            if not mc.kernel_lanes_ok:
                raise ConfigError(
                    f"cache widths {mc.kv_widths} (head_dim, or the latent block's "
                    "rotated key and kv_lora_rank) are not multiples "
                    "of 128: Mosaic cannot tile the ragged kernel for this "
                    "model on a TPU"
                )
        self._use_pallas = ecfg.use_pallas and (
            ecfg.interpret or mc.kernel_lanes_ok
        )
        # Per-path kernel dispatch counters (decode / suffix-prefill /
        # spec-verify): how often each serving path actually ran, next to
        # the per-path engagement flags in pallas_paths() — a headline
        # `pallas=true` can then never mask a jnp fork OR an idle path.
        # Worker-thread writes, GIL-atomic cross-thread reads.
        self._pallas_dispatches = {  # mcpx: owner[engine-worker, atomic]
            "decode": 0, "prefill": 0, "spec_verify": 0, "ssm": 0, "gather": 0,
        }
        self.state = "cold"
        self._state_lock = threading.Lock()
        self._mesh = mesh
        self._queue: "queue.Queue[Optional[GenerateRequest]]" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self._stop = False
        self._startup_error: Optional[BaseException] = None  # mcpx: owner[engine-worker, atomic]
        # Device state (worker thread only after start):
        self._params = None  # mcpx: owner[engine-worker]
        self._paged_kv = None  # mcpx: owner[engine-worker]
        # The second kind of per-row state (kv_cache.init_state_pool): {} for
        # a model with no recurrent layer, which adds nothing to a jitted call.
        self._state_pool: dict = {}  # mcpx: owner[engine-worker]
        # Admissions of a model with recurrent layers that found their pages
        # resident and prefilled whole all the same (no node holds the STATE
        # a recurrent layer starts from): mcpx_engine_prefix_state_total
        # {event="miss"}.
        self._prefix_state_misses = 0  # mcpx: owner[engine-worker, atomic]
        # ... and those that started from a copy of the declared head's end
        # state ({event="hit"}; ``GemmaConfig.head_state``). ``_head_state``
        # is the head whose END STATE the pool's last slot holds (None: no
        # head's), written by ``_ensure_prefix`` alone.
        self._prefix_state_hits = 0  # mcpx: owner[engine-worker, atomic]
        self._head_state: Optional[tuple] = None  # mcpx: owner[engine-worker]
        self._dfa_cache: "OrderedDict[tuple, tuple]" = OrderedDict()  # mcpx: owner[engine-worker]
        # Heterogeneous batching (EngineConfig.hetero_batch): the stacked-DFA
        # slot table. ``_dfa_slots[k]`` is the grammar whose padded tables
        # occupy stack index k (slot 0 = trivial all-accept DFA, None = free
        # slot, filled with the trivial DFA when stacking); ``_dfa_slot_refs``
        # counts resident rows per slot — a slot is reclaimable at refs == 0.
        # ``_stack_cache`` holds the stacked device tables keyed by slot
        # occupancy so re-admissions of resident grammars upload nothing.
        # Worker thread only.
        self._trivial_grammar: Optional[PlanGrammar] = None  # mcpx: owner[engine-worker]
        self._dfa_slots: list[Optional[PlanGrammar]] = []  # mcpx: owner[engine-worker]
        self._dfa_slot_refs: list[int] = []  # mcpx: owner[engine-worker, atomic]
        self._stack_cache: Optional[tuple] = None  # (key, slot grammars, tables)  # mcpx: owner[engine-worker]
        # Per-class backlog snapshot published by the worker each iteration
        # for queue_stats() (cross-thread read of a freshly-swapped dict).
        self._pending_stats: dict = {  # mcpx: owner[engine-worker, atomic]
            "constrained": 0, "free": 0, "hol_wait_ms": 0.0,
        }
        # Mesh axes and weight placement, swapped in whole by _setup once
        # the tree is placed; queue_stats() merges it (empty while cold).
        self._placement: dict = {}  # mcpx: owner[engine-worker, atomic]
        # Pipelined segment outputs awaiting their (lagged) flag fetch:
        # entries are (done, emitted, out_buf, n_fwd device handles,
        # gen snapshot); decode wall time is taken at harvest. Worker
        # thread only.
        self._inflight: "deque[tuple]" = deque()  # mcpx: owner[engine-worker]
        # Rows retired on the host whose DEVICE page-table rows still point
        # at freed pages; zeroed (scatter to the null page) in the next
        # merge dispatch — which always happens before freed pages can be
        # reused, because reuse requires an admission and every admission
        # dispatches a merge.
        self._dirty_rows: set[int] = set()  # mcpx: owner[engine-worker]
        # Admission chains whose completion hasn't been observed yet:
        # (dispatch-end time, marker handle, row indices, gen snapshot).
        # Resolved by non-blocking is_ready() polls — admission never
        # blocks the host (async admission), so prefill timing comes from
        # the poll that first sees the chain finished (≤1 tick late).
        self._pending_admissions: list[tuple] = []  # mcpx: owner[engine-worker]
        self._seg_counter = 0  # mcpx: owner[engine-worker]
        # Decode segments dispatched so far: the ``seq`` of engine.segment
        # spans and the step_num of the segment's ``mcpx.segment`` event in
        # a profiler trace (_seg_counter also counts admissions: it seeds
        # the PRNG).
        # ``generate`` reads it at enqueue, a plain int (atomic): how many
        # dispatches a request then sits out is engine.decode's
        # ``missed_dispatches``.
        self._dispatch_seq = 0  # mcpx: owner[engine-worker, atomic]
        # The newest segment's ready stamp (its blocking fetch returned;
        # ``time.monotonic``, 0.0 = none yet): what a plan's wall is placed
        # against. ``generate`` reads it at enqueue (atomic) for
        # engine.queue_wait's ``since_ready_ms``.
        self._t_ready = 0.0  # mcpx: owner[engine-worker, atomic]
        # When the first device work since the previous segment dispatch
        # was enqueued (an admission's prefill chain; 0.0 = none, the next
        # dispatch is the first): the timeline's ``starved_ms``.
        self._t_queued = 0.0  # mcpx: owner[engine-worker]
        # Rows admitted since the previous segment dispatch: their prefills
        # are chained in front of the next segment on the device, so its
        # engine.segment spans carry the count as ``prefill_rows``.
        self._rows_admitted = 0  # mcpx: owner[engine-worker]
        # Those of them admitted while the segment was being held for
        # arrivals (``hold_joined_rows``), and their lifetime total for
        # queue_stats()'s worker_profile.
        self._hold_joined = 0  # mcpx: owner[engine-worker]
        self._hold_joined_total = 0  # mcpx: owner[engine-worker, atomic]
        # The expert counters of their prefills (a sparse model's), still
        # on the device: the next segment's lagged harvest fetches them
        # with its own flags, in the same device_get.
        self._prefill_moe: list = []  # mcpx: owner[engine-worker]
        # Lifetime sums of the decode segments' lengths as dispatched and
        # of the configured ceiling (worker_profile, like the total above).
        self._window_total = 0  # mcpx: owner[engine-worker, atomic]
        self._window_max_total = 0  # mcpx: owner[engine-worker, atomic]
        # Lifetime sums of the engine.segment spans' layer-kind counters
        # (_layer_kind_attrs), swapped in whole for queue_stats(); stays
        # empty for a model whose layers are all dense and full.
        self._layer_kind_totals: dict[str, int] = {}  # mcpx: owner[engine-worker, atomic]
        # A sparse model's weight bytes by kind (moe.forward_weight_bytes),
        # bound once with the weights: one routed expert's, and the rest of
        # what a forward reads.
        self._weight_bytes = (0, 0)  # mcpx: owner[engine-worker]
        # The bytes a forward reads of the short-convolution mixers.
        self._conv_weight_bytes = 0  # mcpx: owner[engine-worker]
        # Just-in-time dispatch of the next segment (engine/pacing.py): the
        # device's queue as the worker knows it and the running estimates
        # its hold deadline comes from. One clock read per admission,
        # dispatch and ready stamp, with or without a profiler.
        self._pacer = SegmentPacer()  # mcpx: owner[engine-worker]
        # Whether every slab row is taken, by the worker's own books, and
        # the last transitions of that with their times (kept while a
        # profiler is on): admission integrates them into the
        # engine.queue_wait span's ``free_row_ms``.
        self._slab_full = False  # mcpx: owner[engine-worker]
        self._occupancy: "deque[tuple[float, bool]]" = deque(maxlen=64)  # mcpx: owner[engine-worker]
        self._seq_counter = 0  # mcpx: owner[engine-worker]
        self._last_admit_t = 0.0  # mcpx: owner[engine-worker]
        # EWMA of per-request engine service time (prefill + decode wall
        # seconds, queue wait excluded), updated at retirement. Written by
        # the worker thread, read cross-thread by queue_stats() — a single
        # float store is GIL-atomic, and the scheduler's ETA math only
        # needs an estimate, not a snapshot.
        self._ewma_service_s = 0.0  # mcpx: owner[engine-worker, atomic]
        # Per-process entropy so temperature>0 sampling differs across
        # restarts and DP replicas (a bare counter would replay the same
        # stream everywhere); each dispatch folds the counter in.
        self._rng_base = time.time_ns() & 0x3FFFFFFF
        self._allocator = PageAllocator(  # mcpx: owner[engine-worker]
            n_pages=max(
                2,
                ecfg.max_batch_size * ecfg.max_pages_per_seq + 1,
            ),
            page_size=ecfg.kv_page_size,
            max_pages_per_seq=ecfg.max_pages_per_seq,
        )
        # Tiered KV cache (engine/spill.py + cache_governor.py,
        # EngineConfig.kv_tier): host-RAM spill tier + per-tenant cache
        # governance under the radix tree. None when disabled — the tree
        # then behaves byte-identically to the single-tier build.
        # Worker-thread-owned after start; counters read cross-thread.
        self._spill_tier = None  # mcpx: owner[engine-worker, atomic]
        self._governor = None  # mcpx: owner[engine-worker, atomic]
        if ecfg.kv_tier.enabled:
            from mcpx.engine.cache_governor import CacheGovernor
            from mcpx.engine.spill import HostSpillTier, SpillChaos

            chaos = None
            if ecfg.kv_tier.chaos_profile:
                try:
                    chaos = SpillChaos.from_config(ecfg.kv_tier.chaos_profile)
                except Exception as e:  # noqa: BLE001 - a bad profile must not kill serving
                    log.warning("spill chaos profile unusable: %s", e)
            self._spill_tier = HostSpillTier(
                host_bytes=int(ecfg.kv_tier.host_mb * 1024 * 1024),
                copy_tokens_per_cycle=ecfg.kv_tier.copy_tokens_per_cycle,
                chaos=chaos,
            )
            if ecfg.kv_tier.governor:
                self._governor = CacheGovernor(ecfg.kv_tier.tenant_weights)
        # Radix-tree prefix KV cache (engine/prefix_cache.py): cross-request
        # prompt-head reuse over the paged pool. Worker-thread-owned after
        # start; counters are read cross-thread (queue_stats, GET /cache).
        self._prefix_cache = RadixPrefixCache(  # mcpx: owner[engine-worker, atomic]
            self._allocator,
            ecfg.kv_page_size,
            max_nodes=max(0, ecfg.prefix_cache_entries),
            spill=self._spill_tier,
            governor=self._governor,
        )
        # Declared shared-prefix heads observed while serving (token tuple
        # -> tenant), bounded: the warm-restart snapshot records them.
        self._declared_heads: "OrderedDict[tuple, str]" = OrderedDict()  # mcpx: owner[engine-worker]
        # Snapshot heads awaiting their lazy post-restart rebuild (only
        # used when a snapshot carried ids but its KV could not be
        # restored): (ids tuple, tenant), consumed on first matching use.
        self._warm_heads: list[tuple[tuple, str]] = []  # mcpx: owner[engine-worker]
        # Last-synced spill counters -> Prometheus (delta fold, exactly
        # like _prefix_seen below).
        self._spill_seen = {  # mcpx: owner[engine-worker]
            "spills": 0, "readmits": 0, "destructive_evictions": 0,
            "host_evictions": 0, "denied_readmits": 0,
        }
        # Last-synced cache counters -> Prometheus (the worker folds deltas
        # into mcpx_kv_prefix_* once per iteration, so the cache itself
        # stays metrics-free and single-purpose).
        self._prefix_seen = {  # mcpx: owner[engine-worker]
            "hits": 0, "misses": 0, "evictions": 0, "matched_tokens": 0,
        }
        # (dispatches, tokens) of the declared-head build in flight: what
        # its engine.prefix_build span reports.
        self._prefix_built = (0, 0)  # mcpx: owner[engine-worker]
        self._prefill_buckets = tuple(
            b
            for b in (64, 128, 256, 512, 768, 1024, 1536, 2048)
            if b <= self.model_cfg.max_seq_len and b % ecfg.kv_page_size == 0
        )
        if not self._prefill_buckets:
            raise EngineError(
                f"no usable prefill bucket <= max_seq_len={self.model_cfg.max_seq_len} "
                f"that is a multiple of kv_page_size={ecfg.kv_page_size}"
            )
        # DFA tables enter the jitted decode as ARGUMENTS (padded shapes,
        # grammar.device_tables()), so per-registry grammars swap without
        # recompiling; recompiles happen only when a pad bucket changes.
        # Unconstrained sampling still needs one vocab-shaped mask: ids past
        # the tokenizer's real vocab are MXU padding whose logits are
        # ordinary numbers (a zero-padded converted checkpoint gives them
        # logit exactly 0), and PAD itself must never be sampled.
        n_real = getattr(self.tokenizer, "n_real", self.tokenizer.vocab_size)
        um = np.zeros((self.tokenizer.vocab_size,), bool)
        um[:n_real] = True
        um[self.tokenizer.pad_id] = False
        self._unconstrained_mask = jnp.asarray(um)
        # Draftable vocab for FREE rows under speculative decoding: the
        # unconstrained mask minus EOS — a stop must come from the verified
        # sample (where done/state bookkeeping handles it), never ride in
        # as an accepted draft.
        um_free = um.copy()
        um_free[self.tokenizer.eos_id] = False
        self._draft_free_mask = jnp.asarray(um_free)
        # Speculative-decoding accounting (worker-writes, queue_stats
        # reads): running drafted/accepted totals per row class, swapped in
        # whole like _pending_stats.
        self._spec_totals = {  # mcpx: owner[engine-worker, atomic]
            "drafted_constrained": 0,
            "accepted_constrained": 0,
            "drafted_free": 0,
            "accepted_free": 0,
        }
        self._spec_window_degraded_logged = False
        # Roofline cost observatory (telemetry/costs.py): per-executable
        # XLA cost accounting + the mcpx_engine_compiles_total retrace
        # sentinel. Created here (not _setup) so GET /costs can read an
        # empty snapshot from a cold/warming engine.
        self.costs = CostRegistry(
            metrics=self.metrics,
            enabled=self.config.telemetry.cost_accounting,
            startup=self.startup,
        )
        # Decode-loop host profiler (telemetry/flight.py): per-iteration
        # phase timers tiling the worker loop's wall time into named
        # phases on the spans' clock, surfaced via
        # queue_stats()["worker_profile"] and the per-segment attributes
        # of engine.segment spans. On with tracing (whose spans carry it)
        # or profile_worker; None = zero clock reads on the hot path. One
        # can be attached to a LIVE engine (the worker re-reads the field
        # each iteration, so an attach/detach lands at the next tick).
        self._profiler: Optional[WorkerProfiler] = (  # mcpx: owner[engine-worker, atomic]
            WorkerProfiler()
            if self.config.tracing.enabled
            or self.config.telemetry.flight.profile_worker
            else None
        )
        # Per-request cost ledger (telemetry/ledger.py): while on, the
        # worker fills the slab's per-row bill accumulators and attaches
        # an itemized bill dict to every GenerateResult. Off (default) no
        # accumulator is ever written and GenerateResult.bill stays None
        # (pass-through parity). Re-read from config each worker decision
        # point so it can be flipped on a LIVE engine like the profiler.
        self._ledger_totals = {  # mcpx: owner[engine-worker, atomic]
            "flops": 0.0, "bytes": 0.0, "by_executable": {},
        }

    @property
    def _ledger_on(self) -> bool:
        return bool(self.config.telemetry.ledger.enabled)

    def ledger_totals(self) -> dict:
        """Cross-thread snapshot of everything the ledger has apportioned
        (GIL-atomic dict swap, queue_stats discipline): total flops/bytes
        handed out to request bills plus the per-executable split — the
        conservation contract's reference side (sum of bills == these
        totals == the cost observatory's harvested per-call costs)."""
        t = self._ledger_totals
        return {
            "flops": t["flops"],
            "bytes": t["bytes"],
            "by_executable": dict(t["by_executable"]),
        }

    def _ledger_account(
        self, entry: Any, name: str, rows: list[int], slab: "_Slab"
    ) -> None:
        """Apportion one harvested executable call's XLA cost equally over
        the rows resident for it (row-residency share) into the per-row
        bill accumulators; accumulate exactly what was handed out into
        the swap-in-whole totals. Worker thread only."""
        if entry is None or not rows:
            return
        entry.ensure()  # lazy AOT materialisation, idempotent per signature
        if entry.flops is None:
            return
        fshare = entry.flops / len(rows)
        bshare = (entry.bytes_accessed or 0.0) / len(rows)
        for i in rows:
            slab.bill_flops[i] += fshare
            slab.bill_bytes[i] += bshare
        t = self._ledger_totals
        by = dict(t["by_executable"])
        by[name] = by.get(name, 0.0) + fshare * len(rows)
        self._ledger_totals = {
            "flops": t["flops"] + fshare * len(rows),
            "bytes": t["bytes"] + bshare * len(rows),
            "by_executable": by,
        }

    # ------------------------------------------------------------- lifecycle
    def _transition(self, to: str) -> bool:
        """Move the lifecycle state machine to ``to`` iff legal from the
        current state (``_ENGINE_STATES``); returns whether the transition
        happened. The lock makes check-and-set atomic across the event loop
        (start/aclose) and any coalescing start() callers — a close that
        lands mid-start wins and stays won (the old bare writes could
        resurrect a closed engine to "ready")."""
        with self._state_lock:
            if to in _ENGINE_STATES.get(self.state, ()):
                self.state = to
                return True
            return False

    async def start(self) -> None:
        """Build mesh, load weights, compile, spin up the worker thread.

        Concurrent callers coalesce: whoever arrives while another start is
        in flight simply waits for it (the server launches startup as a
        background task so /healthz can report "warming"; the first real
        requests then block here until the engine is ready). All state
        writes go through the guarded ``_transition`` — exactly one caller
        wins cold->warming (and starts the worker thread), and a concurrent
        aclose() cannot be overwritten back to "ready"."""
        if self.state == "ready":
            return
        if self.state in ("closed", "failed"):
            raise EngineError(f"engine not startable (state={self.state})")
        if self._transition("warming"):
            self._thread = threading.Thread(
                target=self._worker, daemon=True, name="mcpx-engine"
            )
            self._thread.start()
        while not self._started.is_set():
            await asyncio.sleep(0.02)
        if self._startup_error is not None:
            self._transition("failed")
            raise EngineError(f"engine startup failed: {self._startup_error}")
        self._transition("ready")
        if self.state != "ready":
            # A concurrent aclose() closed the engine mid-start; the
            # transition above lost, and this caller must not serve.
            raise EngineError(f"engine not startable (state={self.state})")
        # Arm the retrace sentinel: compiles during startup/warmup were the
        # expected cold path (logged INFO); from here every new signature
        # is a compile in the SERVING path and logs the WARNING line.
        self.costs.arm()

    async def aclose(self) -> None:
        with self._state_lock:
            self.state = "closed"  # terminal from ANY state, races included
        self._stop = True
        self._queue.put(None)
        if self._thread is not None:
            await asyncio.to_thread(self._thread.join, 5.0)
        if self._thread is None or not self._thread.is_alive():
            # Drop device buffers (weights + KV pools) so a successor engine
            # in the same process can fit in HBM — only once the worker is
            # actually gone (a still-running batch may hold these).
            # thread-ownership: sanctioned cross-thread teardown — the
            # branch guard above proves the worker (the owner) is gone, so
            # there is no concurrent writer left to race.
            if (
                self._spill_tier is not None
                and self.config.engine.kv_tier.snapshot_path
                and self._started.is_set()
                and self._startup_error is None
                and self._params is not None  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            ):
                # CLEAN close: persist the warm-restart snapshot before the
                # pools drop (worker joined — no writer left to race; an
                # unclean close, startup failure, or prior snapshot just
                # skips). An in-flight spill/readmit copy joins here via
                # the tier's blocking drain, so no host buffer leaks and
                # no freed page run is read after the pools die.
                try:
                    self._save_snapshot()
                except Exception:  # noqa: BLE001 - a deploy never hangs on its snapshot
                    log.warning("KV snapshot save failed", exc_info=True)
            if self._spill_tier is not None:
                # Drop pending copy handles + host buffers (post-snapshot):
                # aclose during an in-flight spill must leave no orphaned
                # pinned memory and no dangling device references.
                self._spill_tier.reset()  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._params = None  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._paged_kv = None  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._jit_prefill = None
            self._jit_admit = None
            self._jit_segment = None
            self._jit_suffix_prefill = None
            self._jit_merge = None
            self._jit_admit_merge = None
            self._jit_hetero_admit = None
            self._jit_hetero_segment = None
            self._jit_hetero_segment_spec = None
            self._jit_spill_gather = None
            self._jit_spill_readmit = None
            # Cost registry keeps its compile/cost history readable but
            # drops the cached AOT executables (device programs) so a
            # successor engine fits in HBM.
            self.costs.release()
            self._stack_cache = None  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._inflight.clear()  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._prefill_moe.clear()  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._pending_admissions.clear()  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._dfa_cache.clear()  # mcpx: ignore[thread-ownership] - worker joined (guard above); teardown
            self._prefix_cache.drop_all()  # mcpx: ignore[thread-ownership] - worker joined (guard above); cached KV dies with the pools
        else:
            log.warning(
                "engine worker still alive after %.1fs join timeout; keeping "
                "HBM buffers (weights + KV pools) referenced — a successor "
                "engine in this process may not fit in HBM",
                5.0,
            )

    # ------------------------------------------------------------------ api
    async def generate(
        self,
        prompt_ids: list[int],
        *,
        max_new_tokens: int = 0,
        constrained: bool = True,
        temperature: Optional[float] = None,
        grammar: Optional[PlanGrammar] = None,
        shared_prefix_len: int = 0,
        deadline_at: Optional[float] = None,
        tenant: str = "default",
    ) -> GenerateResult:
        if self.state != "ready":
            raise EngineError(f"engine not ready (state={self.state})")
        ecfg = self.config.engine
        with tracing.span(
            "engine.generate",
            prompt_tokens=len(prompt_ids),
            constrained=constrained,
        ) as esp:
            req = GenerateRequest(
                prompt_ids=list(prompt_ids),
                max_new_tokens=max_new_tokens or ecfg.max_decode_len,
                constrained=constrained,
                temperature=ecfg.temperature if temperature is None else temperature,
                future=asyncio.get_running_loop().create_future(),
                loop=asyncio.get_running_loop(),
                enqueued_at=time.monotonic(),
                grammar=grammar,
                shared_prefix_len=shared_prefix_len if ecfg.prefix_cache else 0,
                deadline_at=deadline_at,
                tenant=tenant or "default",
                span=esp,
                ready_seen=self._t_ready,
                dispatch_seen=self._dispatch_seq,
            )
            self._queue.put(req)
            res = await req.future
            t_resumed = time.monotonic()
            if res.bill is not None:
                # Fold the worker's engine bill into the request's ledger
                # bill (contextvar — this runs back on the request task, so
                # all bill mutation stays on the event loop).
                bill = ledger_mod.current_bill()
                if bill is not None:
                    bill.add_engine(res.bill)
            if esp is not None:
                esp.set(
                    tokens=res.generated_tokens,
                    queue_ms=round(res.queue_ms, 3),
                    prefill_ms=round(res.prefill_ms, 3),
                    decode_ms=round(res.decode_ms, 3),
                    # From the ready stamp of the plan's last segment to
                    # here, back on the event loop: the harvest's per-row
                    # work, call_soon_threadsafe and the loop's wake-up.
                    deliver_ms=round((t_resumed - res.ready_at) * 1e3, 3),
                )
            return res

    async def pin_prefix(self, prompt_ids: list[int]) -> Optional[PrefixNode]:
        """Pin the deepest resident radix-tree node whose path prefixes
        ``prompt_ids`` so eviction cannot reclaim it; returns an opaque
        handle for ``unpin_prefix`` (None when nothing is resident, the
        cache is off, or the engine is not serving). The structured
        ``/plan_and_execute`` program uses this to keep its plan's prompt
        KV warm across tool execution, so a failure-triggered replan
        continues decoding from the cached prefix instead of cold
        re-prefilling."""
        if self.state != "ready" or not self.config.engine.prefix_cache:
            return None
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future[Optional[PrefixNode]]" = loop.create_future()
        self._queue.put(_PinPrefixOp(list(prompt_ids), fut, loop))
        return await fut

    async def warm_grammar(self, grammar: PlanGrammar) -> None:
        """Compile, before traffic needs them, the executables whose shapes
        depend on ``grammar``'s tables: ``_warmup`` covers only the generic
        grammar, and on a big subword vocab a registry trie lands in another
        column bucket. The worker does it between segments
        (``_warm_grammar``); no request is served. Raises what the worker
        raised."""
        if self.state != "ready":
            raise EngineError(f"engine not ready (state={self.state})")
        loop = asyncio.get_running_loop()
        fut: "asyncio.Future[None]" = loop.create_future()
        self._queue.put(_WarmGrammarOp(grammar, fut, loop))
        await fut

    def unpin_prefix(self, handle: Optional[PrefixNode]) -> None:
        """Release a ``pin_prefix`` pin (idempotent for None; fire-and-
        forget — the worker applies it at its next queue drain)."""
        if handle is None or self.state == "closed":
            return
        self._queue.put(_UnpinPrefixOp(handle))

    def prefix_cache_stats(self) -> dict:
        """Cross-thread counter snapshot of the radix prefix cache (the
        ``GET /cache`` surface); ``enabled`` reflects the live config.
        With the tiered cache armed, ``tier`` carries the host-RAM spill
        accounting (resident host tokens/bytes, spills/readmits/
        destructive evictions) and ``governor`` the per-tenant residency
        and hit-rate spread; both are None single-tier."""
        out = {
            "enabled": bool(self.config.engine.prefix_cache),
            **self._prefix_cache.stats(),
            "tier": None,
            "governor": None,
        }
        if self._spill_tier is not None:
            out["tier"] = {"enabled": True, **self._spill_tier.stats()}
        if self._governor is not None:
            out["governor"] = self._governor.stats(self._prefix_cache.max_tokens)
        return out

    def pallas_paths(self) -> dict:
        """Per-path kernel engagement — the honest replacement for the old
        single ``pallas`` boolean (a true flag used to coexist with the
        suffix-prefill path silently forking to jnp for seven PRs). Each
        serving path that dispatches paged attention reports whether IT
        routes through the ragged kernel (``engaged``) and how many times
        it has actually run (``dispatches``); ``reason`` names the
        blocking condition when a path is NOT kernel-routed, or why an
        engaged path is idle (subsystem off) — absence of a reason means
        kernel-routed and armed. Cold-engine safe: the route is resolved
        at __init__ from config + model geometry, and the counters are
        GIL-atomic ints."""
        ecfg = self.config.engine
        on = bool(self._use_pallas)
        if not ecfg.use_pallas:
            blocked = "engine.use_pallas=false (config)"
        elif not on:
            mc = self.model_cfg
            what = f"cache widths {mc.kv_widths}" if mc.latent else f"head_dim {mc.head_dim}"
            blocked = (
                f"{what} % 128 != 0: Mosaic "
                "lane tiling rejects the kernel on hardware "
                "(engine.interpret=true lifts the constraint off-TPU)"
            )
        else:
            blocked = None
        d = self._pallas_dispatches

        def path(name: str, idle: Optional[str]) -> dict:
            return {
                "engaged": on,
                "dispatches": d[name],
                "reason": blocked if not on else idle,
            }

        more = {}
        if self.model_cfg.hybrid and not self.model_cfg.conv_ffn:
            # The recurrent layers' window kernel (kernels/ssm.py): every
            # decode segment of such a model runs it, where ONE device holds
            # the state pool.
            one = self._mesh is None or self._mesh.size == 1
            more["ssm"] = {
                "engaged": on and one,
                "dispatches": d["ssm"],
                "reason": blocked if not on else (
                    None if one else "the state pool's kernel runs on one device; a mesh takes the jnp form"
                ),
            }
        if self.model_cfg.n_block_layers:
            # Block-selecting attention: a decode window's calls run the
            # ragged kernel over each (slot, KV head)'s own page list, where
            # a row's table can hold a block a query drops.
            from mcpx.models.gemma.sparse import selects

            wide = selects(self.model_cfg, ecfg.max_pages_per_seq * ecfg.kv_page_size)
            more["gather"] = {
                "engaged": on and wide,
                "dispatches": d["gather"],
                "reason": blocked if not on else (
                    None if wide else "a row's pages hold no more than the blocks every query keeps"
                ),
            }
        return {
            "enabled": on,
            "interpret": bool(ecfg.interpret),
            "reason": blocked,
            "paths": {
                **more,
                "decode": path("decode", None),
                "prefill": path(
                    "prefill",
                    "idle: prefix_cache=off (no suffix prefills)"
                    if not ecfg.prefix_cache
                    else "idle: a model with recurrent layers prefills whole (no suffix prefills)"
                    if not self.model_cfg.suffix_route
                    else None,
                ),
                "spec_verify": path(
                    "spec_verify",
                    None
                    if self._spec_k() > 0
                    else "idle: speculative decoding off",
                ),
            },
        }

    def queue_stats(self) -> dict:
        """Cross-thread snapshot of engine load for the serving scheduler
        (mcpx/scheduler/): how many requests wait unadmitted, how many slab
        rows are live, and an ETA (seconds) for a request joining the queue
        NOW. The ETA is fair-share arithmetic over the service-time EWMA —
        queued requests drain ``max_batch_size`` at a time, plus one extra
        service interval when the slab is already full (the joiner waits
        for a drain before its cohort can even admit). All reads are
        GIL-atomic scalars; approximate by design (the worker thread owns
        the truth)."""
        slab = getattr(self, "_slab", None)
        active = slab.n_active if slab is not None else 0
        depth = self._queue.qsize()
        B = max(1, self.config.engine.max_batch_size)
        svc = self._ewma_service_s
        # Queued requests that fit the slab's free rows admit at the next
        # segment boundary (ms) — only the OVERFLOW waits out service
        # drains, batch-at-a-time.
        overflow = max(0, depth - max(0, B - active))
        eta = math.ceil(overflow / B) * svc
        if active >= B:
            eta += svc
        # Per-class backlog + head-of-line age over the WORKER's pending
        # line (requests drained from the queue but not yet admitted — the
        # population drain-to-switch used to starve), published by the
        # worker each iteration; ``depth`` above counts the pre-drain queue.
        ps = self._pending_stats
        # Speculative-decoding acceptance (grammar-aware drafter): running
        # accept rates overall and split by row class — the split is the
        # design claim ("acceptance stays high exactly where decode is
        # slowest") made observable. All zeros while speculation is off.
        sp = self._spec_totals
        drafted = sp["drafted_constrained"] + sp["drafted_free"]
        accepted = sp["accepted_constrained"] + sp["accepted_free"]
        # Prefix scoreboard (radix KV cache): resident-tree size and hit
        # rates — what the locality-aware admission sort is working with,
        # published for the serving scheduler and /healthz.
        ps_pfx = self._prefix_cache.stats()
        tier = self._spill_tier
        # Decode-loop host profile (telemetry/flight.py): present ONLY
        # while a profiler is attached, so the disabled-mode queue_stats
        # payload stays byte-identical (recorder-off parity contract).
        prof = self._profiler
        extra = (
            {
                "worker_profile": {
                    **prof.snapshot(),
                    # Rows admitted while a segment was held for them (the
                    # "hold" phase's seconds are among the phases).
                    "hold_joined_rows": self._hold_joined_total,
                    # Forwards asked for over all dispatches and the
                    # ceilings they were chosen under: their ratio is how
                    # far the pacer shortens the segment.
                    "window_forwards": self._window_total,
                    "window_max_forwards": self._window_max_total,
                    **self._layer_kind_totals,
                    **(
                        {
                            "prefix_state_miss": self._prefix_state_misses,
                            **(
                                {"prefix_state_hit": self._prefix_state_hits}
                                if self.model_cfg.head_state or self.model_cfg.page_state else {}
                            ),
                        }
                        if self.model_cfg.hybrid else {}
                    ),
                }
            }
            if prof is not None
            else {}
        )
        return {
            **extra,
            # Mesh axes and weight placement (source, wall of the draw,
            # bytes per device); absent until _setup has placed the tree.
            **self._placement,
            # Per-path ragged-kernel engagement (decode / suffix-prefill /
            # spec-verify): route + dispatch counts + blocking reason, so
            # the scheduler, /healthz watchers and the chip benchmark all
            # read the SAME per-path truth (ISSUE 15 satellite — a single
            # boolean used to mask the suffix-prefill jnp fork).
            "pallas": self.pallas_paths(),
            "prefix_nodes": ps_pfx["nodes"],
            "prefix_resident_pages": ps_pfx["resident_pages"],
            "prefix_hit_rate": ps_pfx["hit_rate"],
            "prefix_token_hit_rate": ps_pfx["token_hit_rate"],
            # Tiered-cache scoreboard (zeros single-tier): host-resident
            # pages and the spill/readmit/destructive-eviction tallies the
            # prefix-affinity router and /healthz watch.
            "prefix_host_pages": ps_pfx["host_pages"],
            "prefix_spills": tier.spills if tier is not None else 0,
            "prefix_readmits": tier.readmits if tier is not None else 0,
            "prefix_destructive_evictions": (
                tier.destructive_evictions if tier is not None else 0
            ),
            "depth": depth,
            "active": active,
            "service_ewma_s": svc,
            "eta_s": eta,
            "depth_constrained": ps["constrained"],
            "depth_free": ps["free"],
            "hol_wait_ms": ps["hol_wait_ms"],
            "resident_grammars": sum(
                1 for k in range(1, len(self._dfa_slot_refs))
                if self._dfa_slot_refs[k] > 0
            ),
            "spec_accept_rate": accepted / drafted if drafted else 0.0,
            "spec_accept_rate_constrained": (
                sp["accepted_constrained"] / sp["drafted_constrained"]
                if sp["drafted_constrained"]
                else 0.0
            ),
            "spec_accept_rate_free": (
                sp["accepted_free"] / sp["drafted_free"]
                if sp["drafted_free"]
                else 0.0
            ),
        }

    # ------------------------------------------------------------ internals
    def _mesh_axes(self, n_devices: int) -> tuple[int, int]:
        """(data, model) axis sizes. Config 0 = auto: cover every device,
        TP over the largest head-dividing factor, but keep a data axis ≥ 2
        when possible (2×4 on a v5e-8 with 8-head Gemma-2B) so throughput
        scales with replicas, not just per-batch latency."""
        ecfg = self.config.engine
        if ecfg.model_axis > 0 or ecfg.data_axis > 0:
            # Explicit axes are clamped to the device count; an axis left at
            # 0 (auto) alongside an explicit one absorbs the remaining
            # devices rather than collapsing to 1.
            if ecfg.model_axis > 0:
                model = min(ecfg.model_axis, n_devices)
                data = (
                    min(ecfg.data_axis, max(1, n_devices // model))
                    if ecfg.data_axis > 0
                    else max(1, n_devices // model)
                )
            else:
                data = min(ecfg.data_axis, n_devices)
                model = max(1, n_devices // data)
            return data, model
        model = math.gcd(n_devices, self.model_cfg.n_heads)
        if model == n_devices and model > 1:
            # Leave a data axis: shrink model by its smallest prime factor so
            # data*model still covers every device (//2 would strand devices
            # on odd counts, e.g. 9 -> 4x2 over 8 of 9).
            spf = next(p for p in range(2, model + 1) if model % p == 0)
            model //= spf
        return n_devices // model, model

    def _named(self, spec: P) -> NamedSharding:
        return NamedSharding(self._mesh, spec)

    def _row_spec(self, n: int, extra_dims: int = 0) -> P:
        """PartitionSpec for an [n, ...] batch-major array: shard the leading
        dim over ``data`` when it divides, replicate otherwise."""
        from mcpx.parallel.mesh import DATA_AXIS, _axis

        return P(_axis(self._mesh, DATA_AXIS, n), *([None] * extra_dims))

    def _setup(self) -> None:
        from mcpx.parallel.mesh import make_mesh
        from mcpx.utils.backend import enable_compilation_cache

        # The timeline's phases from here on tile the worker's start-up:
        # backend -> weights -> pools -> warmup (telemetry/startup.py).
        phase = self.startup.phase
        self.startup.end_build()
        with phase("startup.backend"):
            # Startup compiles dozens of bucket executables; the persistent
            # cache makes every start after the first load them instead. What
            # the cache holds NOW (empty? at its cap?) is the first thing a
            # cold start's log should say.
            log.info(
                "compilation cache: dir=%(dir)s files=%(files)d bytes=%(bytes)d "
                "max_bytes=%(max_bytes)d", self.startup.note_cache(enable_compilation_cache()),
            )
            # _use_pallas was resolved in __init__ (config + head-dim probe) so
            # the cold-engine observability surfaces could already report it;
            # nothing at setup time changes the verdict.
            if self._mesh is None:
                data_axis, model_axis = self._mesh_axes(len(jax.devices()))
                self._mesh = make_mesh(data=data_axis, model=model_axis)
        # quantize="int8" (models/gemma/quant.py): the random-init path
        # quantizes each leaf at creation so the full-precision tree never
        # exists (7B-int8 on one 16 GB chip); checkpoints quantize after
        # restore — see load_or_init's documented limitation.
        with phase("startup.weights") as weights:
            self._params, source = load_or_init(
                self.model_cfg,
                self.config.model.checkpoint_path,
                self._mesh,
                quantize=self.config.model.quantize,
            )
            jax.block_until_ready(self._params)
        # One pair of stamps, two names: the phase's wall is the gauge's.
        with phase("startup.pools"):
            self._setup_pools(source, weights.t1 - weights.t0)
        if self.config.engine.warmup_compile:
            self._warmup()

    def _setup_pools(self, source: str, init_s: float) -> None:
        """``_setup`` between the weights and the warm-up: what was placed
        where, the KV and state pools, every jit wrapper, the slab."""
        ecfg = self.config.engine
        if self.model_cfg.dense_pattern:
            # No routed expert: every leaf is read whole, but an embedding
            # that is not the head too (a forward gathers a few of its rows).
            held = sum(a.nbytes for a in jax.tree.leaves(self._params))
            tied = self.model_cfg.tie_embeddings
            self._weight_bytes = (0, held - (0 if tied else self._params["embed"].nbytes))
        if self.model_cfg.conv_ffn:
            # What a forward reads of the short-convolution mixers, whole.
            self._conv_weight_bytes = sum(
                a.nbytes for a in jax.tree.leaves(self._params.get("conv_layers", {}))
            )
        if self.model_cfg.n_experts:
            self._weight_bytes = forward_weight_bytes(self.model_cfg, self._params)
            # One sample an expert held, from 0: an expert no token ever
            # chooses still counts in a reader's mean.
            first = self.model_cfg.expert_first
            for e in range(first, first + self.model_cfg.n_experts_held):
                self.metrics.moe_expert_tokens.labels(expert=str(e))
        # How the weights were placed, for /healthz and /metrics: the wall
        # of the draw (or restore) and what each device now holds.
        held = bytes_per_device(self._params)
        self.metrics.weights_init_seconds.set(init_s)
        for dev, n_bytes in held.items():
            self.metrics.weights_bytes.labels(device=dev).set(n_bytes)
        self._placement = {
            "mesh": {k: int(v) for k, v in self._mesh.shape.items()},
            "weights": {
                "source": source,
                "init_s": round(init_s, 3),
                "bytes_per_device": held,
            },
        }
        self._paged_kv = self._init_pools()
        self._state_pool = self._init_state_pool()
        if self.model_cfg.scan_ffn:
            # The fourth kind, for /healthz: the slots' states, tails and
            # pending windows, every array stacked a layer.
            self._placement["state_pool"] = {
                "bytes": sum(a.nbytes for a in jax.tree.leaves(self._state_pool)),
                "state_bytes": self._state_pool["ssm"].nbytes,
                "slots": int(self._state_pool["n"].shape[0]),
            }
        if self.model_cfg.page_state:
            # The third kind of per-row state, for /healthz: the slots' tails
            # and pending windows, and the tail a page.
            tails = self._state_pool["tails"].nbytes
            self._placement["state_pool"] = {
                "bytes": sum(a.nbytes for a in jax.tree.leaves(self._state_pool)),
                "page_tails_bytes": tails,
                "slots": int(self._state_pool["n"].shape[0]),
            }
        # Every jitted executable goes through the cost registry
        # (telemetry/costs.py): one AOT compile per signature harvests
        # XLA's cost_analysis() and increments the
        # mcpx_engine_compiles_total{executable} retrace sentinel; the
        # compiled executable then serves directly. cost_accounting=false
        # returns the jitted callables unwrapped (pass-through).
        wrap = self.costs.wrap
        self._jit_prefill = wrap(
            "prefill",
            jax.jit(
                self._prefill_impl,
                static_argnames=("T",),
                donate_argnames=("paged_k", "paged_v", "state"),
            ),
            static_argnames=("T",),
        )
        self._jit_admit = wrap(
            "admit",
            jax.jit(self._admit_impl, static_argnames=("temperature", "constrained")),
            static_argnames=("temperature", "constrained"),
        )
        self._jit_suffix_prefill = wrap(
            "suffix_prefill",
            jax.jit(
                self._suffix_prefill_impl, donate_argnames=("paged_k", "paged_v", "state")
            ),
        )
        # out_buf is NOT donated: the pipelined worker reads a LAGGED
        # segment's out_buf after newer segments were already dispatched —
        # donation would invalidate the handle it still has to fetch. The
        # copy is [B, steps] int32, noise next to the KV pools. ``iters``,
        # the segment's length, is a device operand (an int32 scalar in
        # the while_loop's condition), not a static: the pacer chooses it
        # at every dispatch and ONE executable serves every length.
        self._jit_segment = wrap(
            "segment",
            jax.jit(
                self._segment_impl,
                static_argnames=("chunk", "temperature", "constrained", "draft"),
                donate_argnames=("paged_k", "paged_v", "state"),
            ),
            static_argnames=("chunk", "temperature", "constrained", "draft"),
        )
        # Merges donate NOTHING: their inputs are the newest segment's
        # output handles, which the newest in-flight entry still needs
        # readable.
        self._jit_merge = wrap("merge", jax.jit(self._merge_impl))
        self._jit_admit_merge = wrap("admit_merge", jax.jit(self._admit_merge_impl))
        # Heterogeneous batching executables: temperature/constrained are
        # DEVICE VECTORS here, not static args, and the grammar arrives as a
        # stacked [G, S, C] table set indexed by a per-row dfa_id — so ONE
        # admit and ONE segment executable serve every sampling config and
        # every resident-grammar combination (the executable count is
        # independent of how many grammars are resident; acceptance
        # criterion of the hetero refactor).
        self._jit_hetero_admit = wrap(
            "hetero_admit", jax.jit(self._hetero_admit_impl)
        )
        self._jit_hetero_segment = wrap(
            "hetero_segment",
            jax.jit(
                self._hetero_segment_impl,
                static_argnames=("chunk",),
                donate_argnames=("paged_k", "paged_v"),
            ),
            static_argnames=("chunk",),
        )
        # Grammar-aware speculative decoding (engine/speculative.py): the
        # drafter-propose + one-forward-verify segment. K and the draft
        # mode are config statics (ONE executable per config), never
        # per-acceptance — variable accepted lengths are data.
        self._jit_hetero_segment_spec = wrap(
            "hetero_segment_spec",
            jax.jit(
                self._hetero_segment_spec_impl,
                static_argnames=("iters", "K", "draft"),
                donate_argnames=("paged_k", "paged_v"),
            ),
            static_argnames=("iters", "K", "draft"),
        )
        if self._spill_tier is not None:
            # Tiered KV cache: the device<->host page-run copies. One
            # gather and one scatter executable per page-count bucket
            # (run lengths pad up to a power of two); the scatter donates
            # the pools exactly like prefill — the readmitted data is
            # device-ordered ahead of any dispatch that reads it.
            self._jit_spill_gather = wrap(
                "spill_gather", jax.jit(self._spill_gather_impl)
            )
            self._jit_spill_readmit = wrap(
                "spill_readmit",
                jax.jit(
                    self._spill_readmit_impl,
                    donate_argnames=("paged_k", "paged_v"),
                ),
            )
            self._spill_tier.bind(
                self._spill_gather_dispatch,
                self._spill_readmit_dispatch,
                self.model_cfg.kv_bytes_per_token + self.model_cfg.index_bytes_per_token,
            )
        # GET /costs sets the registry's numbers against datasheet peaks:
        # an accelerator missing from the table fails start-up here, not
        # at the first scrape (the CPU backend has none and says so).
        device_peaks()
        if ecfg.speculative.enabled and ecfg.hetero_batch:
            # The verify window samples [B, K+1]-shaped draws each forward;
            # with the default non-partitionable threefry every mesh device
            # redundantly generates the FULL bit tensor (measured ~2x the
            # whole segment on the CPU proxy). Partitionable threefry
            # shards bit generation with the data. Process-global and
            # one-way by design: flipped only when speculation is armed, so
            # a speculation-off engine keeps byte-identical streams.
            try:
                jax.config.update("jax_threefry_partitionable", True)
            except Exception as e:  # noqa: BLE001 - perf knob, never fatal
                log.warning("jax_threefry_partitionable unavailable: %s", e)
        if ecfg.speculative.enabled and not ecfg.hetero_batch:
            # Same loud-interaction convention as draft_mode below: the
            # drafter's grammar pre-filter indexes the PER-ROW stacked DFA
            # tables, which only the heterogeneous slab carries.
            log.warning(
                "speculative.enabled without hetero_batch has no effect: "
                "the grammar-aware drafter needs the per-row stacked DFA "
                "tables — set engine.hetero_batch=true to speculate"
            )
        if ecfg.hetero_batch and ecfg.draft_mode == "prompt":
            # Not a validation error — both knobs default sensibly on their
            # own — but the interaction must be loud: an operator flipping
            # hetero_batch on keeps DFA fast-forward speculation yet loses
            # prompt-lookup drafts, which can slow a single-config workload.
            log.warning(
                "hetero_batch=on disables draft_mode='prompt' speculation "
                "(the heterogeneous segment is single-executable and its "
                "proposal chain is single-grammar); grammar fast-forward "
                "still applies per row — set draft_mode='off' to silence"
            )
        self._trivial_grammar = build_trivial_grammar(self.tokenizer)
        # Slot 0 = trivial DFA (unconstrained rows); slot 1 pre-seeded with
        # the engine's generic plan grammar so warmup's stack matches the
        # common serving stack and default-grammar admissions never rebuild.
        n_slots = max(2, ecfg.hetero_grammar_slots)
        self._dfa_slots = [self._trivial_grammar, self.grammar] + [None] * (
            n_slots - 2
        )
        self._dfa_slot_refs = [0] * n_slots
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        fitting = [b for b in self._prefill_buckets if b <= capacity]
        self._slab = _Slab(
            ecfg.max_batch_size,
            ecfg.max_decode_len,
            ecfg.max_pages_per_seq,
            self.tokenizer.pad_id,
            # Draft-lookup prompt buffer: sized to the largest admittable
            # prefill bucket (suffix tokens only; the shared-prefix header
            # is fixed boilerplate with nothing worth drafting from).
            prompt_cap=max(fitting) if fitting else 1,
            # Recurrent drafter hidden width = the model width (the state
            # is scored against the tied unembedding).
            draft_dim=self.model_cfg.d_model,
        )
        if self._spill_tier is not None and ecfg.kv_tier.snapshot_path:
            self._load_snapshot()

    def _dfa_for(self, grammar: PlanGrammar) -> tuple:
        """Device copies of a grammar's tables (``PlanGrammar.device_tables``:
        trans, mask, dist_succ, active ids, eos columns, inverse columns),
        padded to the engine's state bucket and replicated over the mesh.
        Cached per (grammar, pad) so every segment using this grammar shares
        one HBM copy; the cache holds the grammar object so an id() can't be
        reused by a new grammar while its tables are still cached."""
        pad = self._grammar_pad()
        key = (id(grammar), pad)
        hit = self._dfa_cache.get(key)
        if hit is not None:
            self._dfa_cache.move_to_end(key)
            return hit[1]
        tables = tuple(
            jax.device_put(t, self._named(P())) for t in grammar.device_tables(pad)
        )
        self._dfa_cache[key] = (grammar, tables)
        while len(self._dfa_cache) > 8:
            self._dfa_cache.popitem(last=False)
        return tables

    # --- heterogeneous batching: stacked-DFA slot management ---------------
    def _stacked_dfa(self) -> tuple:
        """Device copies of the resident grammars' tables stacked along a
        leading slot axis ([G, S, C] / [G, S] / [G, C]) for per-row
        ``dfa_id`` indexing inside the hetero segment. Free slots stack the
        trivial DFA so G is a FIXED static shape — swapping a slot's
        occupant re-uploads table DATA but never changes an executable.
        Cached per slot occupancy (grammar identity per slot + pad geometry);
        the cache holds the grammar objects so ids can't be recycled while
        their tables are live. Worker thread only."""
        pad = self._grammar_pad()
        slots = [g if g is not None else self._trivial_grammar for g in self._dfa_slots]
        # The slab latch keeps the spec companions alive through a live
        # flip-off drain: resident spec rows still dispatch the 7-table
        # executable until they retire.
        spec = self._spec_k() > 0 or self._slab.spec
        key = (tuple(id(g) for g in slots), pad, spec)
        if self._stack_cache is not None and self._stack_cache[0] == key:
            return self._stack_cache[2]
        host = stacked_tables(slots, pad)
        if spec:
            # Speculative companions (same slot snapshot, same pad
            # geometry): the precomputed successor-distance table and the
            # token->column inverse map the spec segment's one-gather
            # finishability and vocab-space verify sampling need. Built
            # only when speculation is armed — they double the stack's
            # device footprint.
            host = host + stacked_spec_tables(slots, pad)
        tables = tuple(jax.device_put(t, self._named(P())) for t in host)
        self._stack_cache = (key, tuple(slots), tables)
        return tables

    def _grammar_slot_for(
        self, grammar: PlanGrammar, reserved: set[int]
    ) -> Optional[int]:
        """Stacked-DFA slot for ``grammar``: the slot already holding it, a
        free one, or a reclaimed refs==0 slot — None when every non-trivial
        slot is held by a LIVE grammar (the caller defers the request until
        a resident grammar drains; the only admission-order exception left
        under hetero batching). ``reserved`` protects slots claimed earlier
        in the same cohort (refs are bumped only at row assignment)."""
        for k, g in enumerate(self._dfa_slots):
            if g is grammar:
                return k
        for k in range(1, len(self._dfa_slots)):
            if self._dfa_slots[k] is None and k not in reserved:
                self._dfa_slots[k] = grammar
                return k
        for k in range(1, len(self._dfa_slots)):
            if self._dfa_slot_refs[k] == 0 and k not in reserved:
                self._dfa_slots[k] = grammar
                return k
        return None

    def _drop_row_grammar(self, slab: "_Slab", i: int) -> None:
        """Release row ``i``'s stacked-DFA slot reference (no-op for
        unconstrained rows and when hetero batching never ran). The slot
        keeps its grammar (tables stay warm for re-admission) until a new
        grammar reclaims it at refs == 0."""
        k = int(slab.dfa[i])
        if 0 < k < len(self._dfa_slot_refs) and self._dfa_slot_refs[k] > 0:
            self._dfa_slot_refs[k] -= 1
        self.metrics.resident_grammars.set(
            sum(1 for r in self._dfa_slot_refs[1:] if r > 0)
        )

    def _warmup(self) -> None:
        """Execute one cohort per (A, T) bucket plus one decode segment so
        every HOT executable is compiled before the first real request
        (SURVEY.md §3.4: warmup is a first-class startup phase; without it
        each new bucket costs seconds of XLA compile *inside* the serving
        path). "Hot" = the constrained path at the engine's configured
        temperature — the planner's only path; an unconstrained request or a
        non-default per-request temperature still compiles on first use.
        The segment warms with all rows inactive: the while_loop exits after
        zero iterations, so the cost is compile only.

        The whole of it is the timeline's ``startup.warmup``; its children
        (``warmup.grammar_tables``, a ``warmup.prefill`` a bucket, a
        ``warmup.admit`` a cohort bucket, ``warmup.segment``,
        ``warmup.merge``, ``warmup.cost_table``) tile it."""
        with self.startup.phase("startup.warmup"):
            self._warmup_phases()

    def _warmup_phases(self) -> None:
        phase = self.startup.phase
        ecfg = self.config.engine
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        t_buckets = [
            t
            for t in self._prefill_buckets
            if t <= max(ecfg.warmup_max_len, self._prefill_buckets[0]) and t <= capacity
        ]
        if not t_buckets:
            raise EngineError(
                f"warmup: no prefill bucket fits page capacity {capacity} "
                f"(kv_page_size*max_pages_per_seq); raise one of them"
            )
        with phase("warmup.grammar_tables"):
            dfa = self._dfa_for(self.grammar)
            # Hetero mode warms the stacked executables instead of the legacy
            # per-(temperature, constrained) ones: ONE admit + ONE segment
            # compile covers every sampling config and grammar combination, so
            # the compile count below is independent of what serving later mixes.
            sdfa = self._stacked_dfa() if ecfg.hetero_batch else None
        for A, shapes in self._cohort_table(t_buckets).items():
            last = None
            for T, routes in shapes.items():
                with phase("warmup.prefill", A=A, T=T):
                    last = self._warm_prefill(A, T, routes)
            with phase("warmup.admit", A=A):
                self._warm_admit_merge(A, self._warm_admit(A, last, dfa, sdfa))
        slab = self._slab
        with phase("warmup.segment"):
            self._warm_segment(slab, dfa, sdfa)
        with phase("warmup.merge"):
            # Compile the admission/retirement merge scatter too (row 0 is free,
            # so merging its clear-values is a semantic no-op); the resulting
            # device state equals the host state and stays usable for serving.
            self._dirty_rows.add(0)
            self._dispatch_merge(slab, [])
            jax.block_until_ready(self._paged_kv["k"])
        with phase("warmup.cost_table"):
            # Materialise the cost table for every warmed signature NOW: every
            # one is lowered and compiled a SECOND time (``ExecCost.ensure``;
            # on TPU the compile is a persistent-cache hit, the lowering is
            # not) so that a warmed engine never compiles for accounting in
            # the serving path, extending warmup's no-compiles-while-serving
            # contract to the observatory. What that costs a start is this
            # phase's wall.
            self.costs.snapshot(materialize=True)

    def _cohort_buckets(self, T: int, suffix: bool) -> tuple[int, ...]:
        """``cohort_buckets`` (the module's table) of this engine's slab."""
        ecfg = self.config.engine
        return cohort_buckets(ecfg.max_batch_size, tuple(ecfg.batch_buckets), T, suffix)

    def _cohort_table(self, t_buckets: Sequence[int]) -> dict[int, dict[int, tuple[bool, ...]]]:
        """What a start compiles for admission, read off ``cohort_buckets``:
        ``{A: {T: routes}}`` over ``t_buckets``, a route ``False`` for the
        whole-prompt prefill and ``True`` for the suffix prefill. Every ``A``
        in it gets one admit and one admit-merge besides."""
        ecfg = self.config.engine
        # Shared-prefix serving prefills SUFFIXES through the chunked route.
        # (A model whose recurrent layers have no suffix route never takes
        # it: its rows prefill whole.)
        suffix_route = ecfg.prefix_cache and self.model_cfg.suffix_route
        table: dict[int, dict[int, tuple[bool, ...]]] = {}
        for T in t_buckets:
            for suffix in (False, True) if suffix_route else (False,):
                for A in self._cohort_buckets(T, suffix):
                    shapes = table.setdefault(A, {})
                    shapes[T] = shapes.get(T, ()) + (suffix,)
        return dict(sorted(table.items()))

    def _warm_admit_merge(self, A: int, admit_out: tuple) -> None:
        """Compile the admit-merge executable for cohort bucket ``A``
        (all-dropped scatter: rows filled with B = padding, a semantic
        no-op)."""
        ecfg = self.config.engine
        tok = self.tokenizer
        rs_a = self._row_spec(A)
        rs_a2 = self._row_spec(A, 1)
        self._jit_admit_merge(
            *self._dev_state(self._slab),
            self._put(np.full((A,), self._slab.B, np.int32), rs_a),
            *admit_out,
            self._put(np.zeros((A,), np.int32), rs_a),
            self._put(np.zeros((A,), np.int32), rs_a),
            self._put(
                np.zeros((A, ecfg.max_pages_per_seq), np.int32), rs_a2
            ),
            self._put(
                np.full((A, self._slab.prompt_cap), tok.pad_id, np.int32),
                rs_a2,
            ),
            self._put(np.zeros((A,), np.int32), rs_a),
            self._put(np.full((A,), tok.pad_id, np.int32), rs_a),
            self._put(np.zeros((A,), np.float32), rs_a),
            self._put(np.zeros((A,), bool), rs_a),
            self._put(np.zeros((A,), np.int32), rs_a),
            self._put(
                np.zeros((A, self._slab.hstate.shape[1]), np.float32), rs_a2
            ),
        )

    def _warm_prefill(self, A: int, T: int, routes: Sequence[bool]):
        """Run one all-pad cohort through the prefill executables serving
        dispatches for bucket (A, T) on ``routes`` (``_cohort_table``'s) and
        return its last-position logits handle — the input the first-sample
        admit is compiled against."""
        ecfg = self.config.engine
        tok = self.tokenizer
        tokens = np.full((A, T), tok.pad_id, np.int32)
        seq_lens = np.ones((A,), np.int32)
        # Null page table: scatters land on reserved page 0, which
        # no live sequence ever reads.
        table = np.zeros((A, ecfg.max_pages_per_seq), np.int32)
        last = None
        if False in routes:  # the whole-prompt prefill
            # Every row a padding row: its state slot is out of range, dropped.
            last, k_p, v_p, _, state = self._jit_prefill(
                self._params,
                self._put(tokens, self._row_spec(A, 1)),
                self._put(seq_lens, self._row_spec(A)),
                self._paged_kv["k"],
                self._paged_kv["v"],
                self._put(table, self._row_spec(A, 1)),
                self._state_pool,
                self._cohort_slots(A),
                T=T,
            )
            self._paged_kv = {"k": k_p, "v": v_p}
            self._state_pool = state
        if True in routes:  # the suffix prefill
            last, k_p, v_p, _, self._state_pool = self._jit_suffix_prefill(
                self._params,
                self._put(tokens, self._row_spec(A, 1)),
                self._put(seq_lens, self._row_spec(A)),
                self._put(np.zeros((A,), np.int32), self._row_spec(A)),
                self._put(table, self._row_spec(A, 1)),
                self._paged_kv["k"],
                self._paged_kv["v"],
                self._state_pool,
                # (admission's operands: a state kept a page is read from no slot)
                self._cohort_slots(A) if self.model_cfg.head_state else None,
                self._cohort_slots(A),
            )
            self._paged_kv = {"k": k_p, "v": v_p}
        return last

    def _warm_admit(self, A: int, last, dfa: tuple, sdfa: Optional[tuple]) -> tuple:
        """Compile the first-sample admit for cohort bucket ``A`` against
        ``dfa``'s table shapes (``sdfa``: the stacked tables, hetero mode).
        No row is active, so nothing is sampled into the slab."""
        ecfg = self.config.engine
        key = jax.random.PRNGKey(0)
        rs_a = self._row_spec(A)
        budgets0 = self._put(np.zeros((A,), np.int32), rs_a)
        active0 = self._put(np.zeros((A,), bool), rs_a)
        if ecfg.hetero_batch:
            admit_out = self._jit_hetero_admit(
                *sdfa[:5],
                last,
                budgets0,
                active0,
                self._put(np.zeros((A,), np.float32), rs_a),
                self._put(np.ones((A,), bool), rs_a),
                self._put(np.ones((A,), np.int32), rs_a),
                key,
            )
        else:
            admit_out = self._jit_admit(
                *dfa,
                last,
                budgets0,
                active0,
                key,
                temperature=ecfg.temperature,
                constrained=True,
            )
        return admit_out

    def _warm_segment(self, slab: "_Slab", dfa: tuple, sdfa: Optional[tuple]) -> None:
        """Compile the decode segment(s) against ``dfa``'s table shapes by
        dispatching them over ``slab``, whose rows must all be idle: the
        while_loop exits after zero iterations and the pools come back
        unchanged."""
        ecfg = self.config.engine
        key = jax.random.PRNGKey(0)
        chunk = self._spec_chunk(True)
        # The length is an operand: the ceiling here, any length served.
        iters = np.int32(self._decode_iters(spec=False))
        rs_b = self._row_spec(slab.B)
        rs_b2 = self._row_spec(slab.B, 1)
        if ecfg.hetero_batch:
            out = self._jit_hetero_segment(
                self._params,
                *sdfa[:5],
                *self._put_slab_state(slab),
                self._paged_kv["k"],
                self._paged_kv["v"],
                self._put(slab.out_buf, rs_b2),
                *self._put_many(
                    (slab.temp, rs_b),
                    (slab.cons, rs_b),
                    (slab.dfa, rs_b),
                ),
                key,
                iters=iters,
                chunk=chunk,
            )
            self._paged_kv = {"k": out[5], "v": out[6]}
            if self._spec_k() > 0:
                # Speculation armed: warm ITS segment executable too (the
                # legacy hetero one above stays warm for a live rollback
                # flip — both coexist, like hetero vs homogeneous).
                out = self._jit_hetero_segment_spec(
                    self._params,
                    *sdfa,
                    *self._put_slab_state(slab),
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    *self._put_many(
                        (slab.out_buf, rs_b2),
                        (slab.temp, rs_b),
                        (slab.cons, rs_b),
                        (slab.dfa, rs_b),
                        (slab.hstate, rs_b2),
                    ),
                    key,
                    iters=self._decode_iters(spec=True),
                    K=self._spec_k(),
                    draft=ecfg.speculative.draft,
                )
        else:
            out = self._jit_segment(
                self._params,
                *dfa,
                *self._put_slab_state(slab),
                self._paged_kv["k"],
                self._paged_kv["v"],
                *self._put_many(
                    (slab.out_buf, rs_b2),
                    (slab.prompt_toks, rs_b2),
                    (slab.prompt_lens, rs_b),
                    (slab.prev, rs_b),
                ),
                key,
                self._state_pool,
                iters=iters,
                chunk=chunk,
                temperature=ecfg.temperature,
                constrained=True,
                draft=ecfg.draft_mode == "prompt",
            )
            self._state_pool = out[-1]
        self._paged_kv = {"k": out[5], "v": out[6]}

    def _warm_grammar(self, grammar: PlanGrammar) -> None:
        """``warm_grammar`` on the worker: the executables whose shapes
        depend on the grammar's tables are the first-sample admit (one per
        cohort bucket) and the decode segment. Each is dispatched directly
        with idle inputs, exactly as ``_warmup`` does for the generic
        grammar, so what gets compiled does not depend on how a burst of
        requests happens to be gathered into cohorts. Hetero mode has
        nothing to do: its stacked tables have one fixed shape."""
        if self.config.engine.hetero_batch:
            return
        dfa = self._dfa_for(grammar)
        for A, shapes in self._cohort_table(self._prefill_buckets).items():
            # Any of the bucket's prefills gives the admit its logits' shape.
            T, routes = next(iter(shapes.items()))
            self._warm_admit(A, self._warm_prefill(A, T, routes[:1]), dfa, None)
        # Resident rows keep decoding afterwards: the segment is compiled
        # over an idle twin of the live slab, never over its state.
        live = self._slab
        idle = _Slab(
            live.B, live.steps, live.page_table.shape[1], live.pad_id,
            prompt_cap=live.prompt_cap, draft_dim=live.hstate.shape[1],
        )
        self._warm_segment(idle, dfa, None)
        jax.block_until_ready(self._paged_kv["k"])

    def _put(self, x, spec: P):
        return jax.device_put(x, self._named(spec))

    def _put_many(self, *pairs):
        """One ``jax.device_put`` for several (array, spec) pairs: a single
        host dispatch instead of one per array. The admission and merge
        paths each upload a handful of small row arrays, and every separate
        dispatch is host time that async admission then serialises into the
        serving loop."""
        arrs = tuple(a for a, _ in pairs)
        shardings = tuple(self._named(s) for _, s in pairs)
        return jax.device_put(arrs, shardings)

    def _put_slab_state(self, slab: "_Slab") -> tuple:
        """Upload the slab's per-row arrays (cur, pos, st, emitted, done,
        budgets, page_table) in one device_put."""
        rs = self._row_spec(slab.B)
        rs2 = self._row_spec(slab.B, 1)
        return self._put_many(
            (slab.cur, rs),
            (slab.pos, rs),
            (slab.st, rs),
            (slab.emitted, rs),
            (slab.done, rs),
            (slab.budgets, rs),
            (slab.page_table, rs2),
        )

    def _dev_state(self, slab: "_Slab") -> tuple:
        """The device-resident slab state tuple — indices 0..7 are (cur,
        pos, st, emitted, done, budgets, page_table, out_buf); 8..10 the
        draft-lookup state (prompt_toks, prompt_lens, prev); 11..13 the
        per-row sampling config (temperature, constrained, dfa_id —
        heterogeneous batching; scattered but unread when hetero_batch is
        off); 14 the recurrent drafter state (speculative decoding;
        scattered but unread when speculation is off). Initialised from
        the host arrays (startup / after a failure reset) when absent."""
        if slab.dev is None:
            rs = self._row_spec(slab.B)
            rs2 = self._row_spec(slab.B, 1)
            slab.dev = self._put_slab_state(slab) + self._put_many(
                (slab.out_buf, rs2),
                (slab.prompt_toks, rs2),
                (slab.prompt_lens, rs),
                (slab.prev, rs),
                (slab.temp, rs),
                (slab.cons, rs),
                (slab.dfa, rs),
                (slab.hstate, rs2),
            )
        return slab.dev

    def _merge_impl(
        self,
        cur,
        pos,
        st,
        e,
        done,
        budgets,
        pt,
        buf,
        ptoks,
        plens,
        prev,
        temp,
        cons,
        dfa,
        hst,
        rows,
        cur_v,
        pos_v,
        st_v,
        e_v,
        done_v,
        budgets_v,
        pt_v,
        buf_v,
        ptoks_v,
        plens_v,
        prev_v,
        temp_v,
        cons_v,
        dfa_v,
        hst_v,
    ):
        """Scatter per-row values into the slab's device state: row
        ``rows[j]`` takes the j-th value of every value array. This is how
        the host mutates rows WITHOUT a materialize round trip — admitted
        rows get their post-prefill state, retired rows get done=True and a
        zeroed page-table row (decode writes land on the reserved null
        page). ``rows[j] == B`` entries are padding, dropped by the scatter
        — one executable serves every merge size."""
        return (
            cur.at[rows].set(cur_v, mode="drop"),
            pos.at[rows].set(pos_v, mode="drop"),
            st.at[rows].set(st_v, mode="drop"),
            e.at[rows].set(e_v, mode="drop"),
            done.at[rows].set(done_v, mode="drop"),
            budgets.at[rows].set(budgets_v, mode="drop"),
            pt.at[rows].set(pt_v, mode="drop"),
            buf.at[rows].set(buf_v, mode="drop"),
            ptoks.at[rows].set(ptoks_v, mode="drop"),
            plens.at[rows].set(plens_v, mode="drop"),
            prev.at[rows].set(prev_v, mode="drop"),
            temp.at[rows].set(temp_v, mode="drop"),
            cons.at[rows].set(cons_v, mode="drop"),
            dfa.at[rows].set(dfa_v, mode="drop"),
            hst.at[rows].set(hst_v, mode="drop"),
        )

    def _admit_merge_impl(
        self,
        cur,
        pos,
        st,
        e,
        done,
        budgets,
        pt,
        buf,
        ptoks,
        plens,
        prev,
        temp,
        cons,
        dfa,
        hst,
        rows,
        cur0,
        st0,
        done0,
        pos_v,
        budgets_v,
        pt_v,
        ptoks_v,
        plens_v,
        prev_v,
        temp_v,
        cons_v,
        dfa_v,
        hst_v,
    ):
        """Scatter a freshly-prefilled admission cohort into the device slab
        state with ZERO host fetches: ``cur0``/``st0``/``done0`` are
        ``_admit_impl``'s output handles, chained device-to-device. Rows
        whose first sample was already EOS (``done0``) enter with emitted=0
        and retire empty at their first harvest. ``rows[j] == B`` entries
        (bucket padding / inactive lanes) are dropped by the scatter.
        ``ptoks_v`` [A, prompt_cap] / ``plens_v`` / ``prev_v`` seed the
        draft-lookup prompt buffer (host-padded to the static buffer
        width, so this executable stays per-A, not per-(A, T))."""
        pad = self.tokenizer.pad_id
        W = buf.shape[1]
        A = rows.shape[0]
        e0 = jnp.where(done0, 0, 1).astype(jnp.int32)
        buf = buf.at[rows].set(
            jnp.full((A, W), pad, jnp.int32), mode="drop"
        )
        buf = buf.at[rows, 0].set(cur0, mode="drop")
        return (
            cur.at[rows].set(cur0, mode="drop"),
            pos.at[rows].set(pos_v, mode="drop"),
            st.at[rows].set(st0, mode="drop"),
            e.at[rows].set(e0, mode="drop"),
            done.at[rows].set(done0, mode="drop"),
            budgets.at[rows].set(budgets_v, mode="drop"),
            pt.at[rows].set(pt_v, mode="drop"),
            buf,
            ptoks.at[rows].set(ptoks_v, mode="drop"),
            plens.at[rows].set(plens_v, mode="drop"),
            prev.at[rows].set(prev_v, mode="drop"),
            temp.at[rows].set(temp_v, mode="drop"),
            cons.at[rows].set(cons_v, mode="drop"),
            dfa.at[rows].set(dfa_v, mode="drop"),
            hst.at[rows].set(hst_v, mode="drop"),
        )

    def _poll_admissions(self, slab: "_Slab") -> None:
        """Resolve pending admission chains whose device work has finished
        (non-blocking ``is_ready`` checks, FIFO — device order means a
        not-ready head implies a not-ready tail). Sets the cohort's
        prefill time and the start-of-decode timestamp; both are observed
        at most one tick late, which is noise next to the blocking fetch
        this replaces."""
        now = time.monotonic()
        while self._pending_admissions:
            (
                t0, marker, rows, gens, t_admit0, pf_entry, pf_name, pf_toks, A, T,
            ) = self._pending_admissions[0]
            if not marker.is_ready():
                # Purge entries whose rows were ALL cancelled/reaped before
                # the marker resolved — otherwise they hold device handles
                # across an idle block in _drain_queue (n_active==0, no
                # inflight) until the next request arrives.
                if all(
                    slab.req[i] is None or slab.gen[i] != g
                    for i, g in zip(rows, gens)
                ):
                    self._pending_admissions.pop(0)
                    continue
                return
            self._pending_admissions.pop(0)
            dt = (now - t0) * 1e3
            if self._ledger_on:
                # Prefill cost apportionment (cost ledger): the cohort
                # executable's XLA cost split equally over the rows still
                # alive at chain completion (row-residency share; a row
                # reaped mid-chain forfeits its share, so the totals stay
                # exactly what the bills received).
                live = [
                    i for i, g in zip(rows, gens)
                    if slab.req[i] is not None and slab.gen[i] == g
                ]
                self._ledger_account(pf_entry, pf_name, live, slab)
            for i, g, n_pf in zip(rows, gens, pf_toks):
                if slab.req[i] is None or slab.gen[i] != g:
                    continue
                slab.prefill_ms[i] = dt
                slab.t_decode0[i] = now
                r = slab.req[i]
                if r.span is not None:
                    # Admission-start to chain-completion: host prep, the
                    # cohort prefill this row rode in, commit-to-pages and
                    # first sample (observed <=1 tick late, same as the
                    # prefill_ms it mirrors).
                    # prefix_* attrs: latency attribution (PR 4) separates
                    # warm prefill (radix-matched head, suffix-only work)
                    # from cold — attached only while the cache is enabled
                    # so disabled-mode span payloads stay byte-identical.
                    pfx_attrs = (
                        {
                            "prefix_matched_tokens": int(slab.prefix_toks[i]),
                            "prefix_hit": bool(slab.prefix_toks[i] > 0),
                        }
                        if self.config.engine.prefix_cache
                        else {}
                    )
                    if self.model_cfg.conv_ffn:
                        # The tokens this row's prefill moved its convolutions'
                        # tails by, and the pages it filled to their last slot,
                        # each with its tail written a ``C`` layer.
                        psz = self.config.engine.kv_page_size
                        P = int(slab.prefix_toks[i])
                        pfx_attrs["conv_prefill_tokens"] = n_pf * self.model_cfg.n_conv_layers
                        pfx_attrs["tail_pages_written"] = (P + n_pf) // psz - P // psz
                    elif self.model_cfg.hybrid:
                        # The tokens this row's prefill moved its recurrent
                        # state by, over the Mamba layers: all it prefilled.
                        pfx_attrs["ssm_prefill_tokens"] = n_pf * self.model_cfg.n_recurrent_layers
                        if self.model_cfg.scan_ffn:
                            # A selective scan WALKS its prompt: the COHORT's
                            # live tokens and the slots of its ``A x T`` window,
                            # each times the ``J`` layers (the same on every
                            # row of the cohort: a reader counts a cohort once),
                            # and the states its calls wrote, one a row a layer.
                            Lj = self.model_cfg.n_scan_layers
                            pfx_attrs["scan_tokens"] = sum(pf_toks) * Lj
                            pfx_attrs["scan_slots"] = A * T * Lj
                            pfx_attrs["ssm_state_bytes"] = A * Lj * self.model_cfg.ssm_slot_bytes
                    r.span.child(
                        "engine.prefill",
                        t0=t_admit0,
                        t1=now,
                        dfa_id=int(slab.dfa[i]),
                        # The cohort this row was prefilled in, and the row
                        # bucket it was padded up to (``cohort_buckets``).
                        cohort_rows=len(rows),
                        cohort_bucket=A,
                        **pfx_attrs,
                    )

    def _dispatch_merge(self, slab: "_Slab", rows: list[int]) -> None:
        """Dispatch one clear-scatter for ``rows`` + any dirty retired rows
        into the device slab state: every named row gets the free-row state
        (done, pad cur, zeroed page-table row → null page). Admitted rows
        take the OTHER merge (``_admit_merge_impl``, device-chained values);
        this one only ever clears. Async — no round trip."""
        B = slab.B
        targets = list(dict.fromkeys(list(rows) + list(self._dirty_rows)))
        self._dirty_rows.clear()
        if not targets:
            return
        idx = np.full((B,), B, np.int32)  # B = dropped padding
        idx[: len(targets)] = targets
        rs = self._row_spec(B)
        rs2 = self._row_spec(B, 1)
        state = self._dev_state(slab)
        slab.dev = self._jit_merge(
            *state,
            *self._put_many(
                (idx, rs),
                (np.full((B,), slab.pad_id, np.int32), rs),
                (np.zeros((B,), np.int32), rs),
                (np.zeros((B,), np.int32), rs),
                (np.zeros((B,), np.int32), rs),
                (np.ones((B,), bool), rs),
                (np.zeros((B,), np.int32), rs),
                (np.zeros((B, slab.page_table.shape[1]), np.int32), rs2),
                (np.full((B, slab.steps), slab.pad_id, np.int32), rs2),
                (np.full((B, slab.prompt_cap), slab.pad_id, np.int32), rs2),
                (np.zeros((B,), np.int32), rs),
                (np.full((B,), slab.pad_id, np.int32), rs),
                (np.zeros((B,), np.float32), rs),
                (np.zeros((B,), bool), rs),
                (np.zeros((B,), np.int32), rs),
                (np.zeros((B, slab.hstate.shape[1]), np.float32), rs2),
            ),
        )

    def prompt_capacity(self, max_new_tokens: int = 0, shared_prefix_len: int = 0) -> int:
        """Longest prompt (in tokens) the engine can serve alongside a
        ``max_new_tokens`` decode budget — the page-capacity/prefill-bucket
        geometry callers should trim to BEFORE submitting. The planner clamps
        its prompt budget to this so the engine's own head-keep safety trim
        (which cannot know which lines matter) never has to engage and the
        trailing "Intent:"/"JSON:" lines always survive.

        ``shared_prefix_len`` mirrors the GenerateRequest field: with a
        shared prefix the SUFFIX must fit a prefill bucket alongside the
        prefix's pages, which can shrink total capacity below the no-prefix
        figure — callers sending a prefix must clamp against this."""
        ecfg = self.config.engine
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        # The worst garbage-write slack either decode path needs: the DFA
        # fast-forward chunk or the speculative verify window (whichever
        # the live config arms wider) — callers must fit both because the
        # slab may serve them either way across its lifetime.
        chunk = max(self._spec_chunk(True), self._spec_k() + 1)
        slack = chunk if chunk > 1 else 0
        budget = min(max_new_tokens or ecfg.max_decode_len, max(1, min(ecfg.max_decode_len, capacity - 1 - slack)))
        full_eligible = [b for b in self._prefill_buckets if b <= capacity]
        if not full_eligible:
            return 1
        full_cap = max(1, min(full_eligible[-1], capacity - budget - slack))
        P = 0
        if ecfg.prefix_cache and shared_prefix_len:
            P = (shared_prefix_len // ecfg.kv_page_size) * ecfg.kv_page_size
        if not P:
            return full_cap
        eligible = [b for b in self._prefill_buckets if b + P <= capacity]
        if not eligible:
            return full_cap  # admission falls back to the full path too
        prefix_cap = max(1, P + min(eligible[-1], capacity - P - budget - slack))
        if P > full_eligible[-1]:
            # A head longer than every bucket is served over its cached
            # pages or not at all: no full prefill could take it.
            return prefix_cap
        # Admission may fall back to full prefill at runtime (page pressure,
        # unbuildable prefix), whose head-keep trim would cut the prompt
        # TAIL — so the caller must fit the WORST of the two paths.
        return min(full_cap, prefix_cap)

    def _grammar_pad(self) -> int:
        """State-dim pad quantum for grammar device tables. One pad bucket =
        one decode executable, so warmup (generic grammar) and serving
        (registry-trie grammar) share compiles as long as both fit the
        budget. Compact tables are [S, C] over the ACTIVE columns only
        (grammar.py column compaction) — for grammars whose active set is
        still huge (shape-only on a subword vocab) the quantum shrinks so
        state padding doesn't cost GBs of HBM."""
        budget = self.config.engine.grammar_state_budget
        C = self.grammar.n_active
        if budget * C > 64_000_000:  # > ~256MB of int32 transitions
            return 64
        return budget

    def _decode_iters(self, spec: bool) -> int:
        """The most model forwards one dispatched decode segment may run —
        the FUSED MULTI-STEP WINDOW: ``decode_steps_per_tick`` (the legacy
        tick) times ``steps_per_dispatch`` folded into one jitted
        ``lax.while_loop`` whose per-row done masks are data, so one host
        dispatch + one harvest serve the whole window. It is a CEILING:
        the length a segment is dispatched with is chosen by the pacer
        (``_segment_window``) and reaches the executable as an operand, so
        warmup (which passes this ceiling) and every served length share
        one executable. The while loop exits early when every row drains,
        so a long window never burns device compute — only the time a
        finished row and a fresh arrival wait for the segment's end.
        The SPECULATIVE segment is excluded: its iterations are unrolled
        without early exit (pool-aliasing constraint, see
        ``_hetero_segment_spec_impl``) and each already covers a
        [rows, K+1] window, so multiplying it would pay full verify
        compute on the drain tail; its count stays a static, one tick."""
        ecfg = self.config.engine
        base = max(1, ecfg.decode_steps_per_tick)
        if spec:
            return base
        return base * max(1, ecfg.steps_per_dispatch)

    def _segment_window(self, spec: bool) -> tuple[int, int]:
        """(forwards the next segment may run, the configured ceiling).
        Worker thread only. The pacer sizes the segment from what it has
        measured (``pacing.segment_forwards``): whole ticks, long enough
        to cover the worker's own work, the ceiling until it has an
        estimate. The speculative segment is
        always its one tick."""
        ceiling = self._decode_iters(spec)
        if spec:
            return ceiling, ceiling
        tick = self.config.engine.decode_steps_per_tick
        return self._pacer.window(tick, ceiling), ceiling

    def _spec_chunk(self, constrained: bool) -> int:
        """Static speculation chunk width — config-derived only (it is a jit
        static arg: one executable shared by warmup and every segment). On
        configs whose page capacity can't spare the chunk's garbage-write
        slack, speculation degrades toward 1 rather than failing — logged
        once so the degradation is visible (VERDICT r2 weak #8)."""
        ecfg = self.config.engine
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        want = ecfg.speculate_k if (constrained and ecfg.speculate_k > 1) else 1
        budget_ceiling = min(ecfg.max_decode_len, capacity - 1)
        got = max(1, min(want, capacity - budget_ceiling))
        if got < want and not getattr(self, "_spec_degraded_logged", False):
            self._spec_degraded_logged = True
            log.warning(
                "speculation chunk degraded %d -> %d: page capacity %d leaves no "
                "slack past max_decode_len=%d (raise max_pages_per_seq/kv_page_size "
                "or lower max_decode_len to restore speculation)",
                want, got, capacity, ecfg.max_decode_len,
            )
        return got

    def _spec_k(self) -> int:
        """Draft tokens per verify forward under grammar-aware speculative
        decoding (EngineConfig.speculative) — 0 when the subsystem is
        inert: disabled, hetero_batch off (the drafter's grammar pre-filter
        needs the per-row stacked DFAs), or page capacity leaving no slack
        for the [K+1]-wide window's garbage writes (degrades toward 0
        rather than failing, logged once, mirroring _spec_chunk)."""
        ecfg = self.config.engine
        if not (ecfg.hetero_batch and ecfg.speculative.enabled):
            return 0
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        budget_ceiling = min(ecfg.max_decode_len, capacity - 1)
        window = max(1, min(ecfg.speculative.k + 1, capacity - budget_ceiling))
        if window - 1 < ecfg.speculative.k and not self._spec_window_degraded_logged:
            self._spec_window_degraded_logged = True
            log.warning(
                "speculative window degraded k=%d -> %d: page capacity %d "
                "leaves no slack past max_decode_len=%d (raise "
                "max_pages_per_seq/kv_page_size or lower max_decode_len)",
                ecfg.speculative.k, window - 1, capacity, ecfg.max_decode_len,
            )
        return window - 1

    # --- jitted bodies ----------------------------------------------------
    def _budget_mask(self, dfa, st, rem):
        """Allow column c iff grammar-legal AND (c is EOS or the successor
        state can still finish within the remaining sample budget) — this
        forces the JSON closed before the budget runs out. When the budget
        can't fit any completion at all (caller asked for fewer tokens than
        the shortest valid plan), degrade to the plain grammar mask: the
        output is then a legal prefix, never garbage. ``dfa`` = the first
        five of ``PlanGrammar.device_tables()``; its third member is
        ``dist_succ [S, C]``, the successor's distance-to-finish, read by
        ROW at the visited state like the mask (a ``dist [S]`` vector read
        through ``trans`` is one scalar gather per row and column: 0.8 ms
        of every forward on a v5e, PERF.md PR 34). Masks live in COMPACT
        column space [B, C]."""
        _trans, mask_tab, dist_succ, _active, eos_cols = dfa
        legal = mask_tab[st]
        finishable = legal & (eos_cols[None, :] | (dist_succ[st] <= rem[:, None]))
        feasible = jnp.any(finishable, axis=-1, keepdims=True)
        return jnp.where(feasible, finishable, legal)

    def _admit_impl(
        self,
        dfa_trans,
        dfa_mask,
        dfa_dist_succ,
        dfa_active,
        dfa_eos,
        dfa_inv,  # unused here; *dfa call sites pass the full 6-tuple
        first_logits,
        budgets,
        active,
        key,
        *,
        temperature: float,
        constrained: bool,
    ):
        """Sample each admitted row's first emission from its prefill logits;
        returns (cur0, state0, done0) with pad substituted for finished rows.
        State 0 is the grammar start (build_plan_grammar invariant).
        Constrained sampling happens in COMPACT column space: gather the
        active columns of the logits, mask, sample a column, map back to a
        token id via active_ids."""
        tok = self.tokenizer
        dfa = (dfa_trans, dfa_mask, dfa_dist_succ, dfa_active, dfa_eos)
        A = budgets.shape[0]
        start_state = jnp.zeros((A,), jnp.int32)
        if constrained:
            mask0 = self._budget_mask(dfa, start_state, budgets - 1)
            col = sample(
                first_logits[:, dfa_active],
                key,
                temperature=temperature,
                top_k=self.config.engine.top_k,
                mask=mask0,
            ).astype(jnp.int32)
            first = dfa_active[col]
            is_eos = dfa_eos[col]
            done0 = is_eos | ~active | (budgets < 1)
            state0 = jnp.where(done0, start_state, dfa_trans[start_state, col])
        else:
            first = sample(
                first_logits,
                key,
                temperature=temperature,
                top_k=self.config.engine.top_k,
                mask=self._unconstrained_mask,
            ).astype(jnp.int32)
            done0 = (first == tok.eos_id) | ~active | (budgets < 1)
            state0 = start_state
        cur0 = jnp.where(done0, tok.pad_id, first)
        return cur0, state0, done0

    def _stacked_budget_mask(self, sdfa, dfa_id, st, rem):
        """Per-row variant of ``_budget_mask`` over STACKED grammar tables:
        row b's mask comes from grammar slot ``dfa_id[b]`` of the [G, S, C]
        stack. Same degrade-to-legal semantics; masks live in the stack's
        common compact column space [B, C]."""
        strans, smask, sdist, _sactive, seos = sdfa
        legal = smask[dfa_id, st]  # [B, C]
        succ = strans[dfa_id, st]  # [B, C]
        finishable = legal & (
            seos[dfa_id] | (sdist[dfa_id[:, None], succ] <= rem[:, None])
        )
        feasible = jnp.any(finishable, axis=-1, keepdims=True)
        return jnp.where(feasible, finishable, legal)

    def _hetero_admit_impl(
        self,
        sdfa_trans,
        sdfa_mask,
        sdfa_dist,
        sdfa_active,
        sdfa_eos,
        first_logits,
        budgets,
        active,
        temp_v,
        cons_v,
        dfa_id,
        key,
    ):
        """Per-row first-sample for a heterogeneous admission cohort: every
        row draws BOTH ways — budget-masked compact-column through its own
        stacked grammar slot, and full-vocab unconstrained — and
        ``jnp.where(cons_v, ...)`` keeps the one that applies; temperature
        is a device vector (``sample_rows``). No static sampling args, so
        one executable per cohort bucket serves every request mix."""
        tok = self.tokenizer
        sdfa = (sdfa_trans, sdfa_mask, sdfa_dist, sdfa_active, sdfa_eos)
        A = budgets.shape[0]
        start = jnp.zeros((A,), jnp.int32)
        a_idx = jnp.arange(A)
        act_rows = sdfa_active[dfa_id]  # [A, C]
        mask0 = self._stacked_budget_mask(sdfa, dfa_id, start, budgets - 1)
        col = sample_rows(
            jnp.take_along_axis(first_logits, act_rows, axis=-1),
            key,
            temp_v,
            top_k=self.config.engine.top_k,
            mask=mask0,
        ).astype(jnp.int32)
        c_first = act_rows[a_idx, col]
        u_first = sample_rows(
            first_logits,
            key,
            temp_v,
            top_k=self.config.engine.top_k,
            mask=self._unconstrained_mask,
        ).astype(jnp.int32)
        first = jnp.where(cons_v, c_first, u_first)
        ended = jnp.where(cons_v, sdfa_eos[dfa_id, col], u_first == tok.eos_id)
        done0 = ended | ~active | (budgets < 1)
        state0 = jnp.where(
            done0 | ~cons_v, start, sdfa_trans[dfa_id, start, col]
        )
        cur0 = jnp.where(done0, tok.pad_id, first)
        return cur0, state0, done0

    def _prefill_impl(
        self, params, tokens, seq_lens, paged_k, paged_v, page_table, state, slots, *, T
    ):
        """``state``, ``slots``: the state pool and each cohort row's slot of
        it ({} and None for a model with no recurrent layer): a row's slot is
        OVERWRITTEN with its prompt's final state, nothing pending (a slot
        out of range, a padding row's, is dropped)."""
        cfg = self.model_cfg
        B = tokens.shape[0]
        dense = init_kv_cache(cfg, B, T)
        # last_only: the [B, T, V] logits buffer must never exist — at
        # subword vocab sizes it is hundreds of MB per cohort and its
        # unembed matmul rivals the whole layer stack.
        # A sparse model's expert counters (moe_stats_init); None from a
        # dense one, which adds no output to its executable.
        # The routed experts take their kernel calls where the paged routes'
        # do: one device holds the window's rows beside the stacks.
        last, dense, moe = prefill(
            params, cfg, tokens, seq_lens, dense, last_only=True, moe_stats=True,
            use_pallas=self._use_pallas and (self._mesh is None or self._mesh.size == 1),
            interpret=self.config.engine.interpret,
        )
        paged = commit_prefill_to_pages(
            {"k": paged_k, "v": paged_v},
            dense,
            page_table,
            seq_lens,
            self.config.engine.kv_page_size,
        )
        if cfg.hybrid:
            state = write_prefill_state(state, slots, dense["ssm"])
        if cfg.page_state:
            # The tail of every page this prompt fills, in the program that
            # writes the page's keys (docs/engine.md "The state's rule").
            state = {**state, "tails": commit_prefill_tails(
                state["tails"], dense["ssm"], page_table, self.config.engine.kv_page_size
            )}
        if cfg.n_block_layers:
            state = {**state, "ksum": commit_prefill_key_sums(
                state["ksum"], dense["k"], page_table, self.config.engine.kv_page_size
            )}
        return last, paged["k"], paged["v"], moe, state

    def _suffix_prefill_impl(
        self, params, tokens, seq_lens, positions, page_table, paged_k, paged_v,
        state=None, src=None, slots=None,
    ):
        """Prefill only the prompt SUFFIX: one chunked forward whose queries
        sit at positions ``positions..positions+S-1`` and attend the shared
        prefix's read-only pages plus themselves (intra-chunk causal) —
        ``decode_chunk_paged``'s existing contract, at prefill width. Pads
        past a row's suffix write garbage K/V at positions its decode later
        overwrites (or the null page); their logits are never read. Routes
        through the ragged kernel on the engine-resolved ``_use_pallas``
        (the hardcoded ``use_pallas=False`` fork this call site carried for
        seven PRs is the bug class mcpxlint's ``hardcoded-kernel-fallback``
        rule now polices): per-row suffix lengths are the kernel's
        ``q_lens``, so short-suffix rows (warm replans prefilling ~1 page)
        stream pages for their own width, not the cohort bucket's.

        ``state``, ``src``, ``slots`` (a model whose recurrent layers have a
        suffix route, ``GemmaConfig.head_state``; {} and None otherwise): the
        state pool, the slot each row's state is READ from (the declared
        head's; out of range: an empty state) and the slot the state AT the
        row's last token is written to, nothing pending (a padding row's is
        out of range and dropped). A model whose state is kept a page
        (``GemmaConfig.page_state``) reads no slot: each row starts from the
        tail of the page before ``positions`` (``src`` None), and the program
        writes the tail of every page it fills beside the page's keys."""
        cfg = self.model_cfg
        carried = cfg.head_state or cfg.page_state
        last, kv, moe = decode_chunk_paged(
            params,
            cfg,
            tokens,
            positions,
            page_table,
            {"k": paged_k, "v": paged_v, **({"state": state} if carried else {})},
            use_pallas=self._use_pallas,
            interpret=self.config.engine.interpret,
            mesh=self._mesh,
            logits_at=seq_lens - 1,  # [A, V]: suffix-final logits only
            q_lens=seq_lens,
            moe_stats=True,  # as _prefill_impl: None from a dense model
            state_slots=(src, slots) if carried else None,
            commit=carried,
        )
        return last, kv["k"], kv["v"], moe, kv.get("state", state)

    # --- tiered KV cache: device<->host page-run copies -------------------
    def _spill_gather_impl(self, paged_k, paged_v, pages):
        """Async device→host spill, step 1: slice the named pages out of
        the pools (functional snapshot — later pool writes cannot touch
        the result). Pad lanes carry the null page's garbage; the readmit
        scatter drops them."""
        return paged_k[:, :, pages], paged_v[:, :, pages]

    def _spill_readmit_impl(self, paged_k, paged_v, k_run, v_run, pages):
        """Host→device readmit: scatter a spilled run back into freshly-
        allocated pages. Pad lanes index out of range and drop."""
        return (
            paged_k.at[:, :, pages].set(k_run, mode="drop"),
            paged_v.at[:, :, pages].set(v_run, mode="drop"),
        )

    @staticmethod
    def _spill_bucket(n: int) -> int:
        """Page-count pad bucket (next power of two): one gather/scatter
        executable per bucket, not per run length."""
        b = 1
        while b < n:
            b <<= 1
        return b

    @owned_by("engine-worker")
    def _spill_gather_dispatch(self, pages: list[int]) -> tuple:
        """HostSpillTier's gather hook: dispatch the page-run slice on the
        CURRENT pools and return the async device handles (the tier polls
        them off the hot path). No donation — the pools stay live."""
        B = self._spill_bucket(len(pages))
        arr = np.zeros((B,), np.int32)  # pad -> null page (content unused)
        arr[: len(pages)] = pages
        return self._jit_spill_gather(
            self._paged_kv["k"],
            self._paged_kv["v"],
            self._put(arr, P()),
        )

    @owned_by("engine-worker")
    def _spill_readmit_dispatch(self, k_host, v_host, pages: list[int]) -> None:
        """HostSpillTier's readmit hook: async host→device scatter into
        ``pages``, donating the pools like every prefill — dispatched
        before anything that reads the pages, so device program order
        makes the data visible with no host sync."""
        # Pad the run to its page-count bucket (host-run splits produce
        # arbitrary lengths; one scatter executable per bucket, never per
        # length). Pad lanes index out of range and drop.
        k_host, v_host = np.asarray(k_host), np.asarray(v_host)
        B = self._spill_bucket(max(len(pages), k_host.shape[2]))
        if k_host.shape[2] < B:
            pad = [(0, 0)] * k_host.ndim
            pad[2] = (0, B - k_host.shape[2])
            k_host = np.pad(k_host, pad)
            v_host = np.pad(v_host, pad)
        arr = np.full((B,), self._allocator.n_pages, np.int32)  # pad -> drop
        arr[: len(pages)] = pages
        k_p, v_p = self._jit_spill_readmit(
            self._paged_kv["k"],
            self._paged_kv["v"],
            self._put(k_host, P()),
            self._put(v_host, P()),
            self._put(arr, P()),
        )
        self._paged_kv = {"k": k_p, "v": v_p}

    # --- tiered KV cache: warm-restart snapshot ---------------------------
    _SNAPSHOT_VERSION = 1

    def _snapshot_meta(self) -> dict:
        mc = self.model_cfg
        return {
            "version": self._SNAPSHOT_VERSION,
            "page_size": self.config.engine.kv_page_size,
            "n_kv_heads": mc.n_kv_heads,
            "n_layers": mc.n_layers,
            "head_dim": mc.head_dim,
            # the pools' last axes: a latent block's are not head_dim
            "kv_widths": list(mc.kv_widths),
            "dtype": str(jnp.dtype(mc.dtype).name),
            "vocab_size": self.tokenizer.vocab_size,
        }

    def _params_fingerprint(self) -> Optional[float]:
        """Cheap identity check that the restoring engine serves the SAME
        weights the snapshot's KV was computed under (random-init runs are
        seeded, so the fingerprint is stable per config; a checkpoint swap
        changes it and the KV restore is skipped — stale KV must never be
        attended)."""
        try:
            leaves = jax.tree_util.tree_leaves(self._params)  # mcpx: ignore[thread-ownership] - worker thread (setup) or post-join teardown (aclose guard)
            total = 0.0
            for i, leaf in enumerate(leaves):
                # Position-weighted abs-sum over EVERY leaf: a fine-tune
                # that leaves any single tensor untouched (frozen
                # embeddings, a norm scale) still shifts the total, and
                # leaf permutations cannot cancel. Snapshot-path only —
                # never on the serving path.
                total += (i + 1.0) * float(
                    jnp.sum(jnp.abs(leaf).astype(jnp.float32))
                )
            return total
        except Exception:  # noqa: BLE001 - no fingerprint = no KV restore
            log.debug("params fingerprint unavailable", exc_info=True)
            return None

    def _save_snapshot(self) -> None:
        """Serialize the warm-restart snapshot: a versioned JSON manifest
        (tree structure, declared heads, governor state, model identity)
        plus a sidecar ``.npz`` of KV page runs, bounded by the tier's
        host byte budget, written atomically. Called from aclose() AFTER
        the worker joined (single-writer preserved: no writer left) and
        BEFORE the pools drop. Best-effort — any failure logs and skips;
        a deploy must never hang on its snapshot."""
        import os

        ecfg = self.config.engine
        path = os.path.expanduser(ecfg.kv_tier.snapshot_path)
        tier = self._spill_tier
        cache = self._prefix_cache
        psz = ecfg.kv_page_size
        tier.drain()  # mcpx: ignore[thread-ownership] - worker joined (aclose guard); blocking shutdown drain
        nodes_out: list[dict] = []
        arrays: dict[str, Any] = {}
        budget = tier.host_bytes or (256 << 20)
        total = 0
        # Root-first BFS so every manifest entry's parent precedes it —
        # the restore contract of RadixPrefixCache.restore_spilled.
        queue = [(cache.root, ())]
        while queue:
            node, prefix = queue.pop(0)
            for child in node.children.values():
                cpath = prefix + child.tokens
                if child.pending:
                    continue
                if child.host is not None and child.host.ready:
                    k_np, v_np = child.host.k, child.host.v
                elif child.pages:
                    pages = np.asarray(child.pages, np.int32)
                    k_np, v_np = jax.device_get(
                        (
                            self._paged_kv["k"][:, :, pages],  # mcpx: ignore[thread-ownership] - worker joined (aclose guard); teardown read
                            self._paged_kv["v"][:, :, pages],  # mcpx: ignore[thread-ownership] - worker joined (aclose guard); teardown read
                        )
                    )
                else:
                    continue
                nbytes = int(k_np.nbytes) + int(v_np.nbytes)
                if total + nbytes > budget:
                    continue  # keep walking: a smaller sibling may fit
                total += nbytes
                key = f"n{len(nodes_out)}"
                arrays[f"{key}_k"] = np.frombuffer(
                    np.ascontiguousarray(k_np).tobytes(), np.uint8
                )
                arrays[f"{key}_v"] = np.frombuffer(
                    np.ascontiguousarray(v_np).tobytes(), np.uint8
                )
                nodes_out.append(
                    {
                        "path": [int(t) for t in cpath],
                        "edge": len(child.tokens),
                        "tenant": child.tenant,
                        "key": key,
                        "shape": list(k_np.shape),
                    }
                )
                queue.append((child, cpath))
        manifest = {
            **self._snapshot_meta(),
            "fingerprint": self._params_fingerprint(),
            "governor": (
                self._governor.snapshot() if self._governor is not None else {}
            ),
            "declared_heads": [
                {"ids": [int(t) for t in k], "tenant": t}
                for k, t in self._declared_heads.items()  # mcpx: ignore[thread-ownership] - worker joined (aclose guard); teardown read
            ],
            "nodes": nodes_out,
        }
        chaos = tier.chaos
        tmp = path + ".tmp"
        npz_tmp = path + ".npz.tmp"
        with open(npz_tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(npz_tmp, path + ".npz")
        with open(tmp, "w") as f:
            if chaos is not None and chaos.snapshot_corrupt:
                f.write(json.dumps(manifest)[: 40] + "...TRUNCATED")
            else:
                json.dump(manifest, f)
        os.replace(tmp, path)
        log.info(
            "KV snapshot saved: %d runs, %.1f MiB, %d declared heads -> %s",
            len(nodes_out), total / (1 << 20),
            len(self._declared_heads),  # mcpx: ignore[thread-ownership] - worker joined (aclose guard); teardown read
            path,
        )

    def _load_snapshot(self) -> None:
        """Restore a warm-restart snapshot written by a prior clean
        ``aclose()``: validated manifest entries become SPILLED tree nodes
        (host-resident KV, re-admitted by the standard async page copy on
        first match — deploys start warm with zero prefill). Corrupt,
        stale or mismatched snapshots are detected, logged and SKIPPED —
        never fatal, never attended. Worker thread, during _setup."""
        import os

        path = os.path.expanduser(self.config.engine.kv_tier.snapshot_path)
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                manifest = json.load(f)
            meta = self._snapshot_meta()
            for k, want in meta.items():
                if manifest.get(k) != want:
                    raise ValueError(
                        f"snapshot {k}={manifest.get(k)!r} != engine {want!r}"
                    )
        except Exception as e:  # noqa: BLE001 - corrupt/stale snapshot: skip, never fatal
            log.warning("KV snapshot unusable, starting cold: %s", e)
            return
        if self._governor is not None:
            try:
                self._governor.restore(manifest.get("governor") or {})
            except Exception:  # noqa: BLE001 - governor state is advisory
                log.warning("snapshot governor state unusable", exc_info=True)
        heads = [
            (tuple(int(t) for t in h.get("ids", ())), str(h.get("tenant", "default")))
            for h in manifest.get("declared_heads", ())
            if h.get("ids")
        ]
        fp_then = manifest.get("fingerprint")
        fp_now = self._params_fingerprint()
        kv_ok = (
            fp_then is not None
            and fp_now is not None
            and abs(fp_then - fp_now) <= 1e-3 * max(1.0, abs(fp_then))
        )
        restored = 0
        if kv_ok:
            try:
                npz = np.load(path + ".npz")
                dtype = jnp.dtype(self.model_cfg.dtype)
                for ent in manifest.get("nodes", ()):
                    shape = tuple(int(s) for s in ent["shape"])
                    k_np = np.frombuffer(
                        npz[ent["key"] + "_k"].tobytes(), dtype
                    ).reshape(shape)
                    v_np = np.frombuffer(
                        npz[ent["key"] + "_v"].tobytes(), dtype
                    ).reshape(shape)
                    if self._prefix_cache.restore_spilled(
                        [int(t) for t in ent["path"]],
                        int(ent["edge"]),
                        k_np,
                        v_np,
                        str(ent.get("tenant", "default")),
                    ):
                        restored += 1
            except Exception as e:  # noqa: BLE001 - partial restore is still a win; the rest rebuilds lazily
                log.warning("KV snapshot arrays unusable past %d runs: %s", restored, e)
        if not kv_ok or restored == 0:
            # KV invalid (weights changed, arrays corrupt): fall back to
            # lazily re-prefilling the declared heads on first use.
            self._warm_heads = [h for h in heads if h[0]]
            log.info(
                "KV snapshot ids-only restore: %d heads queued for lazy "
                "re-prefill (kv_ok=%s)", len(self._warm_heads), kv_ok,
            )
        else:
            log.info("KV snapshot restored %d runs into the host tier", restored)
        for k, t in heads:
            self._declared_heads[k] = t

    def _pop_warm_head(self, req: GenerateRequest) -> Optional[tuple]:
        """The longest snapshot head strictly prefixing ``req``'s prompt
        (ids-only restore fallback), popped for its one lazy rebuild."""
        best = None
        best_i = -1
        for i, (ids, tenant) in enumerate(self._warm_heads):
            if len(ids) < len(req.prompt_ids) and tuple(
                req.prompt_ids[: len(ids)]
            ) == ids:
                if best is None or len(ids) > len(best[0]):
                    best, best_i = (ids, tenant), i
        if best is not None:
            self._warm_heads.pop(best_i)
        return best

    def _ensure_prefix(
        self, key: tuple, tenant: str = "default"
    ) -> Optional[PrefixNode]:
        """Make the declared shared prompt head ``key`` fully resident in
        the radix tree, prefilling only the part the tree does not already
        hold. A part that fits a prefill bucket is ONE dispatch
        (``_build_prefix``); a longer one (a catalogue head of thousands of
        tokens) is built in chunks of the largest bucket that fits beside
        what is resident, each a suffix prefill over the pages of those
        before it and inserted into the tree before the next is prefilled
        over it. The chunks run back to back on the worker: a head is built
        once per registry version, and decode segments wait for it (ROADMAP
        M2 keeps interleaving them). Returns the deepest node covering
        ``key`` (unpinned), or None when it cannot be built right now (page
        pressure, capacity): per-row matching then reuses whatever IS
        resident. Worker-thread only."""
        P = len(key)
        capacity = self.config.engine.max_pages_per_seq * self.config.engine.kv_page_size
        stateful = self.model_cfg.head_state
        if stateful:
            # The head's END STATE lives in the state pool's last slot, each
            # chunk of the build handing the next its state through it. ONE
            # such state exists: pages of this head that are resident with no
            # state to continue from (the slot holds another head's end, or
            # was reset) are not built over, the head gets no state, and rows
            # that reach it prefill whole and count as misses.
            n, _, node = self._prefix_cache.match(key, cap=P, record=False)
            if self._head_state == key and n == P:
                return node
            if n > 0:
                return None
            self._head_state = None  # the slot is overwritten from here on
        while True:
            n = self._prefix_cache.match(key, cap=P, record=False)[0]
            eligible = [b for b in self._prefill_buckets if b + n <= capacity]
            end = P if not eligible or P - n <= eligible[-1] else n + eligible[-1]
            node = self._build_prefix(key[:end], tenant)
            if node is None or end == P:
                if stateful and node is not None:
                    self._head_state = key
                return node

    def _build_prefix(
        self, key: tuple, tenant: str = "default"
    ) -> Optional[PrefixNode]:
        """``_ensure_prefix`` for a head whose unmatched part fits a prefill
        bucket (one [1, T] dispatch — suffix-offset when a head is matched,
        dense full prefill from zero). This pre-build exists so even the
        FIRST cohort of a burst shares its declared header instead of
        prefilling it once per row."""
        ecfg = self.config.engine
        cache = self._prefix_cache
        P = len(key)
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        n, _pages, mnode = cache.match(key, cap=P, record=False)
        if n == P:
            return mnode
        # The prefix must leave room for a minimal suffix + decode budget,
        # and its unmatched remainder must fit a prefill bucket — checked
        # BEFORE any pages are allocated (a raise here must not leak).
        R = P - n
        eligible = tuple(b for b in self._prefill_buckets if b + n <= capacity)
        if (
            not eligible
            or R > eligible[-1]
            or P + self._prefill_buckets[0] + ecfg.max_decode_len > capacity
        ):
            return None
        T = _bucket(R, eligible)
        if mnode is not None:
            mnode.refs += 1  # hold: the build below may evict under pressure
        node = cache.insert(key, n, R, tenant=tenant)
        if mnode is not None:
            mnode.refs -= 1
        if node is None:
            return None
        table = np.zeros((1, ecfg.max_pages_per_seq), np.int32)
        table[0, : n // ecfg.kv_page_size] = _pages
        table[0, n // ecfg.kv_page_size : P // ecfg.kv_page_size] = node.pages
        tokens = np.full((1, T), self.tokenizer.pad_id, np.int32)
        tokens[0, :R] = key[n:]
        # A head's state (``GemmaConfig.head_state``) is read from and written
        # to the pool's last slot: the chunk before left it there.
        # A head whose state is its pages' tails has no slot: the build's row
        # is a padding row to the state pool (out of range, written nowhere;
        # None for a model with no state pool).
        head_slot = self._cohort_slots(
            1, [self.config.engine.max_batch_size] if self.model_cfg.head_state else ()
        )
        try:
            if n > 0:
                # Continue from the resident head: prefill only [n, P).
                last, k_p, v_p, _, self._state_pool = self._jit_suffix_prefill(
                    self._params,
                    self._put(tokens, self._row_spec(1, 1)),
                    self._put(np.asarray([R], np.int32), self._row_spec(1)),
                    self._put(np.asarray([n], np.int32), self._row_spec(1)),
                    self._put(table, self._row_spec(1, 1)),
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    self._state_pool,
                    head_slot if self.model_cfg.head_state else None,
                    head_slot,
                )
                # Every suffix-prefill dispatch counts toward the
                # prefill path's engagement report, not just the
                # admission-cohort site — a server whose only suffix
                # prefills are pre-built heads must not read as
                # "engaged but never ran" (pallas_paths).
                self._pallas_dispatches["prefill"] += 1
            else:
                last, k_p, v_p, _, self._state_pool = self._jit_prefill(
                    self._params,
                    self._put(tokens, self._row_spec(1, 1)),
                    self._put(np.asarray([R], np.int32), self._row_spec(1)),
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    self._put(table, self._row_spec(1, 1)),
                    self._state_pool,
                    head_slot,
                    T=T,
                )
            self._paged_kv = {"k": k_p, "v": v_p}
            del last
        except BaseException:
            cache.rollback(node)
            raise
        # The build counts as prefill work (amortised once per resident
        # prefix, not per request) — prefill-tokens-per-request accounting
        # must see it or reuse would overstate itself.
        self.metrics.prefill_tokens.inc(R)
        self.metrics.prefill_slots.inc(T)
        self.metrics.prefix_build_chunks.inc()
        self._prefix_built = (self._prefix_built[0] + 1, self._prefix_built[1] + R)
        cache.seal()  # dispatched: later cohorts may read these pages
        node.refs -= 1  # drop the insert's born-pin; callers re-pin
        return node

    def _evict_prefixes(self, need_tokens: int = 0) -> None:
        """Reclaim refcount-0 radix subtrees (LRU leaves first) while over
        the node cap or until ``need_tokens`` worth of pages can be
        allocated. The cap is re-read from config so a live operator tune
        (or a test forcing full eviction) takes effect immediately."""
        self._prefix_cache.max_nodes = max(
            0, self.config.engine.prefix_cache_entries
        )
        self._prefix_cache.evict(need_tokens)

    def _segment_impl(
        self,
        params,
        dfa_trans,
        dfa_mask,
        dfa_dist_succ,
        dfa_active,
        dfa_eos,
        dfa_inv,
        cur,
        pos,
        st,
        emitted,
        done,
        budgets,
        page_table,
        paged_k,
        paged_v,
        out_buf,
        prompt_toks,
        prompt_lens,
        prev,
        key,
        state,
        *,
        iters: int,
        chunk: int,
        temperature: float,
        constrained: bool,
        draft: bool,
    ):
        """One bounded decode segment over the whole slab: up to ``iters``
        model forwards (each a ``chunk``-wide grammar fast-forward chunk when
        speculation is on), exiting early when every row is done.

        Grammar fast-forward speculation (constrained only): a token is
        *forced* when its DFA state has exactly one legal successor — the
        constrained sample is then deterministic regardless of logits, so
        ``chunk-1`` forced tokens ride along each sampled token's forward
        with no verification/rejection needed (exact, unlike probabilistic
        speculation; SURVEY.md §6's speculation lever specialised to the
        plan grammar). ``chunk=1`` is the plain one-token-per-forward loop;
        greedy outputs are bit-identical across chunk widths (tested).

        Prompt-lookup draft speculation (``draft``, greedy/constrained
        only): positions fast-forward can't force — trie branch points,
        free strings — are filled with the continuation after the last
        (prev, cur) bigram match in the row's own prompt (plans echo
        shortlist names and schema keys verbatim), and the whole proposal
        chain is verified per-position against the budget-masked greedy
        argmax over COMPACT column logits (``decode_chunk_paged``'s
        ``active_cols`` path — the full-vocab [B, S, V] buffer never
        exists). Verification IS the greedy sample, so accepted tokens are
        exactly what sequential greedy decode would emit: output-identical
        to draft-off, more tokens per forward. Auto-off at temperature>0
        (probabilistic acceptance not implemented); forced tokens always
        pass verification (their mask has one legal column), so this path
        strictly generalises fast-forward.

        Emissions are written at absolute slots ``out_buf[b, emitted..]`` so
        rows admitted at different segment boundaries coexist in one slab.
        Returns (cur, pos, st, emitted, done, pools_k, pools_v, out_buf,
        prev, n_forwards, live_forwards [B]: each row's count of the
        forwards at whose start it was not done) and, for a model with
        sparse or windowed layers, their counters (``_segment_stats``) as
        one more int32 vector; last, always, the state pool (``state``: {}
        for a model with no recurrent layer). A row's recurrent state moves
        by exactly the tokens its position moves by, ``1 + accepted`` of a
        window and never the window: each forward leaves its window pending
        and ``keep_window`` says, after the verify, how much of it stays.
        """
        cfg = self.model_cfg
        tok = self.tokenizer
        B = cur.shape[0]
        W = out_buf.shape[1]
        dfa = (dfa_trans, dfa_mask, dfa_dist_succ, dfa_active, dfa_eos)
        trans, mask_tab = dfa_trans, dfa_mask
        budget_mask = self._budget_mask
        pad, eos = tok.pad_id, tok.eos_id
        b_idx = jnp.arange(B)
        use_draft = draft and constrained and chunk > 1 and temperature <= 0.0
        sparse, windowed = self._segment_stats

        def cond(c):
            it, cur, pos, st, e, done, k_p, v_p, buf, prev, key, ms, live, ssm = c
            return (it < iters) & jnp.any(~done)

        def kept(kv, ms, adv, done):
            """The state pool after a forward whose rows keep ``adv`` tokens
            of their windows, and the counters with those tokens added."""
            if not cfg.hybrid:
                return ssm0, ms
            tokens = jnp.sum(adv).astype(jnp.int32) * cfg.n_recurrent_layers
            return keep_window(kv["state"], b_idx, adv, ~done), ms.at[-1].add(tokens)

        def draft_body(c):
            from mcpx.engine.sampling import NEG_INF

            it, cur, pos, st, e, done, k_p, v_p, buf, prev, key, ms, live, ssm = c
            J = chunk - 1
            Lp = prompt_toks.shape[1]
            j_ar = jnp.arange(J)

            # --- continuation after the LAST (prev, cur) bigram match in
            # the row's own prompt (latest occurrence = most local context).
            pi = jnp.arange(Lp - 1)
            m = (prompt_toks[:, :-1] == prev[:, None]) & (
                prompt_toks[:, 1:] == cur[:, None]
            )
            m &= (pi[None, :] + 2) < prompt_lens[:, None]
            has = jnp.any(m, axis=1)
            last_i = (Lp - 2) - jnp.argmax(m[:, ::-1], axis=1)
            cont_idx = last_i[:, None] + 2 + j_ar[None, :]
            cont_ok = has[:, None] & (cont_idx < prompt_lens[:, None])
            cont = jnp.take_along_axis(
                prompt_toks, jnp.clip(cont_idx, 0, Lp - 1), axis=1
            )
            cont = jnp.where(cont_ok, cont, pad)  # [B, J]
            cont_col = dfa_inv[cont]  # [B, J]; -1 = active in no state

            # --- proposal chain: forced tokens (always) + draft tokens
            # while the realized chain stays in sync with the continuation.
            def prop_step(carry, xs):
                s, alive, sync = carry
                c_tok, c_col, c_ok = xs
                row = mask_tab[s]  # [B, C]
                f_col = jnp.argmax(row, axis=-1).astype(jnp.int32)
                is_forced = jnp.sum(row, axis=-1) == 1
                c_col_c = jnp.maximum(c_col, 0)
                d_legal = (
                    c_ok
                    & (c_col >= 0)
                    & jnp.take_along_axis(row, c_col_c[:, None], axis=1)[:, 0]
                    & ~dfa_eos[c_col_c]
                )
                p_col = jnp.where(is_forced, f_col, c_col_c)
                use = alive & jnp.where(
                    is_forced, ~dfa_eos[f_col], sync & d_legal
                )
                p_tok = dfa_active[p_col]
                return (
                    jnp.where(use, trans[s, p_col], s),
                    use,
                    sync & (p_tok == c_tok),
                ), (jnp.where(use, p_tok, pad), p_col, use, s)

            (s_fin, _, _), (p_toks, p_cols, p_use, s_before) = lax.scan(
                prop_step,
                (st, ~done, jnp.ones((B,), bool)),
                (cont.T, cont_col.T, cont_ok.T),
            )
            p_toks, p_cols, p_use = p_toks.T, p_cols.T, p_use.T  # [B, J]
            s_before = jnp.moveaxis(s_before, 0, 1)  # [B, J]

            # --- one forward over [cur, proposals], compact logits at
            # EVERY chunk position (verification needs them all). Ragged:
            # each row's live window is cur + its own proposal chain
            # (p_use is a prefix mask), so the kernel streams pages for
            # what the row actually proposed, not the static chunk width.
            chunk_toks = jnp.concatenate([cur[:, None], p_toks], axis=1)
            logits_c, kv, *fwd_ms = decode_chunk_paged(
                params,
                cfg,
                chunk_toks,
                pos,
                page_table,
                {"k": k_p, "v": v_p, "state": ssm},
                use_pallas=self._use_pallas,
                interpret=self.config.engine.interpret,
                mesh=self._mesh,
                active_cols=dfa_active,
                q_lens=jnp.where(
                    done, 0, 1 + jnp.sum(p_use, axis=1).astype(jnp.int32)
                ),
                moe_stats=sparse,
            )  # [B, chunk, C] float32

            # --- verify: accepted prefix = positions where the proposal IS
            # the budget-masked greedy argmax (the same mask formula as
            # _budget_mask, vectorised over chunk positions: two row
            # gathers at the J states the chain visited).
            rem_j = budgets[:, None] - e[:, None] - j_ar[None, :] - 1
            legal_j = mask_tab[s_before]  # [B, J, C]
            finish_j = legal_j & (
                dfa_eos[None, None, :]
                | (dfa_dist_succ[s_before] <= rem_j[..., None])
            )
            feas_j = jnp.any(finish_j, axis=-1, keepdims=True)
            m_j = jnp.where(feas_j, finish_j, legal_j)
            v = jnp.where(m_j, logits_c[:, :J, :], NEG_INF)
            vmax = jnp.argmax(v, axis=-1)  # [B, J]
            ok = (
                p_use
                & (vmax == p_cols)
                & (e[:, None] + j_ar[None, :] < budgets[:, None])
            )
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).astype(bool)
            a = jnp.sum(acc, axis=1).astype(jnp.int32)  # [B] accepted count

            # --- correction token from the first unaccepted position (the
            # standard speculation bonus: a+1 tokens per forward).
            s_full = jnp.concatenate([s_before, s_fin[:, None]], axis=1)
            st1 = s_full[b_idx, a]
            e1 = e + a
            key, sub = jax.random.split(key)
            mask = budget_mask(dfa, st1, budgets - e1 - 1)
            col = sample(
                logits_c[b_idx, a],
                sub,
                temperature=temperature,
                top_k=self.config.engine.top_k,
                mask=mask,
            ).astype(jnp.int32)
            nxt_id = dfa_active[col]
            newly_done = done | dfa_eos[col] | (e1 >= budgets)
            st_next = jnp.where(newly_done, st1, trans[st1, col])
            nxt = jnp.where(newly_done, pad, nxt_id)

            idx_p = jnp.where(acc, e[:, None] + j_ar[None, :], W)
            buf = buf.at[b_idx[:, None], idx_p].set(p_toks, mode="drop")
            buf = buf.at[b_idx, jnp.where(newly_done, W, e1)].set(
                nxt, mode="drop"
            )
            adv = jnp.where(done, 0, 1) + a  # p_use has ~done, so a=0 there
            prev2 = jnp.where(
                done | newly_done, prev, chunk_toks[b_idx, a]
            )
            ssm, ms = kept(kv, ms + fwd_ms[0] if sparse else ms, adv, done)
            return (
                it + 1,
                nxt,
                pos + adv,
                st_next,
                e1 + jnp.where(newly_done, 0, 1),
                newly_done,
                kv["k"],
                kv["v"],
                buf,
                prev2,
                key,
                ms,
                live + ~done,
                ssm,
            )

        def body(c):
            it, cur, pos, st, e, done, k_p, v_p, buf, prev, key, ms, live, ssm = c

            if chunk > 1 and constrained:
                # Fast-forward: chain of forced tokens after `cur`. Emission
                # stops permanently at the first non-forced state (state
                # freezes, emit stays False), at a forced EOS, or when the
                # per-row budget is exhausted mid-chain (`over`, only
                # reachable when the caller's budget is below the grammar's
                # minimum completion length and the mask degraded to legal).
                # Everything runs in compact column space; emitted buffer
                # entries are mapped back to token ids via active_ids.
                def ff_step(carry, _):
                    s, d, er = carry
                    row = mask_tab[s]  # [B, C]
                    t_c = jnp.argmax(row, axis=-1).astype(jnp.int32)
                    forced = (jnp.sum(row, axis=-1) == 1) & ~d
                    is_eos = forced & dfa_eos[t_c]
                    emit = forced & ~is_eos & (er < budgets)
                    over = forced & ~is_eos & (er >= budgets)
                    return (
                        jnp.where(emit, trans[s, t_c], s),
                        d | is_eos | over,
                        er + emit,
                    ), (jnp.where(emit, dfa_active[t_c], pad), emit)

                (st1, done1, e1), (ff_toks, ff_emit) = lax.scan(
                    ff_step, (st, done, e), None, length=chunk - 1
                )
                ff_toks = ff_toks.T  # [B, chunk-1] token ids
                ff_emit = ff_emit.T
                # Forced tokens land at buf slots e, e+1, ...; non-emitted
                # slots are routed out of range and dropped.
                idx = jnp.where(ff_emit, e[:, None] + jnp.cumsum(ff_emit, axis=1) - 1, W)
                buf = buf.at[b_idx[:, None], idx].set(ff_toks, mode="drop")
                chunk_toks = jnp.concatenate([cur[:, None], ff_toks], axis=1)
                adv_extra = jnp.sum(ff_emit, axis=1)
            else:
                st1, done1, e1 = st, done, e
                chunk_toks = cur[:, None]
                adv_extra = 0

            # One chunked forward consumes [cur, forced...]; pad slots past
            # a row's chain write garbage K/V that the next chunk overwrites
            # (decode_chunk_paged contract); done/free rows write to the
            # null page via their zeroed page-table rows. ``adv`` doubles
            # as the ragged q_lens: each row's live window is its own
            # consumed chain (0 for done rows — they idle through the
            # fused window at zero attention cost).
            adv = jnp.where(done, 0, 1) + adv_extra  # tokens consumed
            last_logits, kv, *fwd_ms = decode_chunk_paged(
                params,
                cfg,
                chunk_toks,
                pos,
                page_table,
                {"k": k_p, "v": v_p, "state": ssm},
                use_pallas=self._use_pallas,
                interpret=self.config.engine.interpret,
                mesh=self._mesh,
                logits_at=jnp.maximum(adv - 1, 0),  # [B, V]: chain-end only
                q_lens=adv,
                moe_stats=sparse,
            )

            key, sub = jax.random.split(key)
            if constrained:
                mask = budget_mask(dfa, st1, budgets - e1 - 1)
                col = sample(
                    last_logits[:, dfa_active],
                    sub,
                    temperature=temperature,
                    top_k=self.config.engine.top_k,
                    mask=mask,
                ).astype(jnp.int32)
                nxt_id = dfa_active[col]
                newly_done = done1 | dfa_eos[col] | (e1 >= budgets)
                st_next = jnp.where(newly_done, st1, trans[st1, col])
            else:
                nxt_id = sample(
                    last_logits,
                    sub,
                    temperature=temperature,
                    top_k=self.config.engine.top_k,
                    mask=self._unconstrained_mask,
                ).astype(jnp.int32)
                newly_done = done1 | (nxt_id == eos) | (e1 >= budgets)
                st_next = st1
            nxt = jnp.where(newly_done, pad, nxt_id)
            buf = buf.at[b_idx, jnp.where(newly_done, W, e1)].set(nxt, mode="drop")
            # prev = the token immediately before the new cur: the chain's
            # last consumed token (cur itself when nothing rode along).
            prev2 = jnp.where(
                done | newly_done,
                prev,
                chunk_toks[b_idx, jnp.maximum(adv - 1, 0)],
            )
            ssm, ms = kept(kv, ms + fwd_ms[0] if sparse else ms, adv, done)
            return (
                it + 1,
                nxt,
                pos + adv,
                st_next,
                e1 + jnp.where(newly_done, 0, 1),
                newly_done,
                kv["k"],
                kv["v"],
                buf,
                prev2,
                key,
                ms,
                live + ~done,
                ssm,
            )

        ssm0 = state
        rows_at_dispatch = []
        if windowed:
            past = ~done & (pos >= cfg.sliding_window)
            rows_at_dispatch = [jnp.stack([jnp.sum(past), jnp.sum(~done)]).astype(jnp.int32)]
        init = (
            jnp.asarray(0, jnp.int32),
            cur,
            pos,
            st,
            emitted,
            done,
            paged_k,
            paged_v,
            out_buf,
            prev,
            key,
            moe_stats_init(cfg) if sparse else None,
            # Row-forwards by state, counted where they happen: a row's
            # count of the forwards at whose start it was not done.
            jnp.zeros_like(done, jnp.int32),
            state,
        )
        it, cur, pos, st, e, done, k_p, v_p, buf, prev, key, ms, live, state = lax.while_loop(
            cond, draft_body if use_draft else body, init
        )
        out = (cur, pos, st, e, done, k_p, v_p, buf, prev, it, live)
        if not any(self._segment_stats):
            return out + (state,)
        # What the layer kinds did, for the lagged harvest's one fetch: the
        # expert counters of the segment's forwards, then the rows that were
        # live at dispatch and those of them at or past the window.
        return out + (jnp.concatenate(([ms] if sparse else []) + rows_at_dispatch), state)

    def _hetero_segment_impl(
        self,
        params,
        sdfa_trans,
        sdfa_mask,
        sdfa_dist,
        sdfa_active,
        sdfa_eos,
        cur,
        pos,
        st,
        emitted,
        done,
        budgets,
        page_table,
        paged_k,
        paged_v,
        out_buf,
        temp_v,
        cons_v,
        dfa_id,
        key,
        *,
        iters: int,
        chunk: int,
    ):
        """One bounded decode segment over a HETEROGENEOUS slab: each row
        carries its own temperature (``temp_v``), constrained flag
        (``cons_v``) and grammar (``dfa_id`` into the stacked [G, S, C]
        tables), so a grammar-constrained greedy /plan, a free-form sampled
        replan and a high-temperature exploration row all decode in the SAME
        fused forward — the per-row principle Ragged Paged Attention applied
        to the KV path, extended to sampling and grammar state. Per-row
        mechanics:

          - grammar fast-forward runs through the per-row tables; ``cons_v``
            gates forcing, and the trivial slot-0 DFA has two legal columns
            everywhere, so unconstrained rows never see a forced token;
          - each forward samples BOTH ways — budget-masked compact-column
            via the row's grammar slot, and full-vocab — then selects per
            row; greedy rows take the same mask-then-argmax the homogeneous
            path takes, so greedy outputs are token-identical to a
            homogeneous run of the same request (tested);
          - sampling statics are GONE: temperature/constrained are device
            values and the grammar is data, so this one executable (per
            iters/chunk config) serves every request mix — the compile
            count is independent of resident grammars and sampling configs.

        Prompt-lookup draft speculation is not offered here: its compact
        unembed and proposal chain are single-grammar, and hetero mode
        trades it for admission freedom (grammar fast-forward — the larger
        win on plan JSON — stays). Returns (cur, pos, st, emitted, done,
        pools_k, pools_v, out_buf, n_forwards, live_forwards [B])."""
        cfg = self.model_cfg
        tok = self.tokenizer
        B = cur.shape[0]
        W = out_buf.shape[1]
        sdfa = (sdfa_trans, sdfa_mask, sdfa_dist, sdfa_active, sdfa_eos)
        pad, eos = tok.pad_id, tok.eos_id
        b_idx = jnp.arange(B)

        def cond(c):
            it, cur, pos, st, e, done, k_p, v_p, buf, key, live = c
            return (it < iters) & jnp.any(~done)

        def body(c):
            it, cur, pos, st, e, done, k_p, v_p, buf, key, live = c

            if chunk > 1:

                def ff_step(carry, _):
                    s, d, er = carry
                    row = sdfa_mask[dfa_id, s]  # [B, C]
                    t_c = jnp.argmax(row, axis=-1).astype(jnp.int32)
                    forced = cons_v & (jnp.sum(row, axis=-1) == 1) & ~d
                    is_eos = forced & sdfa_eos[dfa_id, t_c]
                    emit = forced & ~is_eos & (er < budgets)
                    over = forced & ~is_eos & (er >= budgets)
                    return (
                        jnp.where(emit, sdfa_trans[dfa_id, s, t_c], s),
                        d | is_eos | over,
                        er + emit,
                    ), (jnp.where(emit, sdfa_active[dfa_id, t_c], pad), emit)

                (st1, done1, e1), (ff_toks, ff_emit) = lax.scan(
                    ff_step, (st, done, e), None, length=chunk - 1
                )
                ff_toks = ff_toks.T  # [B, chunk-1]
                ff_emit = ff_emit.T
                idx = jnp.where(
                    ff_emit, e[:, None] + jnp.cumsum(ff_emit, axis=1) - 1, W
                )
                buf = buf.at[b_idx[:, None], idx].set(ff_toks, mode="drop")
                chunk_toks = jnp.concatenate([cur[:, None], ff_toks], axis=1)
                adv_extra = jnp.sum(ff_emit, axis=1)
            else:
                st1, done1, e1 = st, done, e
                chunk_toks = cur[:, None]
                adv_extra = 0

            # adv doubles as the ragged q_lens (done rows idle at zero
            # attention cost through the fused window), like the
            # homogeneous segment above.
            adv = jnp.where(done, 0, 1) + adv_extra
            last_logits, kv = decode_chunk_paged(
                params,
                cfg,
                chunk_toks,
                pos,
                page_table,
                {"k": k_p, "v": v_p},
                use_pallas=self._use_pallas,
                interpret=self.config.engine.interpret,
                mesh=self._mesh,
                logits_at=jnp.maximum(adv - 1, 0),  # [B, V]: chain-end only
                q_lens=adv,
            )

            key, sub = jax.random.split(key)
            act_rows = sdfa_active[dfa_id]  # [B, C]
            mask = self._stacked_budget_mask(sdfa, dfa_id, st1, budgets - e1 - 1)
            col = sample_rows(
                jnp.take_along_axis(last_logits, act_rows, axis=-1),
                sub,
                temp_v,
                top_k=self.config.engine.top_k,
                mask=mask,
            ).astype(jnp.int32)
            c_tok = act_rows[b_idx, col]
            u_tok = sample_rows(
                last_logits,
                sub,
                temp_v,
                top_k=self.config.engine.top_k,
                mask=self._unconstrained_mask,
            ).astype(jnp.int32)
            nxt_id = jnp.where(cons_v, c_tok, u_tok)
            ended = jnp.where(cons_v, sdfa_eos[dfa_id, col], u_tok == eos)
            newly_done = done1 | ended | (e1 >= budgets)
            st_next = jnp.where(
                newly_done | ~cons_v, st1, sdfa_trans[dfa_id, st1, col]
            )
            nxt = jnp.where(newly_done, pad, nxt_id)
            buf = buf.at[b_idx, jnp.where(newly_done, W, e1)].set(nxt, mode="drop")
            return (
                it + 1,
                nxt,
                pos + adv,
                st_next,
                e1 + jnp.where(newly_done, 0, 1),
                newly_done,
                kv["k"],
                kv["v"],
                buf,
                key,
                live + ~done,
            )

        init = (
            jnp.asarray(0, jnp.int32),
            cur,
            pos,
            st,
            emitted,
            done,
            paged_k,
            paged_v,
            out_buf,
            key,
            jnp.zeros_like(done, jnp.int32),
        )
        it, cur, pos, st, e, done, k_p, v_p, buf, key, live = lax.while_loop(
            cond, body, init
        )
        return cur, pos, st, e, done, k_p, v_p, buf, it, live

    def _hetero_segment_spec_impl(
        self,
        params,
        sdfa_trans,
        sdfa_mask,
        sdfa_dist,
        sdfa_active,
        sdfa_eos,
        sdfa_dist_succ,
        sdfa_inv,
        cur,
        pos,
        st,
        emitted,
        done,
        budgets,
        page_table,
        paged_k,
        paged_v,
        out_buf,
        temp_v,
        cons_v,
        dfa_id,
        hstate,
        key,
        *,
        iters: int,
        K: int,
        draft: str,
    ):
        """One bounded SPECULATIVE decode segment over the heterogeneous
        slab (grammar-aware speculative decoding; engine/speculative.py has
        the drafter design). Each of the up-to-``iters`` iterations:

          1. **Draft**: the recurrent drafter proposes up to ``K`` tokens
             per row, pre-filtered through the row's stacked grammar DFA
             (``draft_window``) — constrained rows only ever draft
             admissible, budget-finishable, non-EOS tokens (single-
             successor states are forced, so plan scaffolding drafts
             itself); free rows (``dfa_id == 0``) draft unmasked from the
             drafter scores.
          2. **Verify**: ONE chunked forward over the fixed ``[B, K+1]``
             window ``[cur, drafts...]`` yields logits at every position;
             every position of every row is then sampled in ONE fused
             vocab-space pass (``sample_window_rows`` with a shared Gumbel
             tensor): the per-position admissibility masks fall out of the
             drafter's DFA walk for free, are gathered to vocab space
             through ``sdfa_inv`` (token → compact column), and free rows
             substitute the static unconstrained mask — one select and one
             argmax over ``[B, K+1, V]`` instead of separate compact and
             full-vocab draws. ``active_ids`` are strictly increasing per
             grammar, so the vocab-space argmax tie-breaks exactly like the
             legacy segment's compact-space argmax: greedy draws stay
             bit-identical (the parity invariant, tested).
          3. **Accept**: the sequential-sample rule (``accept_rows``): a
             row keeps the longest draft prefix its samples reproduce; the
             first mismatching sample is the correction token — so every
             forward nets ``accepted + 1`` tokens and emits, for any
             temperature, exactly what token-by-token decode would
             (greedy byte-identical, tested).

        Per-row accepted lengths are DATA (``emitted`` advances by
        ``a + 1``); the window never changes shape, so one executable
        serves every acceptance pattern, grammar mix and sampling config.
        Rejected window positions wrote garbage KV past the accepted end —
        the next iteration's window (which starts there) overwrites them,
        the same contract the fast-forward chunk relies on; admission
        reserves ``K+1`` pages of slack per row for exactly this.

        The ``iters`` loop is UNROLLED at trace time (a Python loop over a
        static count), not a ``lax.while_loop``: the loop carry would
        force per-iteration double-buffering of the KV pools on backends
        whose while lowering cannot alias them, which measured several
        times the body's own cost — and the early-exit the while loop
        bought only pays on an all-done slab (the drain tail), where the
        extra iterations are cheap no-ops (every row masked done). Returns
        (cur, pos, st, emitted, done, pools_k, pools_v, out_buf, hstate,
        drafted [B], accepted [B], n_forwards, live_forwards [B])."""
        cfg = self.model_cfg
        tok = self.tokenizer
        B = cur.shape[0]
        W = out_buf.shape[1]
        # draft_window consumes the precomputed successor-distance table in
        # the dist slot: budget-finishability costs ONE gather per visited
        # state instead of a chained transition-then-distance pair.
        sdfa_draft = (sdfa_trans, sdfa_mask, sdfa_dist_succ, sdfa_active, sdfa_eos)
        pad, eos = tok.pad_id, tok.eos_id
        V = self._unconstrained_mask.shape[0]
        b_idx = jnp.arange(B)
        j_ar = jnp.arange(K + 1)

        def body(c):
            cur, pos, st, e, done, k_p, v_p, buf, h, n_dr, n_ac, key, live = c

            # --- 1. draft K tokens per row through the grammar pre-filter.
            # The walk also emits the verify window's per-position
            # admissibility masks (it gathered them anyway at exactly the
            # states verification samples from).
            p_toks, p_use, s_before, s_fin, masks_w = draft_window(
                params["embed"],
                sdfa_draft,
                dfa_id,
                st,
                cur,
                h,
                e,
                budgets,
                done,
                cons_v,
                self._draft_free_mask,
                pad,
                k=K,
                mode=draft,
            )

            # --- 2. ONE verify forward over the fixed [B, K+1] window.
            # The window SHAPE is fixed (one executable per K), but the
            # rows are ragged DATA: each verifies cur + its own drafted
            # prefix (p_use is a prefix mask), so a row that drafted 2 of
            # K=8 streams pages for 3 positions and a done row for none —
            # the spec-verify path of the ragged kernel.
            window = jnp.concatenate([cur[:, None], p_toks], axis=1)
            logits_w, kv = decode_chunk_paged(
                params,
                cfg,
                window,
                pos,
                page_table,
                {"k": k_p, "v": v_p},
                use_pallas=self._use_pallas,
                interpret=self.config.engine.interpret,
                mesh=self._mesh,
                q_lens=jnp.where(
                    done, 0, 1 + jnp.sum(p_use, axis=1).astype(jnp.int32)
                ),
            )  # [B, K+1, V] float32

            # Per-position verification samples: position j is masked at
            # the DFA state after the window prefix 0..j with the budget
            # remaining at emission index e+j — exactly what sequential
            # decode would mask with there (``masks_w``, emitted by the
            # draft walk). The masks are gathered out of compact column
            # space into vocab space through the stacked inverse-column
            # table so constrained and free rows share ONE fused draw.
            col_of = sdfa_inv[dfa_id]  # [B, V] token -> column, -1 inactive
            vmask = jnp.take_along_axis(
                masks_w,
                jnp.broadcast_to(
                    jnp.clip(col_of, 0)[:, None, :], (B, K + 1, V)
                ),
                axis=-1,
            ) & (col_of >= 0)[:, None, :]
            mask_w = jnp.where(
                cons_v[:, None, None],
                vmask,
                self._unconstrained_mask[None, None, :],
            )
            key, sub = jax.random.split(key)
            # ONE full-vocab Gumbel tensor + ONE argmax serves every row
            # and position (sample_window_rows' gumbel path): greedy rows
            # add zeroed noise so their winner is the masked argmax, hot
            # rows draw via the Gumbel-max identity — on the CPU proxy the
            # second bit-generation pass and the two categorical
            # log-softmaxes this fuses away cost more than the verify
            # forward itself.
            gum = jax.random.gumbel(sub, logits_w.shape, jnp.float32)
            tok_w = sample_window_rows(
                logits_w,
                temp_v,
                top_k=self.config.engine.top_k,
                mask=mask_w,
                gumbel=gum,
            ).astype(jnp.int32)  # [B, K+1]

            # --- 3. accept the longest sample-reproduced draft prefix;
            # the sample at the first mismatch is the correction.
            acc, a = accept_rows(tok_w[:, :K], p_toks, p_use)
            e1 = e + a
            nxt_tok = tok_w[b_idx, a]
            # Winning token back to its compact column for the DFA advance
            # (>= 0 wherever cons_v selects it: constrained samples come
            # from the admissible support by construction).
            col_a = jnp.clip(col_of[b_idx, nxt_tok], 0)
            s_full = jnp.concatenate([s_before, s_fin[:, None]], axis=1)
            st1 = s_full[b_idx, a]
            ended = jnp.where(cons_v, sdfa_eos[dfa_id, col_a], nxt_tok == eos)
            newly_done = done | ended | (e1 >= budgets)
            st_next = jnp.where(
                newly_done | ~cons_v, st1, sdfa_trans[dfa_id, st1, col_a]
            )
            nxt = jnp.where(newly_done, pad, nxt_tok)

            idx_p = jnp.where(acc, e[:, None] + j_ar[None, :K], W)
            buf = buf.at[b_idx[:, None], idx_p].set(p_toks, mode="drop")
            buf = buf.at[b_idx, jnp.where(newly_done, W, e1)].set(
                nxt, mode="drop"
            )
            adv = jnp.where(done, 0, 1) + a  # done rows drafted nothing
            if draft == "recurrent":
                # Drafter state after absorbing cur + the accepted drafts
                # (the correction becomes the next cur, absorbed next
                # round); closed form, no scan.
                h2 = jnp.where(
                    done[:, None],
                    h,
                    advance_drafter_state(h, params["embed"], window, a + 1),
                )
            else:
                h2 = h  # grammar mode never reads the drafter state
            return (
                nxt,
                pos + adv,
                st_next,
                e1 + jnp.where(newly_done, 0, 1),
                newly_done,
                kv["k"],
                kv["v"],
                buf,
                h2,
                n_dr + jnp.sum(p_use, axis=1).astype(jnp.int32),
                n_ac + a,
                key,
                live + ~done,
            )

        c = (
            cur,
            pos,
            st,
            emitted,
            done,
            paged_k,
            paged_v,
            out_buf,
            hstate,
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32),
            key,
            jnp.zeros_like(done, jnp.int32),
        )
        for _ in range(max(1, iters)):
            c = body(c)
        cur, pos, st, e, done, k_p, v_p, buf, h, n_dr, n_ac, key, live = c
        return (
            cur, pos, st, e, done, k_p, v_p, buf, h, n_dr, n_ac,
            jnp.asarray(max(1, iters), jnp.int32), live,
        )

    # --- worker -----------------------------------------------------------
    def _worker(self) -> None:  # mcpx: thread-entry[engine-worker]
        try:
            self._setup()
        except BaseException as e:  # mcpx: ignore[broad-except] - stored as _startup_error, surfaced via start() and /healthz
            self._startup_error = e
            self._started.set()
            return
        self._started.set()
        slab = self._slab
        pending: "deque[GenerateRequest]" = deque()
        # Just-in-time dispatch (engine/pacing.py): until when the next
        # segment is being HELD for arrivals; None = not holding. Decided
        # after each pass's admission, so that what a harvest left pending
        # is admitted first; while it stands, the drain waits for arrivals
        # and the loop comes round again without dispatching, so a
        # caller's re-send joins the next segment and not the one after.
        hold: Optional[float] = None
        while True:
            # Decode-loop host profiler (telemetry/flight.py): lap() marks
            # tile the iteration's wall time into named phases; prof is
            # re-read each iteration so a live attach/detach lands at the
            # next tick. None = no phase is timed (the
            # pacer's stamps, one per admission, dispatch and ready stamp,
            # are all the clock this path reads). The TraceAnnotations put
            # the same phases, as
            # ``mcpx.worker.<phase>`` events, on this thread's line of a
            # profiler trace (POST /profile/start), the device ops' clock;
            # with no session open each costs an atomic load.
            prof = self._profiler
            if prof is not None:
                prof.loop_tick()
            with TraceAnnotation("mcpx.worker.drain"):
                self._drain_queue(
                    pending,
                    block=(not pending and slab.n_active == 0 and not self._inflight),
                    hold=hold,
                )
            if prof is not None:
                prof.lap("drain")
            if self._stop:
                break
            self._refresh_queue_gauges(pending)
            if prof is not None:
                prof.lap("host_bookkeeping")
            self._poll_admissions(slab)
            if prof is not None:
                prof.lap("poll")
            if self._spill_tier is not None:
                # Complete landed device->host spill fetches (non-blocking
                # is_ready polls; a no-op scan when nothing is in flight).
                self._spill_tier.poll()
                if prof is not None:
                    prof.lap("spill_copy")
            self._reap_cancelled(slab)
            if prof is not None:
                prof.lap("host_bookkeeping")
            admitted = 0
            if pending and slab.n_active < slab.B:
                admitted, t_admit = self._rows_admitted, self._pacer.clock()
                try:
                    with TraceAnnotation("mcpx.worker.admit"):
                        self._admit(slab, pending, holding=hold is not None)
                except BaseException as e:  # noqa: BLE001 - keep worker alive
                    log.exception("admission failed; failing resident rows")
                    self._fail_rows(slab, e)
                    self._reset_pools()
                admitted = self._rows_admitted - admitted
                if admitted > 0:
                    # A prefill chain went onto the device's queue, at this
                    # much host time: what the hold's deadline is made of.
                    self._pacer.admitted(t_admit, self._pacer.clock())
                    if hold is not None:
                        self._hold_joined += admitted
                if prof is not None:
                    prof.lap("admit")
            hold = self._hold_until(slab, pending)
            if hold is not None:
                # Time left before the segment in flight is ready, a row
                # free and nothing left behind: wait for company.
                continue
            if (
                admitted > 0
                and not self._inflight
                and slab.n_active < slab.B
                and not self._queue.empty()
            ):
                # From idle, a burst is still arriving (its later members
                # landed while the first were admitted): admit them too
                # before the first dispatch, so that the burst decodes as
                # one cohort and not as a sliver with the rest a segment
                # behind. Ends with the inbox or the free rows.
                continue
            if slab.n_active:
                try:
                    # Dispatch first, THEN fetch a lagged segment's flags:
                    # the fetch's round trip rides on top of the segment the
                    # device is already computing. After a hold that ran to
                    # its deadline the segment in flight is about to end:
                    # the dispatch lands behind it just in time, and the
                    # fetch blocks only briefly.
                    with TraceAnnotation("mcpx.worker.dispatch_submit"):
                        self._dispatch_segment(slab)
                    if prof is not None:
                        # Submit only — the async XLA enqueue's host cost.
                        # Blocking device waits show up as the "sync"
                        # carve inside harvest, so the fused-dispatch win
                        # (submit down) is attributable separately from
                        # "the device is now the bottleneck" (sync up).
                        prof.lap("dispatch_submit")
                    with TraceAnnotation("mcpx.worker.harvest"):
                        self._harvest(
                            slab,
                            keep_inflight=max(0, self.config.engine.pipeline_depth - 1),
                        )
                    if prof is not None:
                        prof.lap("harvest")
                except BaseException as e:  # noqa: BLE001 - keep worker alive
                    log.exception("decode segment failed; failing resident rows")
                    self._fail_rows(slab, e)
                    self._reset_pools()
            elif self._inflight:
                # Nothing active by the host's (lagged) view but segments
                # still in flight: drain them so idle blocking is safe.
                try:
                    with TraceAnnotation("mcpx.worker.harvest"):
                        self._harvest(slab, keep_inflight=0)
                except BaseException as e:  # noqa: BLE001 - keep worker alive
                    log.exception("segment harvest failed; failing resident rows")
                    self._fail_rows(slab, e)
                    self._reset_pools()
                if prof is not None:
                    prof.lap("harvest")
        # Shutdown: harvest what the device already finished — a request one
        # lagged flag-fetch away from delivery must resolve, not be failed —
        # then nothing resident, pending, or enqueued may be left hanging.
        if self._inflight:
            try:
                self._harvest(slab, keep_inflight=0)
            except BaseException:  # noqa: BLE001 - closing anyway
                log.exception("final harvest failed during shutdown")
        closed = EngineError("engine closed")
        self._fail_rows(slab, closed)
        for r in pending:
            r.loop.call_soon_threadsafe(_resolve, r.future, None, closed)
        while True:
            try:
                r = self._queue.get_nowait()
            except queue.Empty:
                break
            if isinstance(r, _PinPrefixOp):
                # A pin racing shutdown resolves to "nothing resident".
                r.loop.call_soon_threadsafe(_resolve, r.future, None, None)
            elif r is not None and not isinstance(r, _UnpinPrefixOp):
                r.loop.call_soon_threadsafe(_resolve, r.future, None, closed)

    def _refresh_queue_gauges(self, pending: "deque[GenerateRequest]") -> None:
        """Publish the per-class backlog and head-of-line age of the
        worker's pending line: a fresh dict swapped in whole (GIL-atomic)
        for queue_stats(), plus the /metrics gauges. Worker thread only;
        approximate by design — the numbers describe the instant between
        two segments."""
        n_cons = sum(1 for r in pending if r.constrained)
        n_free = len(pending) - n_cons
        head_ms = (
            (time.monotonic() - pending[0].enqueued_at) * 1e3 if pending else 0.0
        )
        self._pending_stats = {
            "constrained": n_cons,
            "free": n_free,
            "hol_wait_ms": head_ms,
        }
        self.metrics.queue_depth_class.labels(cls="constrained").set(n_cons)
        self.metrics.queue_depth_class.labels(cls="free").set(n_free)
        # Radix prefix-cache counters -> Prometheus, as deltas so the cache
        # itself stays metrics-free (one sync point, no double counting).
        c = self._prefix_cache
        seen = self._prefix_seen
        for attr, metric in (
            ("hits", self.metrics.prefix_hits),
            ("misses", self.metrics.prefix_misses),
            ("evictions", self.metrics.prefix_evictions),
            ("matched_tokens", self.metrics.prefix_matched_tokens),
        ):
            cur = getattr(c, attr)
            if cur > seen[attr]:
                metric.inc(cur - seen[attr])
                seen[attr] = cur
            elif cur < seen[attr]:  # rollback reversed an insert/eviction
                seen[attr] = cur
        self.metrics.prefix_shared_pages.set(
            c.resident_tokens // max(1, c.page_size)
        )
        tier = self._spill_tier
        if tier is not None:
            seen = self._spill_seen
            for attr, metric in (
                ("spills", self.metrics.kv_spills),
                ("readmits", self.metrics.kv_readmits),
                ("destructive_evictions", self.metrics.kv_destructive_evictions),
                ("host_evictions", self.metrics.kv_host_evictions),
                ("denied_readmits", self.metrics.kv_denied_readmits),
            ):
                cur = getattr(tier, attr)
                if cur > seen[attr]:
                    metric.inc(cur - seen[attr])
                    seen[attr] = cur
            self.metrics.kv_host_tokens.set(tier.host_tokens)
            self.metrics.kv_host_bytes.set(tier.host_bytes_used)
        if self._governor is not None:
            for tenant, tokens in self._governor.resident_by_tenant().items():
                # Bounded label space: the governor folds tenants past its
                # cardinality cap into "other" before they reach here.
                self.metrics.kv_tenant_resident_tokens.labels(
                    tenant=tenant
                ).set(tokens)

    def _hold_until(
        self, slab: "_Slab", pending: "deque[GenerateRequest]"
    ) -> Optional[float]:
        """Until when the next segment is held for arrivals (the decision
        is ``pacing.hold_until``); None = dispatch now. Worker thread only.
        The hold refers to the NEWEST segment in flight, and only while it
        still decodes a resident row and the device has not finished it: a
        segment of retired rows ends at once, whatever its forwards."""
        busy = False
        if self._inflight:
            done_d, gen_snap = self._inflight[-1][0], self._inflight[-1][4]
            busy = not done_d.is_ready() and any(
                r is not None and gen_snap[i] == slab.gen[i]
                for i, r in enumerate(slab.req)
            )
        pacer = self._pacer
        return hold_until(
            pacer.clock(),
            in_flight=busy,
            free_rows=slab.B - slab.n_active,
            backlog=len(pending),
            ready_at=pacer.ready_at(),
            margin=pacer.margin_s,
        )

    def _hold_wait(self, until: float) -> Any:
        """Block on the queue until an item arrives (returned) or the hold
        ends (``queue.Empty``): at ``until``, or as soon as the device has
        finished the segment in flight, which is looked at once a
        predicted forward so that a segment whose rows all finished early
        is not found out a whole period late."""
        pacer = self._pacer
        newest = self._inflight[-1][0]
        while True:
            remaining = until - pacer.clock()
            if remaining <= 0 or newest.is_ready():
                raise queue.Empty
            try:
                return self._queue.get(timeout=min(remaining, pacer.forward_s))
            except queue.Empty:
                continue

    def _drain_queue(
        self,
        pending: "deque[GenerateRequest]",
        block: bool,
        hold: Optional[float] = None,
    ) -> None:
        """Move queued requests into ``pending``. When idle (``block``), wait
        briefly for the first arrival, then hold a short gather window so a
        burst forms one large admission cohort instead of a size-1 prefill
        followed by stragglers. While the next segment is held (``hold``,
        the time the hold ends) wait until then instead, with the same
        gather window: the callers a harvest answered re-send together."""
        prof = self._profiler
        # Blocking waits are carved out of the enclosing drain lap so the
        # profile separates waiting from moving work: "idle" waits for
        # work, "hold" for company while the device is busy.
        waiting = "hold" if hold is not None else "idle"
        try:
            if block or hold is not None:
                t_wait = prof.mark() if prof is not None else 0.0
                try:
                    with TraceAnnotation(f"mcpx.worker.{waiting}"):
                        item = (
                            self._hold_wait(hold)
                            if hold is not None
                            else self._queue.get(timeout=0.05)
                        )
                finally:
                    if prof is not None:
                        prof.carve(waiting, t_wait)
            else:
                item = self._queue.get_nowait()
        except queue.Empty:
            return
        first_arrival = item is not None and (block or hold is not None)
        while True:
            if item is None:
                self._stop = True
                return
            if not self._apply_control_op(item):
                self._to_pending(pending, item)
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
        if first_arrival:
            deadline = time.monotonic() + 0.003
            if hold is not None:
                deadline = min(deadline, hold)
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return
                t_wait = prof.mark() if prof is not None else 0.0
                try:
                    with TraceAnnotation(f"mcpx.worker.{waiting}"):
                        item = self._queue.get(timeout=remaining)
                except queue.Empty:
                    return
                finally:
                    if prof is not None:
                        prof.carve(waiting, t_wait)
                if item is None:
                    self._stop = True
                    return
                if not self._apply_control_op(item):
                    self._to_pending(pending, item)

    @staticmethod
    def _to_pending(pending: "deque[GenerateRequest]", r: GenerateRequest) -> None:
        if r.span is not None:
            # The worker has now SEEN the request: until here it sat in
            # queue.Queue (engine.queue_wait's unseen_ms).
            r.seen_at = time.monotonic()
        pending.append(r)

    def _apply_control_op(self, item: Any) -> bool:
        """Apply a control op riding the request queue (prefix pin / unpin,
        grammar warm — all from the event loop); returns whether ``item``
        was one. Worker thread only — the single-writer discipline is
        exactly why they travel through the queue instead of touching the
        tree or the pools cross-thread."""
        if isinstance(item, _PinPrefixOp):
            node = self._prefix_cache.lookup(item.ids)
            if node is not None:
                node.refs += 1
            item.loop.call_soon_threadsafe(_resolve, item.future, node, None)
            return True
        if isinstance(item, _UnpinPrefixOp):
            if item.node.refs > 0:
                item.node.refs -= 1
            return True
        if isinstance(item, _WarmGrammarOp):
            err: Optional[BaseException] = None
            try:
                self._warm_grammar(item.grammar)
            # The warm's calls donate the pools: recover as a failed segment
            # does, and hand the cause to the caller's future.
            except BaseException as e:  # noqa: BLE001 - keep worker alive
                log.exception("grammar warm failed; failing resident rows")
                err = e
                self._fail_rows(self._slab, e)
                self._reset_pools()
            item.loop.call_soon_threadsafe(_resolve, item.future, None, err)
            return True
        return False

    def _admit(
        self,
        slab: "_Slab",
        pending: "deque[GenerateRequest]",
        holding: bool = False,
    ) -> None:
        """Admit pending requests into free slab rows: prefill the cohort,
        commit its KV to pages, first-sample, merge row state.

        Homogeneous mode (``hetero_batch=off``): compatibility (constrained
        flag, temperature, grammar object) is slab-wide — all resident rows
        share one fused decode segment. When the slab is empty its config
        resets to the head request's. A pending request incompatible with a
        busy slab waits for it to drain; ``fairness_timeout_s`` stops
        further admissions once the head of the line has waited that long,
        so a steady compatible stream cannot starve it forever.

        Heterogeneous mode (``hetero_batch=on``): sampling config and
        grammar are per-row state, so ANY pending request fits ANY free row
        and admission is strictly queue-ordered — no compatibility gate, no
        drain-to-switch. The small-cohort hysteresis (prefill amortisation)
        still applies; the only ordering exceptions left are page pressure,
        a full stacked-grammar slot table (where ``fairness_timeout_s``
        bounds the wait: an over-age slot-starved request stops admissions
        behind it until a slot drains), and differing shared-prefix keys
        (which only shape cohorts, not rows)."""
        ecfg = self.config.engine
        tok = self.tokenizer
        free = slab.free_rows()
        if not free or not pending:
            return
        if self._spill_tier is not None:
            # New admission cycle: reset the tier's copy-bandwidth budget
            # (spills and readmits both draw on it; overruns degrade to
            # destructive eviction / shorter matches, never a stall).
            self._spill_tier.begin_cycle()
        if slab.n_active == 0:
            slab.hetero = ecfg.hetero_batch  # mode latch: see _Slab.hetero
            slab.spec_k = self._spec_k()  # speculative latch, same rules
            slab.spec = slab.spec_k > 0
            slab.spec_draft = ecfg.speculative.draft
        elif slab.hetero != ecfg.hetero_batch or slab.spec_k != self._spec_k() or (
            slab.spec and slab.spec_draft != ecfg.speculative.draft
        ):
            # A mode flag flipped while rows admitted under the OLD mode
            # are still decoding: their page-slack geometry belongs to that
            # mode, so pause admission and let them drain — the flip lands
            # at the next empty-slab admission. This is what makes a
            # runtime flip (operator rollback)
            # safe rather than merely documented-safe.
            return
        hetero = slab.hetero
        if not hetero and slab.n_active == 0:
            head = pending[0]
            slab.constrained = head.constrained
            slab.temperature = head.temperature
            slab.grammar = head.grammar
        elif not hetero and not slab.compatible(pending[0]) and (
            time.monotonic() - pending[0].enqueued_at > ecfg.fairness_timeout_s
        ):
            return  # drain the slab so the head of the line can run
        elif not holding and slab.n_active and len(free) < (
            ecfg.admit_min_free or max(1, slab.B // 4)
        ) and (
            time.monotonic() - self._last_admit_t < ecfg.admit_max_wait_s
        ):
            # Busy slab, few free rows, admitted recently: keep decoding and
            # let retirements accumulate into a worthwhile prefill cohort
            # instead of paying a compute-bound prefill for a sliver. The
            # clock is time-since-LAST-admission (not request age — under
            # saturation every queued request is "old", which would disable
            # the guard exactly when it matters): small cohorts are rate-
            # limited to one per admit_max_wait_s, full ones go immediately.
            # Not while the next segment is HELD (``holding``): then there
            # is nothing to keep decoding instead, no retirement can come
            # before the hold ends, and the request would wait a whole
            # period for a row that is free now.
            return

    # --- prefix locality + declared-head pre-build ------------------------
        # Locality-aware admission (radix prefix cache): group cohort
        # admits by shared-prefix depth against the resident tree so
        # co-resident rows maximise sharing — EDF/age-guarded so the
        # serving scheduler's deadline ordering survives the regroup.
        prof = self._profiler
        if ecfg.prefix_cache:
            t_ls = prof.mark() if prof is not None else 0.0
            self._locality_sort(slab, pending)
            if prof is not None:
                prof.carve("locality_sort", t_ls)
        if hetero:
            head_req = next((r for r in pending if not r.future.cancelled()), None)
        else:
            head_req = next((r for r in pending if slab.compatible(r)), None)
        if head_req is None:
            return
        # Retired rows' DEVICE page tables must be zeroed BEFORE any pages
        # are (re)allocated below (prefix build or cohort prefill writes
        # into freed pages; a dirty row's in-flight garbage writes must be
        # pointed at the null page first). Async dispatch, device-ordered
        # ahead of the prefills.
        if self._dirty_rows:
            self._dispatch_merge(slab, [])
        hold: Optional[PrefixNode] = None
        head_key = (
            head_req.prefix_key(ecfg.kv_page_size)
            # (a head no recurrent layer could start from is not built)
            if ecfg.prefix_cache and self.model_cfg.suffix_route
            else None
        )
        warm_head = (
            self._pop_warm_head(head_req)
            if ecfg.prefix_cache and self._warm_heads
            else None
        )
        if head_key is not None and self._spill_tier is not None:
            # Warm-restart bookkeeping: the snapshot records the declared
            # heads this engine actually served (bounded LRU).
            self._declared_heads[head_key] = head_req.tenant
            self._declared_heads.move_to_end(head_key)
            while len(self._declared_heads) > 64:
                self._declared_heads.popitem(last=False)
        if head_key is not None or warm_head is not None:
            # Cold-start sharing: make the DECLARED shared head resident in
            # the radix tree before the cohort prefills, so even the first
            # burst's rows share it instead of each prefilling its own copy
            # (per-row matching below picks it up like any resident path).
            # A snapshot head whose KV could not be restored rebuilds here
            # too — lazily, on its first matching use after restart.
            t_pm = prof.mark() if prof is not None else 0.0
            t_build, self._prefix_built = time.monotonic(), (0, 0)
            try:
                if warm_head is not None:
                    if (
                        self._ensure_prefix(warm_head[0], tenant=warm_head[1])
                        is None
                    ):
                        # Build refused (page pressure / geometry): requeue
                        # the head — it retries on the next matching
                        # request instead of being silently lost.
                        self._warm_heads.append(warm_head)
                if head_key is not None:
                    hold = self._ensure_prefix(head_key, tenant=head_req.tenant)
            except BaseException as e:  # noqa: BLE001 - prefill donated pools
                if warm_head is not None:
                    # The popped snapshot head must survive the failure —
                    # it retries on the next matching request.
                    self._warm_heads.append(warm_head)
                log.exception("prefix build failed; failing resident rows")
                self._fail_rows(slab, e)
                self._reset_pools()
                return
            finally:
                if prof is not None:
                    prof.carve("prefix_match", t_pm)
            chunks, built = self._prefix_built
            if chunks and head_req.span is not None:
                # The head's build, on the request that paid for it.
                head_req.span.child(
                    "engine.prefix_build", t0=t_build, t1=time.monotonic(),
                    chunks=chunks, head_tokens=built,
                )
        if hold is not None:
            # Admission hold: page-pressure eviction inside the cohort loop
            # must never free the head this very admission is wiring into
            # page tables (rows take their own refs only as they commit).
            hold.refs += 1
        try:
            self._admit_cohort(slab, pending)
        finally:
            if hold is not None:
                hold.refs -= 1

    def _locality_sort(
        self, slab: "_Slab", pending: "deque[GenerateRequest]"
    ) -> None:
        """Reorder the pending line by shared-prefix depth against the
        resident radix tree (deepest first — those rows prefill almost
        nothing and their pins keep the shared subtree warm), via the
        EDF-safe sort in scheduler/locality.py: over-age requests and
        requests whose deadline cannot afford a regroup keep strict
        earliest-deadline-first order at the front. Stable, so an empty
        tree reproduces arrival order byte-for-byte; bounded to a window
        of 4 slabs' worth so a deep backlog costs O(window) probes, not
        O(queue)."""
        if len(pending) < 2 or not self._prefix_cache.n_nodes:
            return
        window = min(len(pending), 4 * slab.B)
        items = list(pending)
        head, tail = items[:window], items[window:]
        cache = self._prefix_cache
        ordered = locality_order(
            head,
            now=time.monotonic(),
            depth_of=lambda r: cache.probe(r.prompt_ids),
            enqueued_of=lambda r: r.enqueued_at,
            deadline_of=lambda r: r.deadline_at,
            age_cap_s=self.config.engine.fairness_timeout_s,
            # A non-urgent request must tolerate roughly one regrouped
            # cohort wave: two service intervals plus dispatch noise.
            deadline_slack_s=2.0 * self._ewma_service_s + 0.05,
        )
        # Identity compare: "did the order change" — the dataclass __eq__
        # would diff prompt_ids element-wise per displaced pair.
        if any(a is not b for a, b in zip(ordered, head)):
            pending.clear()
            pending.extend(ordered)
            pending.extend(tail)

    def _admit_cohort(
        self,
        slab: "_Slab",
        pending: "deque[GenerateRequest]",
    ) -> None:
        ecfg = self.config.engine
        tok = self.tokenizer
        hetero = slab.hetero  # the latched admission mode, not the live flag
        free = slab.free_rows()
        cache = self._prefix_cache
        use_prefix = bool(ecfg.prefix_cache)
        # A recurrent layer starts from the STATE at a hit's boundary and no
        # radix node holds one: such a model's rows prefill whole (and still
        # insert their heads: pages are pages), and a row whose pages were
        # resident is a counted miss.
        no_state = use_prefix and not self.model_cfg.suffix_route
        # ... but where the declared head's END STATE is kept
        # (``GemmaConfig.head_state``) a row whose prompt starts with that
        # head matches it at EXACTLY its length and starts from a copy of the
        # state; any other depth has no state and is the same counted miss.
        stateful = use_prefix and self.model_cfg.head_state
        # ... and where the state is kept a PAGE (``GemmaConfig.page_state``:
        # a short convolution's tail) a row matches the tree at ANY depth, as
        # a default-block row does, and its suffix prefill starts every such
        # layer from the tail of its last matched page: a counted hit. Such a
        # model has no miss.
        paged_state = use_prefix and self.model_cfg.page_state
        head = self._head_state if stateful else None
        head_slot = ecfg.max_batch_size
        psz = ecfg.kv_page_size

        behind_head: dict[int, bool] = {}  # a request's prompt starts with the head: compared once

        def _match_cap(r: GenerateRequest, cap_tokens: int) -> int:
            """How deep ``r`` may match: the tree's own cap under
            ``cap_tokens``, or the head's length alone (0: nothing)."""
            cap = min(cap_tokens, cache.match_cap(len(r.prompt_ids)))
            if not stateful:
                return cap
            if head is None or cap < len(head):
                return 0
            if id(r) not in behind_head:
                behind_head[id(r)] = tuple(r.prompt_ids[: len(head)]) == head
            return len(head) if behind_head[id(r)] else 0

    # --- per-request geometry
        # Hetero slabs always run the constrained-width chunk (the segment
        # is one executable for every mix; unconstrained rows just never
        # force), so every row's pages carry the chunk's garbage-write slack.
        # Under the speculative latch the window is [K+1] wide instead —
        # rejected draft positions write garbage KV past the accepted end,
        # so rows need that window's slack.
        if hetero and slab.spec:
            spec_chunk = slab.spec_k + 1
        else:
            spec_chunk = self._spec_chunk(True if hetero else slab.constrained)
        slack = spec_chunk if spec_chunk > 1 else 0
        capacity = ecfg.max_pages_per_seq * ecfg.kv_page_size
        base_budget_cap = min(slab.steps, capacity - 1 - slack)
        base_eligible = tuple(b for b in self._prefill_buckets if b <= capacity)
        if base_budget_cap < 1 or not base_eligible:
            err = EngineError(
                f"page capacity {capacity} (max_pages_per_seq*kv_page_size) "
                f"cannot fit any decode budget/prefill bucket"
            )
            while pending:
                r = pending.popleft()
                r.loop.call_soon_threadsafe(_resolve, r.future, None, err)
            return

    # --- stage 1: candidate scan (prefix-independent admission gates)
        cands: list[tuple[GenerateRequest, int]] = []
        reserved: set[int] = set()
        defer: list[GenerateRequest] = []
        while pending and len(cands) < len(free):
            r = pending.popleft()
            if r.future.cancelled():
                # Abandoned while queued (client disconnect / timeout):
                # skipping here saves the prefill compute and pages that
                # _reap_cancelled would otherwise claw back a tick later.
                continue
            if hetero:
                slot = 0
                if r.constrained:
                    slot = self._grammar_slot_for(r.grammar or self.grammar, reserved)
                    if slot is None:
                        # Every stacked slot holds a LIVE grammar: this
                        # request waits for one to drain — the only
                        # config-shaped admission wait left under hetero.
                        # fairness_timeout_s still bounds it: once this
                        # request has waited that long, nothing behind it
                        # admits either, so resident rows retire (decode is
                        # budget-bounded), a slot's refcount hits zero, and
                        # the next admission serves it — a later-arriving
                        # stream on the resident grammars cannot starve it.
                        defer.append(r)
                        if (
                            time.monotonic() - r.enqueued_at
                            > ecfg.fairness_timeout_s
                        ):
                            break
                        continue
                    reserved.add(slot)
            elif not slab.compatible(r):
                # Homogeneous slab: different sampling config waits for a
                # drain (the drain-to-switch path hetero_batch deletes).
                defer.append(r)
                continue
            else:
                slot = 0
            cands.append((r, slot))

        def _geometry(r: GenerateRequest, P: int) -> tuple[int, list[int]]:
            """(decode budget, suffix ids) for ``r`` admitted at matched
            depth ``P``. Keeps the prompt HEAD on overflow — the planner
            ranks its best candidate services first and trims the tail,
            and the engine must agree (VERDICT r2 weak #4)."""
            budget = max(
                1, min(r.max_new_tokens, min(slab.steps, capacity - 1 - slack - P))
            )
            elig_last = max(b for b in base_eligible if b + P <= capacity)
            longest = min(elig_last, capacity - P - budget - slack)
            ids = r.prompt_ids[P : P + longest] or [tok.bos_id]
            return budget, ids

        def _usable_depth(r: GenerateRequest, cap_tokens: int) -> int:
            """Matched depth for ``r`` under ``cap_tokens``, degraded to 0
            when that depth leaves no room for a decode budget or any
            prefill bucket (serve without reuse rather than failing)."""
            if not use_prefix or no_state or cap_tokens <= 0:
                return 0
            cap = _match_cap(r, cap_tokens)
            P = cache.probe(r.prompt_ids, cap) if cap > 0 else 0
            if P <= 0 or (stateful and P != cap):
                return 0
            if min(slab.steps, capacity - 1 - slack - P) < 1 or not any(
                b + P <= capacity for b in base_eligible
            ):
                return 0
            return P

    # --- stage 2: prefill-bucket fix-point over the candidate plans.
        # Per-row matched depths and the cohort's (shared) prefill bucket T
        # are mutually dependent: suffix-prefill pad positions index the
        # page table at (P + t)//page_size for t < T, so every row must
        # satisfy P + T <= capacity — but shrinking a row's P grows its
        # suffix, which can grow T. Iterate: plan under a T limit, recompute
        # the T the plan needs, restart if it grew. T is bucket-quantised
        # and monotone non-decreasing, so this terminates within
        # len(buckets) passes of pure host bookkeeping (read-only probes).
        prof = self._profiler
        t_pm = prof.mark() if prof is not None else 0.0
        T = base_eligible[0]
        planned: list[tuple[int, int, list[int]]] = []  # (P, budget, ids)
        while True:
            planned = []
            worst = 1
            for r, _slot in cands:
                P = _usable_depth(r, capacity - T)
                budget, ids = _geometry(r, P)
                planned.append((P, budget, ids))
                worst = max(worst, len(ids))
            T_needed = _bucket(worst, base_eligible)
            if T_needed <= T:
                break
            T = T_needed
        if prof is not None:
            # The radix-probe fix-point is the admission path's pure
            # prefix-matching cost (stage-3 re-matches are commit noise).
            prof.carve("prefix_match", t_pm)

    # --- stage 3: commit — match+pin, plan the radix insert, allocate.
        cohort: list[GenerateRequest] = []
        prompts: list[list[int]] = []  # SUFFIX ids (whole prompt when P == 0)
        budgets: list[int] = []
        slots: list[int] = []  # stacked-DFA slot per cohort member (hetero)
        prefixes: list[tuple[int, list[int], tuple]] = []  # (P, pages, nodes)
        sids: list[tuple] = []
        row_pages: list[list[int]] = []
        pushback: list[GenerateRequest] = []
        ledger_on = self._ledger_on
        tier = self._spill_tier
        for k, (r, slot) in enumerate(cands):
            if pushback:
                pushback.append(r)
                continue
            P, budget, ids = planned[k]
            mnode: Optional[PrefixNode] = None
            mpages: list[int] = []
            # Readmit copy tokens this request's match pulls host->device
            # (cost-ledger item): _try_readmit runs inside cache.match, so
            # the tier's counter delta around it is exactly this row's bill.
            copy0 = (
                tier.readmit_tokens if (ledger_on and tier is not None) else 0
            )
            if P > 0:
                # record=False: hit/miss accounting happens AFTER the
                # degrade decision below — a match the row cannot use
                # (tree shrank, geometry infeasible) must not inflate the
                # reuse counters.
                P2, mpages, mnode = cache.match(
                    r.prompt_ids, _match_cap(r, capacity - T), record=False,
                )
                if stateful and P2 != P:
                    P2 = 0  # a part of the head has no state to start from
                if P2 != P:
                    # The tree changed between plan and commit (an earlier
                    # cohort-mate's insert evicted a planned node under
                    # budget pressure): recompute this row's geometry at
                    # the depth actually matched — P only ever SHRINKS
                    # here. The regrown suffix is clamped to the fix-point
                    # T below, so other rows' P + T <= capacity invariant
                    # survives (their pad positions index the page table
                    # at (P + t)//page_size for t < T).
                    P = P2 if P2 and min(
                        slab.steps, capacity - 1 - slack - P2
                    ) >= 1 else 0
                    if P == 0:
                        mpages, mnode = [], None
                    budget, ids = _geometry(r, P)
                    ids = ids[:T]
            if mnode is not None:
                mnode.refs += 1
            # Insert the page-aligned remainder of the prompt into the
            # tree: the NEXT request sharing this head re-prefills none of
            # it. Collision (a pending cohort-mate's branch, divergence
            # inside the first page) or budget pressure skips caching —
            # never the admission.
            ins = 0
            inode: Optional[PrefixNode] = None
            if use_prefix and not stateful:  # (no row could start from a node below the head)
                want = ((P + len(ids)) // psz) * psz - P
                if want > 0:
                    inode = cache.insert(r.prompt_ids, P, want, tenant=r.tenant)
                    if inode is not None:
                        ins = want
            need = len(ids) - ins + budget + slack
            if not self._allocator.can_allocate(need):
                self._evict_prefixes(need)
                if not self._allocator.can_allocate(need):
                    # FIFO: wait for pages; unwind this row's tree state and
                    # push it (and everything after it) back unreordered.
                    if inode is not None:
                        cache.rollback(inode)
                    if mnode is not None:
                        mnode.refs -= 1
                    pushback.append(r)
                    continue
            self._seq_counter += 1
            sid = ("seq", self._seq_counter)
            pages = self._allocator.allocate(sid, need)
            # Hit/miss accounting only for rows that actually ADMIT (the
            # counters are per admitted request; a pushed-back row would
            # otherwise count twice across its two admissions).
            if use_prefix:
                if P > 0:
                    cache.hits += 1
                    cache.matched_tokens += P
                else:
                    cache.misses += 1
                if (stateful or paged_state) and P > 0:
                    self._prefix_state_hits += 1
                    self.metrics.prefix_state.labels(event="hit").inc()
                elif (no_state or stateful) and cache.probe(
                    r.prompt_ids, min(capacity - T, cache.match_cap(len(r.prompt_ids)))
                ) > 0:
                    self._prefix_state_misses += 1
                    self.metrics.prefix_state.labels(event="miss").inc()
                if self._governor is not None:
                    # Per-tenant reuse accounting: matched vs prefilled
                    # tokens — the per-tenant hit-rate spread GET /cache
                    # serves.
                    self._governor.on_lookup(r.tenant, P, len(ids))
            cohort.append(r)
            prompts.append(ids)
            budgets.append(budget)
            slots.append(slot)
            nodes = tuple(n for n in (mnode, inode) if n is not None)
            copy_toks = (
                tier.readmit_tokens - copy0
                if (ledger_on and tier is not None)
                else 0
            )
            prefixes.append(
                (P, mpages + (inode.pages if inode else []), nodes, copy_toks)
            )
            sids.append(sid)
            row_pages.append(pages)
        for r in reversed(pushback):
            pending.appendleft(r)
        for r in reversed(defer):
            pending.appendleft(r)
        if not cohort:
            return
        # A row behind a matched prefix sends the whole cohort down the
        # suffix route; which row buckets exist there, at this T, is the
        # table's to say (what warm-up compiled).
        any_prefix = any(pfx[0] > 0 for pfx in prefixes)
        A = _bucket(len(cohort), self._cohort_buckets(T, any_prefix))
        # The STAGE-2 fix-point T, not a recompute from the committed
        # prompts: every planned match depth satisfies P + T <= capacity
        # against THIS T, and a commit-time degraded row's regrown suffix
        # was clamped to it — recomputing from prompts could grow T past
        # another deep-prefix row's invariant.
        tokens = np.full((A, T), tok.pad_id, np.int32)
        seq_lens = np.ones((A,), np.int32)
        positions = np.zeros((A,), np.int32)  # per-row suffix start offsets
        active = np.zeros((A,), bool)
        budgets_np = np.zeros((A,), np.int32)
        # Per-row sampling config scattered at merge: the head request's
        # slab-wide config in homogeneous mode, each request's own in
        # hetero mode (padding lanes stay at the inert defaults).
        temp_np = np.zeros((A,), np.float32)
        cons_np = np.zeros((A,), bool)
        dfa_np = np.zeros((A,), np.int32)
        table = np.zeros((A, ecfg.max_pages_per_seq), np.int32)
        for j, (r, ids, budget) in enumerate(zip(cohort, prompts, budgets)):
            ids = ids[:T]
            tokens[j, : len(ids)] = ids
            seq_lens[j] = len(ids)
            active[j] = True
            budgets_np[j] = budget
            if hetero:
                temp_np[j] = r.temperature
                cons_np[j] = r.constrained
                dfa_np[j] = slots[j]
            else:
                temp_np[j] = slab.temperature
                cons_np[j] = slab.constrained
            # Page-table layout: [matched tree pages][this row's inserted
            # tree pages][row-private pages] — positions < P read the
            # shared, read-only tree run; the suffix prefill writes
            # [P, P+len(ids)) into the inserted+private pages; decode
            # writes land strictly past the prompt, in private pages.
            P, shared_pages, _nodes, _copy = prefixes[j]
            positions[j] = P
            n_pp = P // psz
            n_sh = len(shared_pages)
            table[j, :n_pp] = shared_pages[:n_pp]
            table[j, n_pp:n_sh] = shared_pages[n_pp:]
            table[j, n_sh : n_sh + len(row_pages[j])] = row_pages[j]
        self.metrics.kv_page_utilization.set(self._allocator.stats().utilization)

        try:
            t0 = time.monotonic()
            dfa = None if hetero else self._dfa_for(slab.grammar or self.grammar)
            sdfa = self._stacked_dfa() if hetero else None
            # All of this admission's row arrays go up in ONE dispatch
            # (budgets/active/sampling-config ride along for the admit call
            # and the admit-merge below).
            rs, rs2 = self._row_spec(A), self._row_spec(A, 1)
            # The slab rows this cohort takes (``free``, in order, below) are
            # its rows' slots of the state pool.
            slots_d = self._cohort_slots(A, free[: len(cohort)])
            if any_prefix:
                (
                    tokens_d, lens_d, p_d, table_d, budgets_d, active_d,
                    temp_d, cons_d, dfa_d,
                ) = self._put_many(
                    (tokens, rs2),
                    (seq_lens, rs),
                    (positions, rs),
                    (table, rs2),
                    (budgets_np, rs),
                    (active, rs),
                    (temp_np, rs),
                    (cons_np, rs),
                    (dfa_np, rs),
                )
                # Suffix-only prefill: one chunked forward whose queries
                # start at each row's OWN matched offset (``positions`` is
                # per-row data — ragged rows share one executable) and
                # attend the shared radix-tree pages + themselves
                # (decode_chunk_paged's contract) — a matched prefix's
                # FLOPs are paid once per resident tree path, not per
                # request.
                src_d = None
                if stateful:
                    # A row that matched the head reads the head's state; one
                    # that did not starts from an empty one (out of range).
                    src = np.full((A,), int(self._state_pool["n"].shape[0]), np.int32)
                    src[: len(cohort)] = [
                        head_slot if pfx[0] > 0 else src[0] for pfx in prefixes
                    ]
                    src_d = self._put(src, rs)
                last_logits, k_p, v_p, moe_d, self._state_pool = self._jit_suffix_prefill(
                    self._params,
                    tokens_d,
                    lens_d,
                    p_d,
                    table_d,
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    self._state_pool,
                    src_d,
                    slots_d if stateful or paged_state else None,
                )
                pf_entry = getattr(self._jit_suffix_prefill, "last_entry", None)
                pf_name = "suffix_prefill"
                self._pallas_dispatches["prefill"] += 1
            else:
                (
                    tokens_d, lens_d, table_d, budgets_d, active_d,
                    temp_d, cons_d, dfa_d,
                ) = self._put_many(
                    (tokens, rs2),
                    (seq_lens, rs),
                    (table, rs2),
                    (budgets_np, rs),
                    (active, rs),
                    (temp_np, rs),
                    (cons_np, rs),
                    (dfa_np, rs),
                )
                last_logits, k_p, v_p, moe_d, self._state_pool = self._jit_prefill(
                    self._params,
                    tokens_d,
                    lens_d,
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    table_d,
                    self._state_pool,
                    slots_d,
                    T=T,
                )
                pf_entry = getattr(self._jit_prefill, "last_entry", None)
                pf_name = "prefill"
            # Pools were donated to prefill: point at the live buffers
            # immediately so an exception below can't leave stale handles.
            self._paged_kv = {"k": k_p, "v": v_p}
            if moe_d is not None:
                self._prefill_moe.append(moe_d)
            # The cohort prefill that writes this admission's inserted
            # radix nodes is dispatched: seal them — later dispatches are
            # device-ordered behind the writes, so they may now match.
            cache.seal()
            self._seg_counter += 1
            # Device handles only — ASYNC ADMISSION: the host never waits
            # for prefill/first-sample. (The old blocking fetch here cost a
            # full device-queue drain + round trip per cohort, the largest
            # single stall in the serving loop once segments pipelined.)
            prng = jax.random.PRNGKey(
                (self._rng_base + self._seg_counter) & 0x7FFFFFFF
            )
            if hetero:
                cur0, st0, done0 = self._jit_hetero_admit(
                    *sdfa[:5],
                    last_logits,
                    budgets_d,
                    active_d,
                    temp_d,
                    cons_d,
                    dfa_d,
                    prng,
                )
            else:
                cur0, st0, done0 = self._jit_admit(  # mcpx: ignore[jit-contract] - homogeneous-mode debt: the slab compat triple admits ONE (temperature, constrained) config per occupancy, so live executables stay bounded by resident configs (warmup precompiles the default); hetero_batch is the structural fix
                    *dfa,
                    last_logits,
                    budgets_d,
                    active_d,
                    prng,
                    temperature=slab.temperature,
                    constrained=slab.constrained,
                )
        except BaseException as e:  # mcpx: ignore[broad-except] - fail cohort AND residents; e propagates to their futures
            # Prefill DONATES the pools: after a dispatch failure the
            # resident rows' KV may live in already-deleted buffers, so they
            # cannot continue either — fail everything and restore fresh
            # pools rather than letting the next segment crash on stale
            # handles. (Runtime failures now surface at the next harvest
            # fetch instead, where the worker-level handler does the same.)
            for sid in sids:
                self._allocator.free(sid)
            for r in cohort:
                r.loop.call_soon_threadsafe(_resolve, r.future, None, e)
            self._fail_rows(slab, e)
            self._reset_pools()
            return

        t1 = time.monotonic()
        self._last_admit_t = t1
        # The prefill chain and its first sample are on the device's queue.
        self._t_queued = self._t_queued or t1
        self.metrics.prefill_tokens.inc(int(seq_lens[: len(cohort)].sum()))
        self.metrics.prefill_slots.inc(A * T)
        self.metrics.admissions.inc()
        self.metrics.admitted_rows.inc(len(cohort))
        rows_idx: list[int] = []
        for j, r in enumerate(cohort):
            i = free.pop(0)
            rows_idx.append(i)
            slab.req[i] = r
            # Bump the row generation NOW: a still-in-flight segment from
            # before this admission reports the then-free row done=True, and
            # without the bump its (lagged) harvest would retire this fresh
            # request with zero tokens.
            slab.gen[i] += 1
            slab.sid[i] = sids[j]
            # cur/st host mirrors stay at clear values: the authoritative
            # first-token state lives only on device (admit outputs chained
            # into the admit-merge). EOS-at-first-sample rows retire empty
            # at their first harvest (emitted=0 via the merge).
            slab.pos[i] = int(positions[j]) + int(seq_lens[j])
            slab.done[i] = False
            slab.budgets[i] = budgets_np[j]
            slab.page_table[i, :] = table[j]
            slab.temp[i] = temp_np[j]
            slab.cons[i] = cons_np[j]
            slab.dfa[i] = dfa_np[j]
            if hetero and dfa_np[j] > 0:
                self._dfa_slot_refs[int(dfa_np[j])] += 1
            slab.queue_ms[i] = (t0 - r.enqueued_at) * 1e3
            self.metrics.hol_wait.observe(slab.queue_ms[i])
            slab.prefill_ms[i] = -1.0  # resolved by _poll_admissions
            slab.t_decode0[i] = t1
            if r.span is not None:
                # Queue-wait (enqueue -> admission-prefill start): the
                # HoL/admit-wait attribution, per request instead of only
                # as a histogram (the chip benchmark's engine.queue_*
                # metrics read this span).
                slab.n_traced += 1
                slab.track[i] = _PlanTrack(admit_t0=t0, admit_t1=t1)
                r.span.child(
                    "engine.queue_wait",
                    t0=r.enqueued_at,
                    t1=t0,
                    cls="constrained" if r.constrained else "free",
                    row=i,
                    **self._queue_wait_why(r, t0),
                )
            # The radix nodes this row references were pinned at stage-3
            # commit (match +1, insert born-pinned); the row now OWNS those
            # pins — clear_row releases them at retirement.
            slab.prefix[i] = prefixes[j][2]
            slab.prefix_toks[i] = prefixes[j][0]
            if ledger_on:
                # Cost-ledger admission facts: suffix tokens this row
                # actually prefills, its private page allocation (the
                # page·seconds base), the readmit copy tokens its match
                # pulled, and the residency clock start.
                slab.suffix_toks[i] = int(seq_lens[j])
                slab.bill_pages[i] = len(row_pages[j])
                slab.bill_copy[i] = int(prefixes[j][3])
                slab.admit_t[i] = t1
        self._rows_admitted += len(cohort)
        # Stamped at the admission's start: from there on its rows were
        # taken, not free for a request still waiting.
        self._note_occupancy(slab, t0)
        if hetero:
            self.metrics.resident_grammars.set(
                sum(1 for n in self._dfa_slot_refs[1:] if n > 0)
            )
        rows_arr = np.full((A,), slab.B, np.int32)  # B = dropped padding
        rows_arr[: len(rows_idx)] = rows_idx
        pos_arr = np.zeros((A,), np.int32)
        pos_arr[: len(cohort)] = (
            positions[: len(cohort)] + seq_lens[: len(cohort)]
        )
        # Draft-lookup seed: the cohort's (suffix) prompt tokens padded to
        # the slab's static buffer width (keeps the admit-merge executable
        # per-A instead of per-(A, T)), plus each row's last prompt token as
        # the initial ``prev`` half of the match bigram.
        ptoks_arr = np.full((A, slab.prompt_cap), tok.pad_id, np.int32)
        ptoks_arr[:, : min(T, slab.prompt_cap)] = tokens[:, : slab.prompt_cap]
        prev_arr = np.full((A,), tok.pad_id, np.int32)
        for j in range(len(cohort)):
            prev_arr[j] = tokens[j, seq_lens[j] - 1]
        rs = self._row_spec(A)
        try:
            state = self._dev_state(slab)
            # budgets_d/table_d from the admission upload are still live
            # (prefill donates only the pools) — reuse, don't re-upload.
            rows_d, pos_d, ptoks_d, prev_d, hst_d = self._put_many(
                (rows_arr, rs),
                (pos_arr, rs),
                (ptoks_arr, self._row_spec(A, 1)),
                (prev_arr, rs),
                # Fresh rows start with a cold drafter state (zeros): the
                # recurrence warms up over the row's own emissions.
                (
                    np.zeros((A, slab.hstate.shape[1]), np.float32),
                    self._row_spec(A, 1),
                ),
            )
            slab.dev = self._jit_admit_merge(
                *state,
                rows_d,
                cur0,
                st0,
                done0,
                pos_d,
                budgets_d,
                table_d,
                ptoks_d,
                lens_d,  # still live: prefill donates only the pools
                prev_d,
                temp_d,  # still live, same reason
                cons_d,
                dfa_d,
                hst_d,
            )
        except BaseException as e:  # mcpx: ignore[broad-except] - rows already assigned; e propagates to every resident request future
            self._fail_rows(slab, e)
            self._reset_pools()
            return
        self._pending_admissions.append(
            (
                t1, slab.dev[4], rows_idx,
                [int(slab.gen[i]) for i in rows_idx], t0, pf_entry, pf_name,
                [int(n) for n in seq_lens[: len(cohort)]], A, T,
            )
        )
        self.metrics.kv_page_utilization.set(self._allocator.stats().utilization)
        self.metrics.batch_occupancy.set(slab.n_active)

    def _release_row(self, slab: "_Slab", i: int) -> None:
        """The one row-release sequence (pages back to the allocator, host
        clear + generation bump, device page-table row marked dirty, gauges
        refreshed) shared by retirement, reaping and failure cleanup — the
        release invariant must not drift between those paths."""
        self._allocator.free(slab.sid[i])
        self._drop_row_grammar(slab, i)
        slab.clear_row(i)
        self._dirty_rows.add(i)
        self._note_occupancy(slab)
        self.metrics.kv_page_utilization.set(self._allocator.stats().utilization)
        self.metrics.batch_occupancy.set(slab.n_active)

    def _note_occupancy(self, slab: "_Slab", t: float = 0.0) -> None:
        """Record a change of "every slab row is taken" with its time
        (``t`` where the caller has already read the clock). Only while a
        profiler is on: otherwise no clock read, and the stale log goes."""
        full = slab.n_active >= slab.B
        if full == self._slab_full:
            return
        self._slab_full = full
        if self._profiler is not None:
            self._occupancy.append((t or time.monotonic(), full))
        else:
            self._occupancy.clear()

    def _queue_wait_why(self, r: GenerateRequest, t_admit: float) -> dict:
        """Why the request waited, as engine.queue_wait span attributes:
        ``unseen_ms``, enqueue to the drain pass that moved it into the
        pending line (it sat in queue.Queue while the worker was not
        looking), and ``free_row_ms``, the part of the wait during which
        the slab had a free row by the worker's books. Both <= the span's
        duration, and written only with a profiler. ``since_ready_ms``
        needs none: the enqueue minus the newest segment's ready stamp as
        ``generate`` read it then, i.e. how long after the harvest that
        (in a closed loop) answered its caller the next request arrived:
        delivery, HTTP, client, server, planner, tokeniser. Left out
        before the first ready stamp."""
        t_enq = r.enqueued_at
        out = {}
        if r.ready_seen:
            out["since_ready_ms"] = round((t_enq - r.ready_seen) * 1e3, 3)
        if self._profiler is None:
            return out
        # Transitions alternate, so before the oldest one kept the state
        # was its opposite; with none kept it is the current state.
        transitions = self._occupancy
        full = (not transitions[0][1]) if transitions else self._slab_full
        free_s, t_prev = 0.0, t_enq
        for t, now_full in transitions:
            if t > t_prev:
                t = min(t, t_admit)
                if not full:
                    free_s += t - t_prev
                t_prev = t
            full = now_full
        if not full and t_admit > t_prev:
            free_s += t_admit - t_prev
        seen = min(max(r.seen_at, t_enq), t_admit)
        out["unseen_ms"] = round((seen - t_enq) * 1e3, 3)
        out["free_row_ms"] = round(free_s * 1e3, 3)
        return out

    def _reap_cancelled(self, slab: "_Slab") -> None:
        """Free rows whose request future was cancelled (client disconnect,
        server-side timeout): pages return to the allocator now and the row
        re-admits immediately instead of decoding an abandoned plan to
        budget exhaustion. The device row keeps decoding harmlessly until
        the next merge zeroes its page-table row — the same freed-page
        safety argument as retirement (garbage writes land in pages that
        cannot be reused before that merge), and the generation bump keeps
        lagged harvests off the row's next occupant."""
        for i in range(slab.B):
            r = slab.req[i]
            if r is None or not r.future.cancelled():
                continue
            self._release_row(slab, i)
            self.metrics.reaped_rows.inc()

    def _dispatch_segment(self, slab: "_Slab") -> None:
        """Dispatch one decode segment chained on the device slab state and
        push its output handles onto the in-flight deque. Async: returns as
        soon as XLA has the work enqueued (~ms), while the device computes.
        Hetero mode dispatches the stacked-table per-row executable (one
        compile for every sampling/grammar mix); homogeneous mode keeps the
        per-(temperature, constrained) specialised segment. The mode is the
        slab's LATCHED admission mode, not the live config flag — resident
        rows always decode under the geometry they were admitted with."""
        ecfg = self.config.engine
        hetero = slab.hetero
        chunk = self._spec_chunk(True if hetero else slab.constrained)
        # Fused multi-step window: one dispatch covers up to
        # steps_per_dispatch ticks of decode (host bookkeeping runs once
        # per window), as many as the pacer asks for at this dispatch; the
        # spec segment keeps its own per-tick iteration count (see
        # _decode_iters for both rationales).
        window, window_max = self._segment_window(spec=hetero and slab.spec)
        self._window_total += window
        self._window_max_total += window_max
        self.metrics.segments.inc()
        self.metrics.segment_active_rows.inc(slab.n_active)
        # Per-path kernel accounting (pallas_paths): every segment is a
        # decode-path dispatch; the spec segment is ALSO a spec-verify
        # dispatch (its verify forward rides the same executable).
        self._pallas_dispatches["decode"] += 1
        if self.model_cfg.hybrid and not self.model_cfg.conv_ffn:
            self._pallas_dispatches["ssm"] += 1
        if self.model_cfg.n_block_layers:
            self._pallas_dispatches["gather"] += 1
        if hetero and slab.spec:
            self._pallas_dispatches["spec_verify"] += 1
        self._seg_counter += 1
        (
            cur_d, pos_d, st_d, e_d, done_d, budgets_d, pt_d, buf_in,
            ptoks_d, plens_d, prev_d, temp_d, cons_d, dfa_d, hst_d,
        ) = self._dev_state(slab)
        prng = jax.random.PRNGKey((self._rng_base + self._seg_counter) & 0x7FFFFFFF)
        dr_d = ac_d = cons_snap = kinds_d = None
        self._dispatch_seq += 1
        seq = self._dispatch_seq
        prefill_rows, self._rows_admitted = self._rows_admitted, 0
        hold_joined, self._hold_joined = self._hold_joined, 0
        self._hold_joined_total += hold_joined
        prefill_moe, self._prefill_moe = self._prefill_moe, []
        t_submit = self._pacer.clock()
        t_queued, self._t_queued = self._t_queued or t_submit, 0.0
        # A step event in a profiler trace: the segment's device ops carry
        # its step_num, which is the engine.segment spans' ``seq``.
        with StepTraceAnnotation("mcpx.segment", step_num=seq):
            if hetero and slab.spec:
                out = self._jit_hetero_segment_spec(
                    self._params,
                    *self._stacked_dfa(),
                    cur_d,
                    pos_d,
                    st_d,
                    e_d,
                    done_d,
                    budgets_d,
                    pt_d,
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    buf_in,
                    temp_d,
                    cons_d,
                    dfa_d,
                    hst_d,
                    prng,
                    iters=window,
                    K=slab.spec_k,
                    draft=slab.spec_draft,
                )
                (
                    cur_d, pos_d, st_d, e_d, done_d, k_p, v_p, buf_d, hst_d,
                    dr_d, ac_d, n_fwd, live_d,
                ) = out
                # Class snapshot at dispatch: the drafted/accepted vectors the
                # lagged harvest fetches belong to the rows resident NOW.
                cons_snap = slab.cons.copy()
            elif hetero:
                out = self._jit_hetero_segment(
                    self._params,
                    *self._stacked_dfa()[:5],
                    cur_d,
                    pos_d,
                    st_d,
                    e_d,
                    done_d,
                    budgets_d,
                    pt_d,
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    buf_in,
                    temp_d,
                    cons_d,
                    dfa_d,
                    prng,
                    iters=np.int32(window),
                    chunk=chunk,
                )
                cur_d, pos_d, st_d, e_d, done_d, k_p, v_p, buf_d, n_fwd, live_d = out
            else:
                dfa = self._dfa_for(slab.grammar or self.grammar)
                out = self._jit_segment(  # mcpx: ignore[jit-contract] - homogeneous-mode debt: per-request temperature/constrained ARE trace statics here, bounded by the slab-wide compat triple (one config per occupancy, drain-to-switch); hetero_batch moves both into per-row device state
                    self._params,
                    *dfa,
                    cur_d,
                    pos_d,
                    st_d,
                    e_d,
                    done_d,
                    budgets_d,
                    pt_d,
                    self._paged_kv["k"],
                    self._paged_kv["v"],
                    buf_in,
                    ptoks_d,
                    plens_d,
                    prev_d,
                    prng,
                    self._state_pool,
                    iters=np.int32(window),
                    chunk=chunk,
                    temperature=slab.temperature,
                    constrained=slab.constrained,
                    draft=ecfg.draft_mode == "prompt",
                )
                (cur_d, pos_d, st_d, e_d, done_d, k_p, v_p, buf_d, prev_d, n_fwd,
                 live_d, *kind_stats, self._state_pool) = out
                kinds_d = kind_stats[0] if kind_stats else None
        self._paged_kv = {"k": k_p, "v": v_p}
        slab.dev = (
            cur_d, pos_d, st_d, e_d, done_d, budgets_d, pt_d, buf_d,
            ptoks_d, plens_d, prev_d, temp_d, cons_d, dfa_d, hst_d,
        )
        # The pacer's stamp of the enqueue (always: the next hold's
        # deadline is predicted from it); the spans' dispatch timestamp
        # only when some resident request is traced (or the cost ledger is
        # billing).
        now = self._pacer.clock()
        self._pacer.dispatched(t_submit, now, window)
        t_disp = now if (slab.n_traced or self._ledger_on) else 0.0
        seg_exec = (
            self._jit_hetero_segment_spec
            if hetero and slab.spec
            else self._jit_hetero_segment if hetero else self._jit_segment
        )
        self._inflight.append(
            (
                done_d, e_d, buf_d, n_fwd, slab.gen.copy(), t_disp,
                # Speculation accounting handles (None on the non-spec
                # paths): per-row drafted/accepted totals of THIS segment
                # plus the dispatch-time class snapshot they attribute by.
                (dr_d, ac_d) if dr_d is not None else None,
                cons_snap,
                # The cost-registry entry (+ executable name, for the
                # ledger's per-executable totals) of the executable just
                # dispatched (entry None when cost accounting is off):
                # harvest attributes the segment's XLA flops/bytes to
                # traced spans and request bills with it.
                getattr(seg_exec, "last_entry", None),
                getattr(seg_exec, "name", "segment"),
                # The engine.segment spans' seq, prefill_rows,
                # hold_joined_rows, window and window_max.
                seq,
                prefill_rows,
                hold_joined,
                window,
                window_max,
                # The layer kinds' counters of this segment (None for a
                # model of dense, full layers) and the expert counters of
                # the prefills in front of it: _layer_kind_attrs.
                kinds_d,
                prefill_moe,
                # Row-forwards by state (_row_forwards): each row's count
                # of this segment's forwards it was live in, still on the
                # device; the host's book of which rows hold a request
                # now; and when the first device work since the previous
                # dispatch was enqueued (an admission's prefill chain, or
                # this dispatch): the timeline's ``starved_ms``.
                live_d,
                [r is not None for r in slab.req],
                t_queued,
            )
        )

    def _layer_kind_attrs(
        self, counts: np.ndarray, n_fwd: int, prefills: "list[np.ndarray]"
    ) -> dict[str, int]:
        """One harvested segment's layer-kind counters (the vector
        ``_segment_impl`` appends) as engine.segment attributes, identical
        on the segment's rows, and into the lifetime sums and the per-expert
        counter. ``prefills``: the expert counters of the admission prefills
        dispatched in front of the segment (``moe_stats_init``, one vector
        each), as ``moe_prefill_assignments`` (their live tokens times the
        experts each chose here, over the sparse layers) and
        ``moe_prefill_rows`` (the rows those layers multiplied by an
        expert's matrices for them); ``moe_expert_steps`` (the expert steps of
        the segment's forwards and of those prefills: one a touched expert
        under the ridge, one a tile past it) and ``moe_kernel_steps`` (those
        taken through ``kernels/routed_experts.py``, the decode's call or the
        admission prefill's two: their ratio is ``moe.kernel_step_share``, 1
        wherever the kernels run).
        Sparse feed-forward: ``moe_assignments`` (live tokens times
        the experts each chose here, over the segment's forwards and sparse
        layers), ``moe_experts_touched`` ((forward, layer, expert) triples
        with at least one live token: the experts whose weights were read)
        and ``moe_expert_slots`` (forwards x sparse layers x experts held:
        what reading every expert would come to), ``moe_layer_forwards``
        (forwards x sparse layers), ``weight_bytes_routed`` (touched experts
        x one expert's bytes) and ``weight_bytes_read`` (that plus
        everything else a forward reads, a constant x forwards);
        ``moe_tokens_routed`` (live tokens times the experts each chose, held
        here or not, over the sparse layers: ``moe_assignments`` over it is
        this device's share of the routing); ``attn_ctx_tokens`` (the context
        tokens the segment's attention calls read, summed over live rows,
        forwards and layers), ``attn_row_calls`` (live rows x forwards x
        layers) and ``kv_bytes_read`` (those tokens times a token's useful
        cache bytes a layer, ``GemmaConfig.kv_bytes_per_token``). A learned
        index: ``attn_sel_tokens`` (the keys those calls attended after the
        selection), ``index_ctx_tokens`` (the index keys they scored: none
        for a row of no more than ``index_topk`` tokens) and
        ``index_bytes_read`` (those times an index key's bytes). A latent
        cache: ``attn_query_slots`` (the query slots those calls' score tiles
        covered: a live row's rung, ``kernels/paged_attention.latent_rung``,
        over forwards and layers; over ``attn_row_calls`` it is 1 where every
        live row decodes one token and the window's width where the tile
        takes no notice of ``q_lens``), ``attn_key_blocks`` (the key blocks
        those calls' programs fetched, over live rows, query blocks, forwards
        and layers) and ``attn_run_blocks`` (those of them fetched in ONE
        copy a pool, the block's pages lying side by side in the pool:
        ``kernels/paged_attention.page_run_flags``, the flags the kernels
        read; their ratio is how far the allocator's order reaches the
        kernels; also ``mcpx_engine_attn_key_blocks_total`` /
        ``_run_blocks_total``). Windowed
        attention:
        ``rows_live`` at dispatch and ``rows_past_window`` of them, the rows
        whose position had reached the window. Short convolutions (a ``C`` /
        ``A`` pattern): ``conv_row_calls`` (live rows x forwards x ``C``
        layers), ``conv_slots`` (the live window slots those calls computed),
        ``conv_tokens`` (the tokens the tails moved by), ``conv_tail_bytes``
        (a call reads and writes its slot's tail and pending window),
        ``conv_weight_bytes`` (the ``C`` mixers' share of
        ``weight_bytes_read``) and ``conv_prefill_tokens`` (the admission
        prefills' tokens times the ``C`` layers), where a Mamba or linear
        model writes ``ssm_*``."""
        mc = self.model_cfg
        sparse, windowed = self._segment_stats
        attrs: dict[str, int] = {}
        if sparse:
            E = mc.n_experts_held
            per_expert = counts[:E]
            attrs["moe_assignments"] = int(per_expert.sum())
            # The forward's own counters (moe.add_forward_stats) follow the
            # layers' E + LAYER_STATS.
            own = E + LAYER_STATS
            routed, ctx_tokens, row_calls = (int(c) for c in counts[own : own + 3])
            attrs["moe_tokens_routed"] = routed
            attrs["attn_ctx_tokens"], attrs["attn_row_calls"] = ctx_tokens, row_calls
            attrs["kv_bytes_read"] = ctx_tokens * (mc.kv_bytes_per_token // mc.n_attn_layers)
            if mc.index_topk:
                # A learned index: the masked form streams every page (what
                # kv_bytes_read counts) and attends the selected keys alone.
                attrs["attn_sel_tokens"], attrs["index_ctx_tokens"] = (
                    int(c) for c in counts[own + 3 : own + 5]
                )
                attrs["index_bytes_read"] = attrs["index_ctx_tokens"] * (
                    mc.index_bytes_per_token // mc.n_layers
                )
            if mc.latent:
                # The forward's last counters: what the absorbed kernel's
                # score tiles covered, a rung a live query block; the key
                # blocks its programs fetched and those fetched as one run.
                latent = own + FORWARD_STATS + (INDEX_STATS if mc.index_topk else 0)
                attrs["attn_query_slots"], attrs["attn_key_blocks"], attrs["attn_run_blocks"] = (
                    int(c) for c in counts[latent : latent + LATENT_STATS]
                )
                self.metrics.attn_key_blocks.inc(attrs["attn_key_blocks"])
                self.metrics.attn_run_blocks.inc(attrs["attn_run_blocks"])
            if mc.n_block_layers:
                # Block-selecting attention: what its calls attended after
                # the selection, the key sums they scored (a page's float32
                # row a KV head), the query slots they computed, and the
                # pages those slots' programs fetched against their contexts'
                # (the gathered form of a decode window fetches a slot's
                # chosen blocks alone; kv_bytes_read counts what was FETCHED).
                blk = own + FORWARD_STATS
                sel, sums, q_slots, got, of = (int(c) for c in counts[blk : blk + BLOCK_STATS])
                attrs["attn_sel_tokens"], attrs["attn_query_slots"] = sel, q_slots
                attrs["attn_gathered_pages"], attrs["attn_ctx_pages"] = got, of
                page_bytes = self.config.engine.kv_page_size * mc.head_dim * 2 * jnp.dtype(mc.dtype).itemsize
                if of:
                    attrs["kv_bytes_read"] = got * page_bytes
                attrs["index_bytes_read"] = sums * mc.head_dim * 4
            if mc.hybrid:
                # Recurrent layers: calls on live rows, the live window slots
                # they computed, the tokens the state moved by; a call reads
                # a slot's state once and writes it once.
                ssm = own + FORWARD_STATS + (BLOCK_STATS if mc.n_block_layers else 0)
                kind = "conv" if mc.conv_ffn else "ssm"
                attrs[kind + "_row_calls"], attrs[kind + "_slots"], attrs[kind + "_tokens"] = (
                    int(c) for c in counts[ssm : ssm + 3]
                )
                if mc.conv_ffn:
                    # A short convolution's call reads its slot's tail and
                    # pending window and writes both; its weights are read
                    # whole a forward.
                    pending = self._state_pool["layers"][0]["pre"].shape[1] + mc.conv_kernel - 1
                    slot_bytes = mc.conv_tail_bytes // (mc.conv_kernel - 1) * pending
                    attrs["conv_tail_bytes"] = attrs["conv_row_calls"] * slot_bytes * 2
                    attrs["conv_weight_bytes"] = n_fwd * self._conv_weight_bytes
                else:
                    attrs["ssm_state_bytes"] = attrs["ssm_row_calls"] * mc.ssm_slot_bytes * 2
                attrs[kind + "_prefill_tokens"] = sum(int(c[ssm + 2]) for c in prefills)
            attrs["moe_experts_touched"] = int(counts[E])
            attrs["moe_layer_forwards"] = n_fwd * mc.n_sparse_layers
            attrs["moe_expert_slots"] = attrs["moe_layer_forwards"] * E
            expert_bytes, rest_bytes = self._weight_bytes
            attrs["weight_bytes_routed"] = attrs["moe_experts_touched"] * expert_bytes
            attrs["weight_bytes_read"] = attrs["weight_bytes_routed"] + n_fwd * rest_bytes
            attrs["moe_prefill_assignments"] = sum(int(c[:E].sum()) for c in prefills)
            attrs["moe_prefill_rows"] = sum(int(c[E + 1]) for c in prefills)
            attrs["moe_expert_steps"] = sum(int(c[E + 2]) for c in (counts, *prefills))
            attrs["moe_kernel_steps"] = sum(int(c[E + 3]) for c in (counts, *prefills))
            for i in np.flatnonzero(per_expert):
                self.metrics.moe_expert_tokens.labels(expert=str(mc.expert_first + int(i))).inc(
                    int(per_expert[i])
                )
        if windowed:
            attrs["rows_past_window"], attrs["rows_live"] = int(counts[-2]), int(counts[-1])
        totals = self._layer_kind_totals
        self._layer_kind_totals = {k: totals.get(k, 0) + v for k, v in attrs.items()}
        return attrs

    def _row_forwards(
        self, live: np.ndarray, occupied: list[bool], n_fwd: int
    ) -> dict[str, int]:
        """What the slab's rows did in one harvested segment of ``n_fwd``
        forwards, as engine.segment attributes (identical on the segment's
        rows) and, traced or not, into
        ``mcpx_engine_row_forwards_total{state=...}``. ``live`` is the
        device's count, a row, of the forwards at whose start it was not
        done; ``occupied`` the host's book at the segment's dispatch. Of
        ``row_forwards`` = rows x forwards: ``row_forwards_empty``, the
        rows that held no request; ``row_forwards_live``, the counter
        summed over those that did; ``row_forwards_done``, the rest of
        theirs: rows that finished earlier in this segment, and rows that
        finished in the segment before, whose harvest came after this
        dispatch (a pipelined worker's harvest lag). The three sum to
        ``row_forwards`` exactly."""
        rows, taken = len(occupied), sum(occupied)
        n_live = int(live[occupied].sum())
        by_state = {
            "live": n_live,
            "done": taken * n_fwd - n_live,
            "empty": (rows - taken) * n_fwd,
        }
        for state, n in by_state.items():
            self.metrics.row_forwards.labels(state=state).inc(n)
        return {
            "row_forwards": rows * n_fwd,
            **{"row_forwards_" + state: n for state, n in by_state.items()},
        }

    @staticmethod
    def _plan_placement(track: _PlanTrack, r: GenerateRequest, seq: int) -> dict:
        """Where a delivered plan's wall stood against the ready stamps, as
        engine.decode attributes; ``seq`` is the segment whose harvest
        delivers it. ``first_seq``..``last_seq`` carried the row, over
        ``segments`` harvests; of their ``ridden_forwards`` the row was
        live in ``live_forwards`` (its device counter, summed).
        ``missed_dispatches``: segments dispatched after the request was
        enqueued and before ``first_seq`` (0 = the next dispatch carried
        it). ``admit_host_ms``: its admission on the host, from the end of
        engine.queue_wait to the prefill chain enqueued. ``behind_ms``:
        from there to when the device could start ``first_seq`` (its
        dispatch, or the previous ready stamp if later): the prefill chain
        and the segment in flight ahead. With engine.generate's
        ``deliver_ms``: queue wait + admit_host_ms + behind_ms + the
        ``period_ms`` of segments first_seq..last_seq + deliver_ms tile
        engine.generate's duration."""
        return {
            "first_seq": track.first_seq,
            "last_seq": seq,
            "segments": track.segments,
            "live_forwards": track.live_forwards,
            "ridden_forwards": track.ridden_forwards,
            "missed_dispatches": track.first_seq - r.dispatch_seen - 1,
            "admit_host_ms": round((track.admit_t1 - track.admit_t0) * 1e3, 3),
            "behind_ms": round((track.t_device - track.admit_t1) * 1e3, 3),
        }

    def _account_speculation(
        self, dr: np.ndarray, ac: np.ndarray, cons_snap: np.ndarray
    ) -> None:
        """Fold one harvested segment's per-row drafted/accepted vectors
        into the running per-row-class totals, the Prometheus counters and
        the accept-rate gauges. Worker thread only; ``_spec_totals`` is
        swapped in whole (GIL-atomic) for queue_stats()'s cross-thread
        read, like ``_pending_stats``."""
        dc = int(dr[cons_snap].sum())
        df = int(dr.sum()) - dc
        acc_c = int(ac[cons_snap].sum())
        acc_f = int(ac.sum()) - acc_c
        if not (dc or df):
            return
        t = self._spec_totals
        t = {
            "drafted_constrained": t["drafted_constrained"] + dc,
            "accepted_constrained": t["accepted_constrained"] + acc_c,
            "drafted_free": t["drafted_free"] + df,
            "accepted_free": t["accepted_free"] + acc_f,
        }
        self._spec_totals = t
        if dc:
            self.metrics.spec_drafted.labels(cls="constrained").inc(dc)
            self.metrics.spec_accepted.labels(cls="constrained").inc(acc_c)
            self.metrics.spec_accept_rate.labels(cls="constrained").set(
                t["accepted_constrained"] / t["drafted_constrained"]
            )
        if df:
            self.metrics.spec_drafted.labels(cls="free").inc(df)
            self.metrics.spec_accepted.labels(cls="free").inc(acc_f)
            self.metrics.spec_accept_rate.labels(cls="free").set(
                t["accepted_free"] / t["drafted_free"]
            )
        # Overall accept rate as its own gauge series: queue_stats()'s
        # spec_accept_rate field on /metrics, so the headline rate is
        # scrapeable without reconstructing it from per-class counters.
        tot_drafted = t["drafted_constrained"] + t["drafted_free"]
        if tot_drafted:
            self.metrics.spec_accept_rate.labels(cls="overall").set(
                (t["accepted_constrained"] + t["accepted_free"]) / tot_drafted
            )

    def _harvest(self, slab: "_Slab", keep_inflight: int) -> None:
        """Fetch flags + out_buf of in-flight segments (oldest first) until
        at most ``keep_inflight`` remain, retiring rows whose requests
        finished. With pipeline_depth D the fetch lags dispatch by D-1
        segments, so its round trip overlaps device compute; done rows stop
        emitting (sticky ``done`` in the segment body), so a lagged out_buf
        is final for any row it reports done. The generation snapshot guards
        against a done-flag from before a row was re-admitted retiring the
        row's NEW request."""
        while len(self._inflight) > keep_inflight:
            (
                done_d, e_d, buf_d, nfwd_d, gen_snap, t_disp, spec_h, cons_snap,
                seg_cost, seg_name, seq, prefill_rows, hold_joined,
                window, window_max, kinds_d, prefill_moe,
                live_d, occupied, t_queued,
            ) = self._inflight.popleft()
            # ONE combined fetch (flags + out_buf): a blocking fetch costs
            # its round trip, not the ~24KB of buffer — splitting into
            # flags-then-buf would add a second round trip on every
            # retirement tick, which at steady state is most ticks. The
            # speculation counters ([B] ints) ride the same fetch, as do
            # each row's live forwards and the layer kinds' counters. The
            # blocking wait is carved out as the profiler's "sync" phase:
            # time spent waiting for device compute, not host bookkeeping
            # (the harvest lap keeps only the latter).
            prof = self._profiler
            t_sync = prof.mark() if prof is not None else 0.0
            dr = ac = None
            with TraceAnnotation("mcpx.worker.sync"):
                if spec_h is not None:
                    done, e, buf, n_fwd, live, dr, ac = jax.device_get(
                        (done_d, e_d, buf_d, nfwd_d, live_d) + spec_h
                    )
                elif kinds_d is not None:
                    done, e, buf, n_fwd, live, kind_counts, prefill_counts = jax.device_get(
                        (done_d, e_d, buf_d, nfwd_d, live_d, kinds_d, prefill_moe)
                    )
                else:
                    done, e, buf, n_fwd, live = jax.device_get(
                        (done_d, e_d, buf_d, nfwd_d, live_d)
                    )
            n_fwd = int(n_fwd)
            kind_attrs = (
                self._layer_kind_attrs(kind_counts, n_fwd, prefill_counts)
                if kinds_d is not None
                else {}
            )
            row_attrs = self._row_forwards(live, occupied, n_fwd)
            timeline = {}
            # The segment's ready stamp: the fetch has just returned. The
            # pacer learns the period from it, profiler or none.
            if prof is not None:
                # The profiler's window between two ready stamps is what
                # the worker did meanwhile; kept for every segment so the
                # windows tile, written only on traced ones (t_disp).
                t_ready = prof.carve("sync", t_sync)
                t_prev, phases = prof.window("harvest", t_ready)
                if t_disp:
                    timeline = self._segment_timeline(
                        seq, prefill_rows, hold_joined, t_disp, t_ready,
                        t_prev, phases, t_queued,
                    )
            else:
                t_ready = self._pacer.clock()
            t_prev_ready, self._t_ready = self._t_ready, t_ready
            self._pacer.ready(t_ready, n_fwd)
            if dr is not None:
                self._account_speculation(dr, ac, cons_snap)
            # The blocking fetch above implies every earlier admission chain
            # has executed — resolve their timings before retiring rows that
            # may have finished in their very first segment.
            self._poll_admissions(slab)
            # decode_ms below is time-to-delivery: it includes the
            # pipeline's depth-1 segment lag, because that lag is part of
            # what the caller actually waits for.
            t1 = time.monotonic()
            self.metrics.decode_forwards.inc(n_fwd)
            if t_disp:
                # Per-segment decode attribution for traced rows (t_disp
                # is set iff some resident row is traced, which holds for
                # every segment of a traced row's residency): dispatch
                # to (lagged) harvest, per-row token delta against the host
                # emitted mirror (valid per row lifetime: cleared to 0 at
                # admission, advanced only here), the row's grammar slot and
                # sampling class — the hetero-batching attribution unit.
                for i in range(slab.B):
                    r = slab.req[i]
                    if r is None or r.span is None or gen_snap[i] != slab.gen[i]:
                        continue
                    # The plan's placement: this segment carried the row
                    # (a finished row is released below, at the harvest of
                    # the segment it finished in, so none is seen twice).
                    track = slab.track[i]
                    if not track.segments:
                        track.first_seq = seq
                        track.t_device = max(t_disp, t_prev_ready)
                    track.segments += 1
                    track.live_forwards += int(live[i])
                    track.ridden_forwards += n_fwd
                    delta = int(e[i]) - int(slab.emitted[i])
                    slab.emitted[i] = e[i]
                    if delta <= 0 and not done[i]:
                        continue
                    attrs = dict(
                        tokens=delta,
                        dfa_id=int(slab.dfa[i]),
                        cls="constrained" if slab.cons[i] else "free",
                        forwards=n_fwd,
                        # Of them, those at whose start this row was not
                        # done (its device counter): they sum to
                        # engine.decode's ``live_forwards``.
                        live_forwards=int(live[i]),
                        # What the pacer asked for at the dispatch, and
                        # the configured ceiling it chose under.
                        window=window,
                        window_max=window_max,
                        # The whole slab's, so identical across the
                        # segment's rows: its row-forwards by state, its
                        # timeline, its layer kinds' counters.
                        **row_attrs,
                        **timeline,
                        **kind_attrs,
                    )
                    if dr is not None:
                        # Speculation attribution per traced row: how many
                        # tokens this segment drafted for the row and how
                        # many survived verification — the per-trace view
                        # of where the speculative win (or miss) landed.
                        attrs["drafted"] = int(dr[i])
                        attrs["accepted"] = int(ac[i])
                    r.span.child("engine.segment", t0=t_disp, t1=t1, **attrs)
            if self._ledger_on:
                # Cost-ledger accumulation for EVERY live row of this
                # segment (not just traced ones): the whole-slab XLA cost
                # apportioned by row-residency share, plus the forwards
                # and accepted speculative tokens the row was resident for.
                resident = [
                    i for i in range(slab.B)
                    if slab.req[i] is not None and gen_snap[i] == slab.gen[i]
                ]
                self._ledger_account(seg_cost, seg_name, resident, slab)
                for i in resident:
                    slab.bill_fwd[i] += n_fwd
                    if ac is not None:
                        slab.bill_spec[i] += int(ac[i])
            retired = False
            for i in range(slab.B):
                r = slab.req[i]
                if r is None or not done[i] or gen_snap[i] != slab.gen[i]:
                    continue
                ids = [int(t) for t in buf[i, : e[i]]]
                res = GenerateResult(
                    token_ids=ids,
                    text=self.tokenizer.decode(ids),
                    prompt_tokens=len(r.prompt_ids),
                    generated_tokens=len(ids),
                    queue_ms=slab.queue_ms[i],
                    prefill_ms=max(0.0, slab.prefill_ms[i]),
                    decode_ms=(t1 - slab.t_decode0[i]) * 1e3,
                    ready_at=t_ready,
                )
                if self._ledger_on:
                    # The engine's itemized bill for this request — a fresh
                    # dict handed across the thread boundary by value; the
                    # request task folds it into the contextvar bill
                    # (telemetry/ledger.py). admit_t==0 means the row was
                    # admitted before the ledger flipped on: residency
                    # items then stay 0 rather than billing garbage.
                    resident_s = (
                        t1 - slab.admit_t[i] if slab.admit_t[i] > 0 else 0.0
                    )
                    res.bill = {
                        "engine_queue_ms": float(res.queue_ms),
                        "prefill_ms": float(res.prefill_ms),
                        "decode_ms": float(res.decode_ms),
                        "prefill_tokens": int(slab.suffix_toks[i]),
                        "prefix_saved_tokens": int(slab.prefix_toks[i]),
                        "decode_tokens": len(ids),
                        "decode_forwards": int(slab.bill_fwd[i]),
                        "spec_accepted_tokens": int(slab.bill_spec[i]),
                        "spill_copy_tokens": int(slab.bill_copy[i]),
                        "kv_pages": int(slab.bill_pages[i]),
                        "kv_page_seconds": float(
                            int(slab.bill_pages[i]) * resident_s
                        ),
                        "flops": float(slab.bill_flops[i]),
                        "hbm_bytes": float(slab.bill_bytes[i]),
                    }
                # Smoothing follows the scheduler's configured alpha: this
                # EWMA exists to feed queue_stats()'s ETA, which floors the
                # scheduler's deadline-shed estimate — two reaction speeds
                # for one gate would make the knob a lie.
                self._ewma_service_s = ewma_update(
                    self._ewma_service_s,
                    (res.prefill_ms + res.decode_ms) / 1e3,
                    self.config.scheduler.ewma_alpha,
                )
                self.metrics.decode_tokens.inc(len(ids))
                self.metrics.engine_queue_seconds.observe(res.queue_ms / 1e3)
                self.metrics.engine_prefill_seconds.observe(res.prefill_ms / 1e3)
                exemplar = None
                if r.span is not None:
                    # Slab residency (admission to delivery, the pipeline's
                    # depth-1 lag included): the summary span whose window
                    # the engine.segment spans subdivide, and where the
                    # plan's wall stood against the ready stamps.
                    r.span.child(
                        "engine.decode",
                        t0=slab.t_decode0[i],
                        t1=t1,
                        tokens=len(ids),
                        row=i,
                        **self._plan_placement(slab.track[i], r, seq),
                    )
                    if self.config.tracing.exemplars and r.span.record.sampled:
                        # Head-unsampled traces are (usually) never
                        # retained: an exemplar naming one would 404 at
                        # GET /traces/{id}. The error-tail exception can't
                        # be known yet mid-flight; sampled is the honest
                        # approximation the middleware's kept-gate refines.
                        exemplar = {"trace_id": r.span.trace_id}
                self.metrics.engine_decode_seconds.observe(
                    res.decode_ms / 1e3, exemplar=exemplar
                )
                self._release_row(slab, i)
                r.loop.call_soon_threadsafe(_resolve, r.future, res, None)
            # The host's share of this harvest: what the pacer sizes the
            # next segment against, with an admission and a dispatch.
            self._pacer.harvested(t_ready, self._pacer.clock())

    @staticmethod
    def _segment_timeline(
        seq: int,
        prefill_rows: int,
        hold_joined_rows: int,
        t_disp: float,
        t_ready: float,
        t_prev: float,
        phases: dict[str, float],
        t_queued: float,
    ) -> dict:
        """When a harvested segment ran and what the worker did meanwhile,
        as flat engine.segment span attributes. ``period_ms``: from when
        the device could start it (its dispatch, or the previous segment's
        ready stamp ``t_prev`` if later) to when the blocked fetch saw it
        done; the ``prefill_rows`` admission prefills chained in front of
        it are inside. ``sync_ms`` is how long that fetch blocked: near
        zero, the device had finished before the host asked and the
        period is the host's lateness: the dispatch came late. ``idle_ms``
        (blocked waiting for requests), ``hold_ms`` (blocked waiting for
        arrivals to join the segment being held, while the device was
        busy) and ``host_ms`` (every other phase, with its named parts)
        are the worker's phases since ``t_prev``: with ``sync_ms`` they
        sum to ``t_ready - t_prev``, which is ``ready_gap_ms``.
        ``hold_joined_rows`` of the ``prefill_rows`` were admitted while
        the segment was held. ``starved_ms``: from the previous ready
        stamp to ``t_queued``, the first thing the worker put on the
        device's queue for this segment (an admission's prefill chain, or
        the dispatch itself); 0 when that was queued already. The device's
        idle time as the program sees it, inside the ``host_ms``,
        ``idle_ms`` and ``hold_ms`` that say what the worker did meanwhile.
        A floor: a device that finished before the host asked
        (``sync_ms`` near zero) idled from then, unseen, to the dispatch
        that preceded the stamp."""
        ms = {p: v * 1e3 for p, v in phases.items()}
        waits = ms["sync"] + ms["idle"] + ms["hold"]
        out = {
            "seq": seq,
            "prefill_rows": prefill_rows,
            "hold_joined_rows": hold_joined_rows,
            "period_ms": round((t_ready - max(t_disp, t_prev)) * 1e3, 3),
            "ready_gap_ms": round((t_ready - t_prev) * 1e3, 3),
            "starved_ms": round(max(0.0, t_queued - t_prev) * 1e3, 3),
            "sync_ms": round(ms["sync"], 3),
            "idle_ms": round(ms["idle"], 3),
            "hold_ms": round(ms["hold"], 3),
            "host_ms": round(sum(ms.values()) - waits, 3),
        }
        for attr, parts in SEGMENT_PARTS.items():
            out[attr] = round(sum(ms[p] for p in parts), 3)
        return out

    def _init_pools(self) -> dict:
        """Fresh zeroed KV page pools, sharded over the mesh: KV heads on
        ``model`` when they divide (GQA/MHA TP), replicated for MQA — the
        north star's "KV-cache sharding over ICI" as a property of the
        SERVING path, not just the dryrun (VERDICT r2 missing #2). Shared by
        startup and post-failure recovery so the two can't drift."""
        from mcpx.parallel.mesh import MODEL_AXIS, _axis

        kv_spec = P(
            _axis(self._mesh, MODEL_AXIS, self.model_cfg.kv_pool_heads),
            None,
            None,
            None,
            None,
        )
        # Created sharded, not built whole on one device and moved.
        return jax.jit(
            lambda: init_paged_kv(
                self.model_cfg, self._allocator.n_pages, self.config.engine.kv_page_size
            ),
            out_shardings=self._named(kv_spec),
        )()

    def _init_state_pool(self) -> dict:
        """Fresh zeroed state pool (``kv_cache.init_state_pool``; {} for a
        model with no recurrent layer): a slot a slab row, whole on every
        device, its pending window as wide as the widest decode window."""
        mc = self.model_cfg
        if not mc.hybrid:
            return {}
        width = max(8, self._spec_chunk(True))
        # Slab row i owns slot i; where the declared head's end state is kept
        # (``GemmaConfig.head_state``) it has the one slot beyond them.
        n_slots = self.config.engine.max_batch_size + mc.head_state
        self._head_state = None
        for event in ("miss",) + (("hit",) if mc.head_state or mc.page_state else ()):
            self.metrics.prefix_state.labels(event=event)  # (the sample exists from the start, at 0)
        n_pages = self._allocator.n_pages
        return jax.jit(
            lambda: init_state_pool(mc, n_slots, width, n_pages), out_shardings=self._named(P()),
        )()

    def _cohort_slots(self, A: int, rows=()):
        """Each row's slot of the state pool for a cohort of ``A``'s prefill:
        the slab row it takes; out of range, which the write drops, for a
        padding row. None for a model with no recurrent layer."""
        if not self._state_pool:
            return None
        slots = np.full((A,), int(self._state_pool["n"].shape[0]), np.int32)
        slots[: len(rows)] = rows
        return self._put(slots, self._row_spec(A))

    def _reset_pools(self) -> None:
        """Recreate the KV page pools after a failed jit call. Prefill and
        segment calls DONATE the pools: an exception after dispatch leaves
        ``self._paged_kv`` pointing at already-deleted buffers, which would
        wedge every subsequent request while /healthz still says ready. All
        resident rows were failed first, so the cached KV content is
        worthless — fresh zeroed pools restore service. The radix tree's
        cached KV lived in the OLD pools: serving it against zeroed pools
        would silently corrupt every later prefix-shared generation, so
        the whole tree is dropped (and rebuilt on next use)."""
        self._prefix_cache.drop_all()
        self._paged_kv = self._init_pools()
        self._state_pool = self._init_state_pool()
        self.metrics.engine_resets.inc()

    def _fail_rows(self, slab: "_Slab", error: BaseException) -> None:
        # Device copies may be stale or deleted (donated into a failed
        # call); host state is authoritative from here. In-flight segment
        # handles chain from the same failed dispatch — drop them (their
        # rows are failed right here, nothing left to harvest).
        slab.dev = None
        self._inflight.clear()
        self._prefill_moe.clear()
        self._t_queued = 0.0
        self._pacer.reset()
        self._dirty_rows.clear()
        self._pending_admissions.clear()
        for i in range(slab.B):
            r = slab.req[i]
            if r is None:
                continue
            if slab.sid[i] is not None:
                self._allocator.free(slab.sid[i])
            self._drop_row_grammar(slab, i)
            slab.clear_row(i)
            r.loop.call_soon_threadsafe(_resolve, r.future, None, error)
        self._note_occupancy(slab)
        self.metrics.kv_page_utilization.set(self._allocator.stats().utilization)
        self.metrics.batch_occupancy.set(0)


def _resolve(future: "asyncio.Future", result, error) -> None:
    if future.cancelled():
        return
    if error is not None:
        future.set_exception(error)
    else:
        future.set_result(result)
