"""Host-RAM spill tier for the radix prefix KV cache.

The radix tree (engine/prefix_cache.py) caps its device residency at half
the paged pool; at SGLang-scale traffic (millions of users' prompt heads)
that cap is a cliff — eviction DESTROYS refcount-0 subtrees, so a working
set one page past the budget decays the token hit rate to zero. This tier
turns the cliff into a slope: an evicted subtree migrates its KV page runs
into pinned host buffers instead of being freed, and a later prefix match
against the spilled run re-admits it with one async host→device page copy
— orders of magnitude cheaper than re-prefilling the run through the model.

Design constraints this module encodes:

  - **Copies never block the worker.** Device→host spills are dispatched
    as an async gather on the CURRENT pools (jax arrays are functional, so
    the gathered values are a consistent snapshot no later write can
    corrupt); the pages are freed immediately and the fetch completes in a
    later iteration's non-blocking ``poll()``. Host→device readmits are a
    single async scatter dispatched BEFORE the cohort prefill that reads
    the pages — device program order makes the data visible without any
    host synchronisation.
  - **Hard bounds, visible degradation.** A pinned-host byte budget and a
    per-admission-cycle copy-token budget (both directions share it) cap
    what the tier may move; on overrun it degrades to today's destructive
    eviction — counted (``destructive_evictions``, ``denied_readmits``),
    never silent, and admission never stalls on the tier.
  - **Single writer.** The engine worker thread owns the tier exactly like
    the tree and the page allocator; the ``owned_by`` marks put every
    mutation under mcpxlint's thread-ownership pass. Cross-thread readers
    (``GET /cache``, ``queue_stats``) see GIL-atomic counter snapshots.
  - **Chaos-ready.** A seeded ``SpillChaos`` profile injects host-alloc
    failures, copy-latency spikes and snapshot corruption so the KV-tier
    tests can prove the degradation paths, not just the happy one.

``evict-without-refcount-consult`` (mcpx/analysis/rules/cache_rules.py)
polices the bug class the host tier must not reintroduce: every eviction
path here and in the tree consults ``refs`` before reclaiming.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import random
import time
from typing import Any, Callable, Optional

from mcpx.utils.ownership import owned_by

log = logging.getLogger("mcpx.engine.spill")


class SpillChaos:
    """Seeded fault injector for the spill tier (ChaosTransport's design
    applied to the cache layer): deterministic per seed, rewindable via
    ``reseed()`` so the exact fault sequence can be replayed against
    tier configurations under comparison.

    Profile keys (all optional):
      - ``seed``: RNG seed (default 7)
      - ``host_alloc_fail_p``: probability a spill's host allocation fails
        (the spill degrades to destructive eviction)
      - ``copy_delay_p`` / ``copy_delay_s``: probability and size of a
        copy-latency spike — the fetched run stays unusable (not ready)
        for ``copy_delay_s`` after the data lands, as a slow DMA would
      - ``snapshot_corrupt``: truncate/garble the warm-restart snapshot at
        save time (the restore path must skip it, never crash)
    """

    def __init__(self, profile: dict, clock: Callable[[], float] = time.monotonic) -> None:
        if not isinstance(profile, dict):
            raise ValueError("spill chaos profile must be a JSON object")
        self.profile = dict(profile)
        self.seed = int(profile.get("seed", 7))
        self.host_alloc_fail_p = float(profile.get("host_alloc_fail_p", 0.0))
        self.copy_delay_p = float(profile.get("copy_delay_p", 0.0))
        self.copy_delay_s = float(profile.get("copy_delay_s", 0.0))
        self.snapshot_corrupt = bool(profile.get("snapshot_corrupt", False))
        for name in ("host_alloc_fail_p", "copy_delay_p"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"spill chaos {name}={p} not in [0, 1]")
        self._clock = clock
        self._rng = random.Random(self.seed)

    @classmethod
    def from_config(cls, spec: str) -> "SpillChaos":
        """Build from a config string: a path to a JSON profile, or inline
        JSON (starts with '{')."""
        text = spec
        if not spec.lstrip().startswith("{"):
            with open(spec) as f:
                text = f.read()
        return cls(json.loads(text))

    def reseed(self) -> None:
        self._rng = random.Random(self.seed)

    def host_alloc_fails(self) -> bool:
        return self.host_alloc_fail_p > 0 and self._rng.random() < self.host_alloc_fail_p

    def copy_ready_at(self) -> float:
        """Monotonic time before which a just-landed copy must not be used
        (0.0 = no spike)."""
        if self.copy_delay_p > 0 and self._rng.random() < self.copy_delay_p:
            return self._clock() + self.copy_delay_s
        return 0.0


@dataclasses.dataclass
class HostRun:
    """One spilled KV page run. While the device→host fetch is in flight
    ``k``/``v`` hold device handles and ``ready`` is False; ``poll()``
    converts them to pinned host (numpy) buffers. ``ready_at`` delays
    usability past landing (chaos copy-latency spikes)."""

    k: Any
    v: Any
    n_tokens: int
    nbytes: int
    tenant: str
    ready: bool = False
    ready_at: float = 0.0


@owned_by("engine-worker")
class HostSpillTier:
    """Budgeted host-RAM tier under the radix tree. The tree keeps full
    custody of its nodes; this class owns only the host buffers, the
    in-flight copies, the budgets and the accounting. Device transfer is
    injected by the engine via ``bind()`` (so the tier itself stays
    jax-free and unit-testable with numpy stubs)."""

    def __init__(
        self,
        *,
        host_bytes: int,
        copy_tokens_per_cycle: int = 0,
        bytes_per_token: int = 0,
        chaos: Optional[SpillChaos] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.host_bytes = max(0, int(host_bytes))
        self.copy_tokens_per_cycle = max(0, int(copy_tokens_per_cycle))
        # Budget-check estimate for a spill DECISION (the exact nbytes is
        # known only when the fetch lands); the engine binds the true
        # per-token KV footprint at setup.
        self.bytes_per_token = max(1, int(bytes_per_token))
        self.chaos = chaos
        self._clock = clock
        # Device transfer closures (engine-bound): gather(pages) -> async
        # (k, v) handles; readmit(k_np, v_np, pages) -> dispatches the
        # host->device scatter and swaps the engine's pools.
        self._gather: Optional[Callable] = None
        self._readmit: Optional[Callable] = None
        # In-flight device->host fetches, completion polled off the hot
        # path: (node, HostRun) in dispatch order (device order => a
        # not-ready head implies a not-ready tail is NOT guaranteed across
        # pools, so each entry is polled independently).
        self._pending: list[tuple[Any, HostRun]] = []
        # Cross-thread-readable counters (GIL-atomic ints; GET /cache and
        # queue_stats snapshot them without touching tier state).
        self.host_tokens = 0
        self.host_bytes_used = 0
        self.spills = 0
        self.readmits = 0
        self.readmit_tokens = 0
        self.host_evictions = 0
        self.destructive_evictions = 0
        self.denied_spills = 0
        self.denied_readmits = 0
        self.chaos_alloc_failures = 0
        self._cycle_tokens_left = self.copy_tokens_per_cycle or -1

    # ------------------------------------------------------------- binding
    def bind(self, gather: Callable, readmit: Callable, bytes_per_token: int) -> None:
        """Attach the engine's device-transfer closures (worker thread,
        during setup). Until bound, every spill degrades to destructive
        eviction — counted like any other overrun."""
        self._gather = gather
        self._readmit = readmit
        self.bytes_per_token = max(1, int(bytes_per_token))

    @property
    def bound(self) -> bool:
        return self._gather is not None

    # ------------------------------------------------------------- budgets
    @owned_by("engine-worker")
    def begin_cycle(self) -> None:
        """Reset the per-admission-cycle copy-token budget (worker, at the
        top of each admission pass)."""
        self._cycle_tokens_left = self.copy_tokens_per_cycle or -1

    def _take_cycle_tokens(self, n: int) -> bool:
        if self._cycle_tokens_left < 0:  # unlimited
            return True
        if self._cycle_tokens_left < n:
            return False
        self._cycle_tokens_left -= n
        return True

    def host_room(self, nbytes: int) -> bool:
        return self.host_bytes_used + nbytes <= self.host_bytes

    # --------------------------------------------------------------- spill
    @owned_by("engine-worker")
    def spill(self, node: Any, pages: list[int]) -> bool:
        """Dispatch the async device→host gather for ``node``'s page run
        and take host-budget custody of it. Returns False (caller evicts
        destructively, counted) when the tier is unbound, the copy budget
        or host budget cannot afford the run, or chaos fails the host
        allocation. On True the caller frees the device pages immediately
        — the gather snapshot is already consistent."""
        n = int(node_tokens(node))
        est = n * self.bytes_per_token
        if self._gather is None or not self.host_room(est):
            self.denied_spills += 1
            return False
        if not self._take_cycle_tokens(n):
            self.denied_spills += 1
            return False
        if self.chaos is not None and self.chaos.host_alloc_fails():
            self.chaos_alloc_failures += 1
            self.denied_spills += 1
            return False
        k_h, v_h = self._gather(pages)
        run = HostRun(k=k_h, v=v_h, n_tokens=n, nbytes=est, tenant=node.tenant)
        node.host = run
        self._pending.append((node, run))
        self.host_tokens += n
        self.host_bytes_used += est
        self.spills += 1
        return True

    @owned_by("engine-worker")
    def adopt(self, node: Any, k_np: Any, v_np: Any, tenant: str) -> bool:
        """Take custody of an already-host-resident run (warm-restart
        snapshot load): no copy, just budget + accounting. Returns False
        when the host budget cannot afford it."""
        n = int(node_tokens(node))
        nbytes = int(getattr(k_np, "nbytes", 0)) + int(getattr(v_np, "nbytes", 0))
        if not self.host_room(nbytes):
            self.denied_spills += 1
            return False
        node.host = HostRun(
            k=k_np, v=v_np, n_tokens=n, nbytes=nbytes, tenant=tenant, ready=True
        )
        self.host_tokens += n
        self.host_bytes_used += nbytes
        return True

    # ---------------------------------------------------------------- poll
    @owned_by("engine-worker")
    def poll(self) -> None:
        """Complete landed device→host fetches (non-blocking ``is_ready``
        checks; worker, once per iteration — a no-op deque scan when
        nothing is in flight). A completed run becomes pinned host memory;
        a chaos latency spike keeps it unusable until ``ready_at``."""
        if not self._pending:
            return
        import numpy as np

        still: list[tuple[Any, HostRun]] = []
        for node, run in self._pending:
            if node.host is not run:
                continue  # dropped (host eviction / reset) while in flight
            handle = run.k
            is_ready = getattr(handle, "is_ready", None)
            if is_ready is not None and not is_ready():
                still.append((node, run))
                continue
            k_np, v_np = self._trim(run, np.asarray(run.k), np.asarray(run.v))
            true_bytes = int(k_np.nbytes) + int(v_np.nbytes)
            self.host_bytes_used += true_bytes - run.nbytes
            run.nbytes = true_bytes
            run.k, run.v = k_np, v_np
            if self.chaos is not None:
                run.ready_at = self.chaos.copy_ready_at()
            run.ready = True
        self._pending = still

    @owned_by("engine-worker")
    def drain(self) -> None:
        """Blocking completion of every in-flight fetch (shutdown /
        snapshot path only — the worker is gone, nothing races)."""
        if not self._pending:
            return
        import numpy as np

        for node, run in self._pending:
            if node.host is not run:
                continue
            run.k, run.v = self._trim(run, np.asarray(run.k), np.asarray(run.v))
            true_bytes = int(run.k.nbytes) + int(run.v.nbytes)
            self.host_bytes_used += true_bytes - run.nbytes
            run.nbytes = true_bytes
            run.ready = True
            run.ready_at = 0.0
        self._pending = []

    @staticmethod
    def _trim(run: HostRun, k_np: Any, v_np: Any) -> tuple:
        """Drop the gather's power-of-two page-bucket padding from a landed
        run (copy, so the padded base buffer actually frees): without this,
        worst-case run lengths would pin nearly 2x their real bytes against
        the host budget for the run's whole lifetime. The page axis is 2;
        tokens-per-page comes from the array itself (axis 3)."""
        psz = max(1, int(k_np.shape[3]))
        real = max(1, -(-run.n_tokens // psz))
        if k_np.shape[2] > real:
            k_np = k_np[:, :, :real].copy()
            v_np = v_np[:, :, :real].copy()
        return k_np, v_np

    # -------------------------------------------------------------- readmit
    def readmit_usable(self, node: Any) -> bool:
        """Whether ``node``'s spilled run could serve a match right now
        (landed, past any chaos delay). Read-only — safe for probe()."""
        run = node.host
        return (
            run is not None
            and run.ready
            and (run.ready_at <= 0.0 or self._clock() >= run.ready_at)
        )

    @owned_by("engine-worker")
    def readmit(self, node: Any, pages: list[int]) -> bool:
        """Dispatch the async host→device scatter restoring ``node``'s run
        into freshly-allocated ``pages`` and release host custody. Returns
        False (caller leaves the node spilled, the match shrinks) when the
        run is not usable yet or the cycle copy budget is exhausted."""
        run = node.host
        if run is None or self._readmit is None or not self.readmit_usable(node):
            self.denied_readmits += 1
            return False
        if not self._take_cycle_tokens(run.n_tokens):
            self.denied_readmits += 1
            return False
        self._readmit(run.k, run.v, pages)
        self.host_tokens -= run.n_tokens
        self.host_bytes_used -= run.nbytes
        self.readmits += 1
        self.readmit_tokens += run.n_tokens
        node.host = None
        return True

    @owned_by("engine-worker")
    def split_host(
        self, child: Any, mid: Any, head_pages: int, head_tokens: int
    ) -> None:
        """Split ``child``'s host-resident run at ``head_pages`` pages /
        ``head_tokens`` tokens: ``mid`` takes the head, ``child`` keeps the
        tail — numpy page-axis slices, copied so each side's lifetime (and
        the byte accounting) stays independent of the original buffer. The
        run must be ready (an in-flight fetch has no host arrays to
        slice); page-axis padding from the gather bucket stays on the tail
        and drops at readmit."""
        run = child.host
        k_head = run.k[:, :, :head_pages].copy()
        v_head = run.v[:, :, :head_pages].copy()
        k_tail = run.k[:, :, head_pages:].copy()
        v_tail = run.v[:, :, head_pages:].copy()
        mid.host = HostRun(
            k=k_head,
            v=v_head,
            n_tokens=head_tokens,
            nbytes=int(k_head.nbytes) + int(v_head.nbytes),
            tenant=run.tenant,
            ready=True,
            ready_at=run.ready_at,
        )
        child.host = HostRun(
            k=k_tail,
            v=v_tail,
            n_tokens=run.n_tokens - head_tokens,
            nbytes=int(k_tail.nbytes) + int(v_tail.nbytes),
            tenant=run.tenant,
            ready=True,
            ready_at=run.ready_at,
        )
        self.host_bytes_used += mid.host.nbytes + child.host.nbytes - run.nbytes

    # ------------------------------------------------------------- reclaim
    @owned_by("engine-worker")
    def drop_host(self, node: Any) -> None:
        """Release host custody of a spilled run (host-tier eviction,
        destructive subtree drop, reset). In-flight entries are skipped by
        poll() once the node no longer owns the run."""
        run = node.host
        if run is None:
            return
        self.host_tokens -= run.n_tokens
        self.host_bytes_used -= run.nbytes
        node.host = None

    @owned_by("engine-worker")
    def reset(self) -> None:
        """Drop everything — pending handles included (pool reset,
        shutdown). Device handles are simply released; host buffers are
        unreferenced; accounting returns to zero."""
        for node, run in self._pending:
            if node.host is run:
                node.host = None
        self._pending.clear()
        self.host_tokens = 0
        self.host_bytes_used = 0

    # --------------------------------------------------------------- stats
    def pending_copies(self) -> int:
        return len(self._pending)

    def stats(self) -> dict:
        """Counter snapshot (safe cross-thread: plain int reads)."""
        return {
            "host_tokens": self.host_tokens,
            "host_bytes": self.host_bytes_used,
            "host_bytes_budget": self.host_bytes,
            "pending_copies": len(self._pending),
            "spills": self.spills,
            "readmits": self.readmits,
            "readmit_tokens": self.readmit_tokens,
            "host_evictions": self.host_evictions,
            "destructive_evictions": self.destructive_evictions,
            "denied_spills": self.denied_spills,
            "denied_readmits": self.denied_readmits,
            "chaos_alloc_failures": self.chaos_alloc_failures,
        }


def node_tokens(node: Any) -> int:
    return len(node.tokens)
