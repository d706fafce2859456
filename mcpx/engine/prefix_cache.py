"""Radix-tree prefix KV cache: cross-request reuse of prompt-head KV pages.

RadixAttention-style (SGLang, PAPERS.md) sharing generalised to the paged
TPU engine: a radix tree over token-id sequences whose nodes own runs of
KV pages in the existing paged pool. On admission the engine matches each
request's prompt against the tree, pins the matched run (refcount), and
prefills only the unmatched suffix — the ``suffix_prefill`` executable
already takes a per-row start offset, so reuse costs zero new executables.
The page-aligned remainder of every admitted prompt is inserted back into
the tree, so the NEXT request sharing any prompt head (fixed planner
header, registry shortlist block, a replan extending the original prompt)
re-prefills none of it.

Design constraints this module encodes:

  - **Page granularity.** KV is shareable only in whole pages: edges are
    token runs whose length is a positive multiple of ``page_size``, and a
    partial edge match floors to the page boundary (splitting the edge
    there — pure bookkeeping via ``PageAllocator.split``, no HBM copies).
    Two prompts diverging inside their first un-shared page share nothing
    new — there is no page to share.
  - **Read-only by position.** A node's pages hold KV for positions
    ``[node_start, node_end)`` of every sequence referencing them; rows
    only ever write at positions >= their full prompt length, which land
    in row-private pages — tree pages are write-once (their inserting
    prefill) then read-only.
  - **Single writer.** The engine worker thread owns the tree, exactly
    like the page allocator (SURVEY.md §5): no locks, races structurally
    impossible. Cross-thread readers (``queue_stats``, ``GET /cache``)
    see only GIL-atomic counter snapshots.
  - **Pending epoch.** Nodes inserted for an admission cohort are
    ``pending`` until that cohort's prefill has been DISPATCHED: a row in
    the same cohort must not attend pages whose KV the same device program
    is still computing. ``seal()`` flips the epoch; later dispatches are
    device-ordered behind the writes.
  - **Refcounted eviction.** Rows (and external pins — a
    ``/plan_and_execute`` holding its plan's prefix warm across tool
    execution) pin the deepest node they reference; eviction removes only
    refcount-0 LEAVES, LRU-first, under pool pressure or budget — a
    pinned run can never be reclaimed out from under a reader, and
    interior nodes are protected by having children.
  - **Tiered residency** (optional: ``spill`` — engine/spill.py). With a
    host tier attached, eviction SPILLS a victim's KV run to pinned host
    buffers instead of destroying it (budget-bounded; degrades to the
    destructive path, counted); a later match re-admits the run by async
    host→device page copy. The tree invariant is top-down residency:
    every device-resident node's ancestors are device-resident (spill is
    bottom-up, readmit top-down along the match path), so a matched
    prefix always attends a contiguous resident run.
  - **Tenant governance** (optional: ``governor`` —
    engine/cache_governor.py). Inserts are charged to the inserting
    tenant; over-quota tenants reclaim their own coldest subtrees first,
    and cross-tenant eviction is deficit-weighted LRU (over-share tenants
    first) so one thrashing tenant cannot flush everyone's KV.

The lint rules ``unbounded-cache-growth`` and
``evict-without-refcount-consult`` police the bug classes this module
must not introduce; every insertion path here consults ``evict()``, and
every reclaim path consults ``refs``.
"""

from __future__ import annotations

import heapq
from typing import Any, Optional, Sequence

from mcpx.engine.kv_cache import PageAllocator
from mcpx.utils.ownership import owned_by


@owned_by("engine-worker")
class PrefixNode:
    """One radix edge: ``tokens`` (length a positive multiple of the page
    size) backed by ``pages`` in the paged pool, allocated under this
    node's own ``sid``. ``refs`` counts live pinners (resident slab rows +
    external pins); ``stamp`` is the LRU clock; ``pending`` marks a node
    whose prefill has not been dispatched yet. ``host`` non-None marks a
    SPILLED node: ``pages`` is empty, the KV run lives in the host tier
    (engine/spill.py HostRun) until a match re-admits it; spilled nodes
    are always refcount-0 (only refcount-0 victims spill, and a readmit
    precedes any new pin). ``tenant`` is the inserting tenant (cache
    governance; "default" when governance is off)."""

    __slots__ = (
        "tokens", "pages", "children", "parent", "refs", "stamp", "pending",
        "sid", "host", "tenant",
    )

    def __init__(
        self,
        tokens: tuple,
        pages: list[int],
        parent: Optional["PrefixNode"],
        sid: Any,
        *,
        pending: bool = False,
        tenant: str = "default",
    ) -> None:
        self.tokens = tokens
        self.host = None
        self.tenant = tenant
        self.pages = pages
        # Children keyed by their edge's FIRST PAGE of tokens (a tuple):
        # page-granularity sharing means two branches diverging INSIDE a
        # page share nothing, so they must coexist as siblings -- a
        # first-token key would collide them (vLLM-style page-content
        # keying; first-token radix keys only work at token granularity).
        self.children: dict[tuple, PrefixNode] = {}
        self.parent = parent
        self.refs = 0
        self.stamp = 0
        self.pending = pending
        self.sid = sid

    def __repr__(self) -> str:  # debugging/test aid only
        return (
            f"PrefixNode(len={len(self.tokens)}, pages={len(self.pages)}, "
            f"refs={self.refs}, pending={self.pending}, "
            f"children={len(self.children)})"
        )


@owned_by("engine-worker")
class RadixPrefixCache:
    """Worker-thread-owned radix tree over page-aligned prompt heads:
    the class-level ``owned_by`` puts every instance-attribute write under
    mcpxlint's thread-ownership pass, and the decorated mutators below
    make every call path into them prove it starts on the worker."""

    def __init__(
        self,
        allocator: PageAllocator,
        page_size: int,
        *,
        max_nodes: int = 512,
        max_tokens: int = 0,
        spill: Any = None,  # engine/spill.HostSpillTier (None = single tier)
        governor: Any = None,  # engine/cache_governor.CacheGovernor
    ) -> None:
        self._alloc = allocator
        self.page_size = page_size
        self.spill = spill
        self.governor = governor
        self.max_nodes = max(0, max_nodes)
        # 0 = auto: cap tree residency at half the pool, so a fully-warm
        # tree can never starve the slab of row pages beyond what one
        # eviction pass reclaims.
        self.max_tokens = (
            max_tokens
            if max_tokens > 0
            else (allocator.n_pages // 2) * page_size
        )
        self.root = PrefixNode((), [], None, None)
        self._clock = 0
        self._sid_counter = 0
        # Cross-thread-readable counters (GIL-atomic ints; queue_stats /
        # GET /cache snapshot them without touching the tree).
        self.n_nodes = 0
        self.resident_tokens = 0
        # Spilled (host-tier) nodes/tokens: counted separately so the
        # device node/token caps govern DEVICE residency only (the host
        # tier has its own byte budget).
        self.n_spilled = 0
        self.spilled_tokens = 0
        self.hits = 0
        self.misses = 0
        self.matched_tokens = 0
        self.inserted_tokens = 0
        self.evictions = 0
        # Nodes inserted since the last seal(): sealing clears exactly
        # these instead of walking the whole (up to max_nodes) tree on
        # every admission.
        self._pending_nodes: list[PrefixNode] = []

    def __len__(self) -> int:
        return self.n_nodes

    # ------------------------------------------------------------- helpers
    def _aligned(self, n: int) -> int:
        return (n // self.page_size) * self.page_size

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _new_sid(self) -> tuple:
        self._sid_counter += 1
        return ("pfx", self._sid_counter)

    def match_cap(self, n_prompt: int) -> int:
        """Longest usable match for an ``n_prompt``-token prompt: page
        aligned, and at least one suffix token must remain to prefill (the
        engine samples from the suffix's last logit)."""
        return self._aligned(max(0, n_prompt - 1))

    # ------------------------------------------------------------- descent
    def _descend(
        self, ids: Sequence[int], limit: int, *, mutate: bool
    ) -> tuple[int, list[int], Optional["PrefixNode"]]:
        """The one radix walk probe() and match() share: follow ready
        children by first-page key, scan edge tokens, stop at ``limit``.
        With ``mutate`` a partial edge match SPLITS at the page boundary
        (so the returned node covers exactly the match) and the path is
        stamped for LRU; without it the walk is read-only and the partial
        depth is just arithmetic. A SPILLED child extends the walk only
        when its whole edge matches within the limit: with ``mutate`` it
        is re-admitted (async host→device copy) first — a denied readmit
        (copy budget, pages, data still in flight) just ends the match
        there, the request prefills the rest; read-only walks count it
        when its run could serve a readmit right now. Returns (depth,
        pages, deepest node)."""
        depth = 0
        node = self.root
        pages: list[int] = []
        psz = self.page_size
        tick = self._tick() if mutate else 0
        while depth + psz <= limit:
            child = node.children.get(tuple(ids[depth : depth + psz]))
            if child is None or child.pending:
                break
            if child.host is not None:  # spilled edge
                if self.spill is None or not self.spill.readmit_usable(child):
                    break
                el = child.tokens
                span = min(len(el), limit - depth)
                common = psz
                while common < span and el[common] == ids[depth + common]:
                    common += 1
                full = common == len(el)
                k = common if full else self._aligned(common)
                if k <= 0:
                    break
                if not mutate:
                    depth += k
                    if not full:
                        break
                    node = child
                    continue
                # A partial match splits the HOST run at the page boundary
                # (numpy slices — no device work), exactly mirroring the
                # device-edge split; the matched head then readmits.
                target = child if full else self._split_spilled(child, k)
                # Readmission may run an eviction pass; pin the current
                # path head so the pass can never spill/drop a node whose
                # pages this very walk already collected (every higher
                # ancestor is protected by having this device child).
                if node is not self.root:
                    node.refs += 1
                ok = self._try_readmit(target)
                if node is not self.root:
                    node.refs -= 1
                if not ok:
                    break
                target.stamp = tick
                pages.extend(target.pages)
                depth += k
                node = target
                if not full:
                    break
                continue
            el = child.tokens
            span = min(len(el), limit - depth)
            common = psz
            while common < span and el[common] == ids[depth + common]:
                common += 1
            if common == len(el):
                if mutate:
                    child.stamp = tick
                    pages.extend(child.pages)
                depth += common
                node = child
                continue
            k = self._aligned(common)
            if k > 0 and mutate:
                node = self._split(child, k)
                node.stamp = tick
                pages.extend(node.pages)
            depth += k
            break
        return depth, pages, (node if node is not self.root else None)

    # --------------------------------------------------------------- probe
    def probe(self, ids: Sequence[int], cap: Optional[int] = None) -> int:
        """Read-only matched depth (tokens) for ``ids``: the page-aligned
        length of the longest READY path sharing a prefix with ``ids``,
        capped to leave a suffix token. Never splits, never stamps — the
        locality-sort key for admission ordering. An explicit ``cap``
        replaces the leave-a-suffix default entirely (callers compose
        their own reserve)."""
        limit = self.match_cap(len(ids)) if cap is None else min(
            self._aligned(cap), self._aligned(len(ids))
        )
        return self._descend(ids, limit, mutate=False)[0]

    # --------------------------------------------------------------- match
    @owned_by("engine-worker")
    def match(
        self,
        ids: Sequence[int],
        cap: Optional[int] = None,
        *,
        record: bool = True,
    ) -> tuple[int, list[int], Optional[PrefixNode]]:
        """Longest ready page-aligned match for ``ids``: returns
        ``(n_tokens, pages, deepest_node)``. A partial edge match splits
        the edge at the matched page boundary so the returned node covers
        exactly the match. Counts a hit (n>0) or miss and stamps the path
        for LRU. The caller pins ``deepest_node`` (refs += 1) for as long
        as any page table references ``pages``."""
        limit = self.match_cap(len(ids)) if cap is None else min(
            self._aligned(cap), self._aligned(len(ids))
        )
        depth, pages, node = self._descend(ids, limit, mutate=True)
        if record:
            if depth > 0:
                self.hits += 1
                self.matched_tokens += depth
            else:
                self.misses += 1
        return depth, pages, node

    @owned_by("engine-worker")
    def _split(self, child: PrefixNode, k: int) -> PrefixNode:
        """Split ``child``'s edge at ``k`` tokens (a page boundary):
        insert an intermediate node owning the first ``k`` tokens/pages;
        ``child`` keeps the tail. Page ownership moves via
        ``PageAllocator.split`` — no device work, page ids unchanged, so
        every live page table naming them stays valid."""
        psz = self.page_size
        kp = k // psz
        parent = child.parent
        mid = PrefixNode(
            child.tokens[:k], [], parent, self._new_sid(), tenant=child.tenant
        )
        mid.pages = self._alloc.split(child.sid, mid.sid, kp)
        mid.stamp = child.stamp
        mid.children = {child.tokens[k : k + psz]: child}
        parent.children[child.tokens[:psz]] = mid
        child.tokens = child.tokens[k:]
        child.pages = child.pages[kp:]
        child.parent = mid
        self.n_nodes += 1
        return mid

    @owned_by("engine-worker")
    def _split_spilled(self, child: PrefixNode, k: int) -> PrefixNode:
        """Split a SPILLED edge at ``k`` tokens (a page boundary): both
        sides stay host-resident — the tier slices the run's numpy arrays
        along the page axis, no device work, no pages. Returns the
        intermediate head node, ready for readmit."""
        psz = self.page_size
        parent = child.parent
        mid = PrefixNode(
            child.tokens[:k], [], parent, None, tenant=child.tenant
        )
        mid.stamp = child.stamp
        mid.children = {child.tokens[k : k + psz]: child}
        parent.children[child.tokens[:psz]] = mid
        self.spill.split_host(child, mid, k // psz, k)
        child.tokens = child.tokens[k:]
        child.parent = mid
        self.n_nodes += 1
        self.n_spilled += 1
        return mid

    # -------------------------------------------------------------- lookup
    def lookup(self, ids: Sequence[int]) -> Optional[PrefixNode]:
        """Deepest READY node whose full path is a prefix of ``ids``
        (whole edges only — no splitting): the external-pin handle for
        ``/plan_and_execute`` holding its plan's prompt warm. None when
        nothing matches."""
        depth = 0
        node = self.root
        psz = self.page_size
        limit = self.match_cap(len(ids))
        while depth + psz <= limit:
            child = node.children.get(tuple(ids[depth : depth + psz]))
            if child is None or child.pending or child.host is not None:
                # Spilled nodes are not pinnable: a pin promises resident
                # KV, which only a real match (readmitting) can restore.
                break
            el = child.tokens
            if depth + len(el) > limit or tuple(
                ids[depth : depth + len(el)]
            ) != el:
                break
            depth += len(el)
            node = child
        return node if node is not self.root else None

    # -------------------------------------------------------------- insert
    def can_insert(self, ids: Sequence[int], depth: int) -> int:
        """Tokens insertable at ``depth`` (the end of a match): the
        page-aligned remainder of ``ids``, or 0 when a sibling edge
        collides (an IDENTICAL first page: only a pending cohort-mate's
        not-yet-readable branch — a ready identical page would have been
        matched or split into instead)."""
        end = self._aligned(len(ids))
        if depth >= end:
            return 0
        node = self._node_at(ids, depth)
        if node is None:
            return 0
        key = tuple(ids[depth : depth + self.page_size])
        if node.children.get(key) is not None:
            return 0
        return end - depth

    def _node_at(
        self, ids: Sequence[int], depth: int, *, allow_spilled: bool = False
    ) -> Optional[PrefixNode]:
        """The node whose path ends exactly at ``depth`` along ``ids``
        (pending edges included — an insert right after a match must see
        cohort-mates' branches to refuse colliding with them).
        ``allow_spilled`` walks through spilled nodes too (warm-restart
        restore attaches spilled children below spilled parents)."""
        d = 0
        node = self.root
        psz = self.page_size
        while d < depth:
            child = node.children.get(tuple(ids[d : d + psz]))
            if child is None or d + len(child.tokens) > depth:
                return None
            if tuple(ids[d : d + len(child.tokens)]) != child.tokens:
                return None
            if child.host is not None and not allow_spilled:
                # A device-resident node may never hang below a spilled
                # ancestor (matching through it could not attend the
                # ancestor's positions); the commit-time match readmits
                # the path first, so refusing here only blocks inserts
                # that skipped the match.
                return None
            d += len(child.tokens)
            node = child
        return node

    @property
    def n_device_nodes(self) -> int:
        return self.n_nodes - self.n_spilled

    @owned_by("engine-worker")
    def insert(
        self,
        ids: Sequence[int],
        depth: int,
        n_tokens: int,
        tenant: str = "default",
    ) -> Optional[PrefixNode]:
        """Attach a PENDING node covering ``ids[depth : depth+n_tokens]``
        (page aligned), allocating its pages from the pool — the caller
        wires ``node.pages`` into the admitting row's page table and the
        cohort prefill writes the KV. Returns None (allocating nothing)
        on collision, page exhaustion, or budget breach after one eviction
        pass. The node is born pinned (refs=1) by its inserting row; call
        ``seal()`` once the prefill is dispatched. With a governor,
        ``tenant`` is charged for the residency and an over-quota tenant
        reclaims its OWN coldest subtrees first — still over (everything
        pinned) skips caching, never the admission."""
        if n_tokens <= 0 or n_tokens % self.page_size:
            return None
        if self.governor is not None:
            # Nodes carry the FOLDED accounting name: evict_tenant filters
            # victims by node.tenant, and a raw name past the governor's
            # cardinality cap would never match its "other" bucket's
            # over-share pressure (folded tenants could then starve).
            tenant = self.governor.fold(tenant)
        if self.can_insert(ids, depth) < n_tokens:
            return None
        parent = self._node_at(ids, depth)
        if parent is None:
            return None
        if self.governor is not None and self.governor.over_share(
            tenant, self.max_tokens, extra=n_tokens
        ):
            # WFQ at the cache layer: the over-quota tenant's pressure
            # lands on its own residency (spill-first, like any reclaim).
            self.evict_tenant(tenant, n_tokens)
            if self.governor.over_share(tenant, self.max_tokens, extra=n_tokens):
                return None
        # Budget consult BEFORE growing (the unbounded-cache-growth rule's
        # contract): the eviction pass makes HEADROOM for this insert —
        # refcount-0 LRU subtrees go first (spilled to the host tier when
        # one is attached, destroyed single-tier); if the tree is still
        # over (everything resident is pinned), skip caching — serving
        # never blocks on the cache. The pre-tier build only evicted when
        # already strictly over budget, so a tree that FILLED with
        # refcount-0 entries froze: every later insert was refused and
        # the hit rate pinned at whatever happened to be resident (hit
        # rate 0.0 once earlier traffic had saturated the node cap).
        if (
            self.resident_tokens + n_tokens > self.max_tokens
            or self.n_device_nodes + 1 > self.max_nodes
        ):
            self.evict(need_resident=n_tokens)
        if (
            self.resident_tokens + n_tokens > self.max_tokens
            or self.n_device_nodes + 1 > self.max_nodes
        ):
            return None
        if not self._alloc.can_allocate(n_tokens):
            self.evict(n_tokens)
            if not self._alloc.can_allocate(n_tokens):
                return None
        sid = self._new_sid()
        pages = self._alloc.allocate(sid, n_tokens)
        node = PrefixNode(
            tuple(ids[depth : depth + n_tokens]), pages, parent, sid,
            pending=True, tenant=tenant,
        )
        node.stamp = self._tick()
        node.refs = 1
        parent.children[node.tokens[: self.page_size]] = node
        self.n_nodes += 1
        self.resident_tokens += n_tokens
        self.inserted_tokens += n_tokens
        if self.governor is not None:
            self.governor.on_insert(tenant, n_tokens)
        self._pending_nodes.append(node)
        return node

    # -------------------------------------------------------------- readmit
    @owned_by("engine-worker")
    def _try_readmit(self, node: PrefixNode) -> bool:
        """Re-admit a spilled node's KV run into freshly-allocated device
        pages (async host→device copy through the tier, dispatched before
        anything that will read the pages — device program order makes the
        data visible). Consults the device budgets exactly like an insert
        (one eviction pass, then give up: the match just ends one node
        shorter). Returns True when the node is device-resident again."""
        tier = self.spill
        if tier is None or not tier.readmit_usable(node):
            return False
        n = len(node.tokens)

        def blocked() -> bool:
            return (
                self.resident_tokens + n > self.max_tokens
                or self.n_device_nodes + 1 > self.max_nodes
                or not self._alloc.can_allocate(n)
            )

        if blocked():
            self.evict(
                n if not self._alloc.can_allocate(n) else 0, need_resident=n
            )
            if blocked():
                tier.denied_readmits += 1
                return False
        sid = self._new_sid()
        pages = self._alloc.allocate(sid, n)
        tenant = node.tenant
        if not tier.readmit(node, pages):
            self._alloc.free(sid)
            return False
        node.sid = sid
        node.pages = pages
        self.n_spilled -= 1
        self.spilled_tokens -= n
        self.resident_tokens += n
        if self.governor is not None:
            self.governor.on_readmit(tenant, n)
        return True

    @owned_by("engine-worker")
    def seal(self) -> None:
        """Clear the pending flags of everything inserted since the last
        seal: the cohort prefill that writes those nodes' KV has been
        dispatched, so later dispatches (device ordered behind it) may
        read them. O(inserted-this-cohort), not O(tree)."""
        for n in self._pending_nodes:
            n.pending = False
        self._pending_nodes.clear()

    # ------------------------------------------------------------ eviction
    def _device_leaf(self, c: PrefixNode) -> bool:
        """Reclaimable-from-device: resident, unpinned, sealed, and no
        device-resident child (spill/eviction is bottom-up so the top-down
        residency invariant survives)."""
        return (
            bool(c.pages)
            and c.refs == 0
            and not c.pending
            and not any(cc.pages for cc in c.children.values())
        )

    @owned_by("engine-worker")
    def evict(self, need_tokens: int = 0, need_resident: int = 0) -> int:
        """Reclaim refcount-0 device leaf subtrees, LRU-first, until the
        tree is within its node/token budgets and (when ``need_tokens`` is
        given) the allocator can satisfy it; ``need_resident`` additionally
        makes HEADROOM for that many incoming device tokens (insert /
        readmit under the tiered cache — spill-LRU-to-make-room instead of
        refuse-when-full). With a host tier attached each victim SPILLS
        (KV run to pinned host buffers, async) instead of being destroyed,
        degrading to the destructive drop — counted — only when the tier's
        budgets refuse it; with a governor, victims come from tenants over
        their fair share first (deficit-weighted LRU). Returns device
        tokens reclaimed. ONE tree walk gathers the candidates into an
        ordered heap; a reclaimed leaf that exposes its parent pushes it
        as the next candidate — so a k-leaf pressure cascade costs
        O(n + k log n), not k full rescans."""

        def over() -> bool:
            return (
                self.n_device_nodes + (1 if need_resident else 0) > self.max_nodes
                or self.resident_tokens + need_resident > self.max_tokens
                or (need_tokens > 0 and not self._alloc.can_allocate(need_tokens))
            )

        return self._reclaim(over)

    @owned_by("engine-worker")
    def evict_tenant(self, tenant: str, need_tokens: int = 0) -> int:
        """Tenant-scoped reclaim (cache governance): spill/drop ``tenant``'s
        own coldest refcount-0 subtrees until its device residency plus
        ``need_tokens`` fits its weighted-fair quota (or nothing of its
        remains unpinned). Other tenants' residency is never touched."""
        gov = self.governor
        if gov is None:
            return 0

        def over() -> bool:
            return gov.over_share(tenant, self.max_tokens, extra=need_tokens)

        return self._reclaim(over, tenant=tenant)

    @owned_by("engine-worker")
    def _reclaim(self, over, *, tenant: Optional[str] = None) -> int:
        if not over():
            return 0
        gov = self.governor
        tier = self.spill
        # Fair shares computed at most once per tenant PER PASS (the
        # weighted-share sum is O(tenants); recomputing it per heap push
        # would make every at-budget insert O(candidates x tenants)).
        # Usage only shrinks during the pass, so a cached share keeps the
        # lazy demotion sound: over-share can only flip to false.
        shares: dict[str, int] = {}

        def prio(c: PrefixNode) -> int:
            # Deficit-weighted LRU: cross-tenant pressure takes over-share
            # tenants' nodes first (bucket 0), LRU within a bucket. A
            # tenant-scoped pass has one tenant — no bucketing.
            if gov is None or tenant is not None:
                return 0
            s = shares.get(c.tenant)
            if s is None:
                s = gov.fair_share_tokens(c.tenant, self.max_tokens)
                shares[c.tenant] = s
            return 0 if gov.device_tokens(c.tenant) > s else 1

        heap: list[tuple[int, int, int, PrefixNode]] = []
        seq = 0
        stack = [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                if c.children:
                    stack.append(c)
                if (tenant is None or c.tenant == tenant) and self._device_leaf(c):
                    seq += 1
                    heapq.heappush(heap, (prio(c), c.stamp, seq, c))
        freed = 0
        while heap and over():
            pr, _stamp, _seq, victim = heapq.heappop(heap)
            if victim.parent is None or not self._device_leaf(victim):
                continue  # dropped, re-pinned, or grew a device child
            if pr == 0 and prio(victim) != 0:
                # Its tenant fell under fair share while earlier victims
                # drained — demote behind every still-over-share candidate.
                seq += 1
                heapq.heappush(heap, (1, victim.stamp, seq, victim))
                continue
            parent = victim.parent
            n_tok = len(victim.tokens)
            if tier is not None and not tier.host_room(
                n_tok * tier.bytes_per_token
            ):
                # Host budget full: LRU-reclaim spilled leaves before
                # degrading this victim to a destructive drop.
                self.evict_host(n_tok * tier.bytes_per_token)
            if tier is not None and tier.spill(victim, victim.pages):
                # Gather dispatched (a consistent functional snapshot) —
                # the device pages free immediately.
                self._alloc.free(victim.sid)
                victim.sid = None
                victim.pages = []
                self.n_spilled += 1
                self.spilled_tokens += n_tok
                self.resident_tokens -= n_tok
                if gov is not None:
                    gov.on_spill(victim.tenant, n_tok)
            else:
                if tier is not None:
                    tier.destructive_evictions += 1
                self._drop(victim)
            freed += n_tok
            if parent is not self.root and self._device_leaf(parent):
                seq += 1
                heapq.heappush(heap, (prio(parent), parent.stamp, seq, parent))
        return freed

    @owned_by("engine-worker")
    def evict_host(self, need_bytes: int = 0) -> int:
        """Host-tier reclaim: drop spilled leaf runs until ``need_bytes``
        more fit the tier's byte budget. With a governor the ordering is
        deficit-weighted LRU exactly like the device tier's ``_reclaim``
        — victims come from tenants over their weighted-fair HOST share
        first, LRU within a bucket, with the same lazy demotion when a
        tenant drains under its share mid-pass — so a spill-heavy tenant
        reclaims its own host residency before touching anyone else's
        (PR 11 left this tier tenant-blind). Spilled nodes are refcount-0
        by invariant — the consult (``refs == 0``) is kept anyway so a
        future pinnable-host design cannot silently reclaim a pinned run.
        Returns tokens dropped."""
        tier = self.spill
        if tier is None:
            return 0

        def over() -> bool:
            return not tier.host_room(need_bytes)

        if not over():
            return 0
        gov = self.governor
        # Host budget in tokens for the fair-share math (the tier budgets
        # bytes; shares are token-denominated like the device tier's).
        host_budget = tier.host_bytes // max(1, tier.bytes_per_token)
        over_cache: dict[str, bool] = {}

        def prio(c: PrefixNode, fresh: bool = False) -> int:
            if gov is None:
                return 0
            if fresh or c.tenant not in over_cache:
                over_cache[c.tenant] = gov.over_host_share(c.tenant, host_budget)
            return 0 if over_cache[c.tenant] else 1

        heap: list[tuple[int, int, int, PrefixNode]] = []
        seq = 0
        stack = [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                if c.children:
                    stack.append(c)
                elif c.host is not None and c.refs == 0:
                    seq += 1
                    heapq.heappush(heap, (prio(c), c.stamp, seq, c))
        freed = 0
        while heap and over():
            pr, _s, _q, victim = heapq.heappop(heap)
            if victim.parent is None or victim.children or victim.host is None:
                continue
            if pr == 0 and prio(victim, fresh=True) != 0:
                # Its tenant fell under fair host share while earlier
                # victims drained. The re-check recomputes over-share
                # FRESH: as tenants drain out of the host-active set,
                # every remaining share GROWS (usage only shrinks,
                # weights only leave), so the cached verdict can
                # misclassify a now-under-share tenant as still over.
                # Bucket-1 entries never need the re-check: under-share
                # cannot become over-share mid-pass.
                seq += 1
                heapq.heappush(heap, (1, victim.stamp, seq, victim))
                continue
            parent = victim.parent
            parent.children.pop(victim.tokens[: self.page_size], None)
            freed += len(victim.tokens)
            self._drop_host_node(victim)
            if (
                parent is not self.root
                and parent.host is not None
                and parent.refs == 0
                and not parent.children
            ):
                seq += 1
                heapq.heappush(heap, (prio(parent), parent.stamp, seq, parent))
        return freed

    @owned_by("engine-worker")
    def _drop_host_node(self, node: PrefixNode, *, destructive: bool = False) -> None:
        """Release a SPILLED node's host run + tree accounting (caller
        detaches it from its parent)."""
        n_tok = len(node.tokens)
        if self.spill is not None:
            self.spill.drop_host(node)
            if destructive:
                self.spill.destructive_evictions += 1
            else:
                self.spill.host_evictions += 1
        if self.governor is not None:
            self.governor.on_host_drop(node.tenant, n_tok)
        node.parent = None
        self.n_nodes -= 1
        self.n_spilled -= 1
        self.spilled_tokens -= n_tok
        self.evictions += 1

    @owned_by("engine-worker")
    def _drop(self, node: PrefixNode) -> None:
        """Destructive removal of a DEVICE node. Its spilled descendants
        become unreachable (their paths include this node), so their host
        runs drop with it — counted as destructive evictions."""
        stack = list(node.children.values())
        while stack:
            c = stack.pop()
            stack.extend(c.children.values())
            self._drop_host_node(c, destructive=True)
        node.children.clear()
        self._alloc.free(node.sid)
        node.parent.children.pop(node.tokens[: self.page_size], None)
        node.parent = None
        self.n_nodes -= 1
        self.resident_tokens -= len(node.tokens)
        self.evictions += 1
        if self.governor is not None:
            self.governor.on_drop(node.tenant, len(node.tokens))

    @owned_by("engine-worker")
    def rollback(self, node: PrefixNode) -> None:
        """Detach a pending node whose prefill was never dispatched (an
        admission unwound by page pressure or a dispatch failure): pages
        back to the pool, insertion accounting reversed — not an
        eviction."""
        node.refs = 0
        self._drop(node)
        self.evictions -= 1
        self.inserted_tokens -= len(node.tokens)
        if node in self._pending_nodes:
            self._pending_nodes.remove(node)

    @owned_by("engine-worker")
    def drop_all(self) -> None:
        """Free every node (engine pool reset / shutdown): cached KV lived
        in the old pools and must not be served against new ones. Host
        runs drop with the tree — they describe KV positions the new
        pools will never reproduce."""
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.pages:
                self._alloc.free(n.sid)
        if self.spill is not None:
            self.spill.reset()
        if self.governor is not None:
            self.governor.reset_residency()
        self.root.children.clear()
        self.n_nodes = 0
        self.resident_tokens = 0
        self.n_spilled = 0
        self.spilled_tokens = 0
        self._pending_nodes.clear()

    # ------------------------------------------------------ warm restart
    @owned_by("engine-worker")
    def restore_spilled(
        self,
        path: Sequence[int],
        edge_len: int,
        k_host: Any,
        v_host: Any,
        tenant: str = "default",
    ) -> bool:
        """Warm-restart restore: attach a SPILLED node covering the last
        ``edge_len`` tokens of ``path``, its KV run already host-resident
        (snapshot bytes — no prefill, no device pages; the first match
        re-admits it through the standard async page copy). Parent-first
        restore order is the caller's contract (snapshot manifests are
        written root-first); a missing parent, key collision or host-
        budget refusal skips the node — never fails the restore."""
        tier = self.spill
        if (
            tier is None
            or edge_len <= 0
            or edge_len % self.page_size
            or edge_len > len(path)
        ):
            return False
        if self.governor is not None:
            tenant = self.governor.fold(tenant)
        depth = len(path) - edge_len
        parent = self._node_at(path, depth, allow_spilled=True)
        if parent is None:
            return False
        key = tuple(path[depth : depth + self.page_size])
        if parent.children.get(key) is not None:
            return False
        node = PrefixNode(
            tuple(path[depth:]), [], parent, None, tenant=tenant
        )
        if not tier.adopt(node, k_host, v_host, tenant):
            return False
        node.stamp = self._tick()
        parent.children[key] = node
        self.n_nodes += 1
        self.n_spilled += 1
        self.spilled_tokens += edge_len
        if self.governor is not None:
            self.governor.on_adopt(tenant, edge_len)
        return True

    # --------------------------------------------------------------- stats
    def pinned_nodes(self) -> int:
        count = 0
        stack = [self.root]
        while stack:
            n = stack.pop()
            for c in n.children.values():
                if c.refs > 0:
                    count += 1
                stack.append(c)
        return count

    def stats(self) -> dict:
        """Counter snapshot (safe to call cross-thread: plain int reads)."""
        lookups = self.hits + self.misses
        touched = self.matched_tokens + self.inserted_tokens
        return {
            "nodes": self.n_nodes,
            "resident_tokens": self.resident_tokens,
            "resident_pages": self.resident_tokens // self.page_size,
            "spilled_nodes": self.n_spilled,
            "host_tokens": self.spilled_tokens,
            "host_pages": self.spilled_tokens // self.page_size,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / lookups if lookups else 0.0,
            "matched_tokens": self.matched_tokens,
            "inserted_tokens": self.inserted_tokens,
            "token_hit_rate": self.matched_tokens / touched if touched else 0.0,
            "evictions": self.evictions,
        }

    # ------------------------------------------------------------ checking
    def check_invariants(self) -> None:
        """Test hook: edge alignment, page/token consistency, child keys,
        parent links, the node/token counters, and the tiered-residency
        invariants (spilled ⇒ no pages + refcount-0; device ⇒ device
        ancestors)."""
        n_nodes = 0
        n_spilled = 0
        tokens = 0
        host_tokens = 0
        stack = [self.root]
        while stack:
            node = stack.pop()
            for first_page, child in node.children.items():
                assert child.tokens, "empty edge"
                assert child.tokens[: self.page_size] == first_page, (
                    "child key != first page"
                )
                assert len(child.tokens) % self.page_size == 0, "unaligned edge"
                assert child.parent is node, "broken parent link"
                assert child.refs >= 0, "negative refcount"
                if child.host is not None:
                    assert not child.pages, "spilled node still owns pages"
                    assert child.refs == 0, "pinned node was spilled"
                    n_spilled += 1
                    host_tokens += len(child.tokens)
                else:
                    assert (
                        len(child.pages) == len(child.tokens) // self.page_size
                    ), "page/token mismatch"
                    assert node is self.root or node.host is None, (
                        "device node below spilled ancestor"
                    )
                    tokens += len(child.tokens)
                n_nodes += 1
                stack.append(child)
        assert n_nodes == self.n_nodes, (n_nodes, self.n_nodes)
        assert n_spilled == self.n_spilled, (n_spilled, self.n_spilled)
        assert tokens == self.resident_tokens, (tokens, self.resident_tokens)
        assert host_tokens == self.spilled_tokens, (
            host_tokens, self.spilled_tokens,
        )
