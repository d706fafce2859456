"""Per-executable XLA cost accounting + retrace sentinel (the roofline
cost observatory's data plane).

Motivation: an *analytic* MFU estimate
(2·params·tokens) over a datasheet or measured peak — it moves when the
model changes, not when the kernels do. XLA already knows exactly what
every compiled executable costs (``compiled.cost_analysis()``: flops,
bytes accessed; ``memory_analysis()``: temp/argument/output bytes), so
this module captures those numbers for every jitted engine executable,
keyed by a stable argument signature:

  - :class:`CostRegistry` wraps each ``jax.jit`` callable
    (``registry.wrap(name, jitted, static_argnames=...)``). The wrapper
    computes a cheap host-side signature of each call's arguments
    (shape/dtype/weak-type per leaf + static values — exactly what jit
    keys its own cache on) and then dispatches through the UNMODIFIED
    jitted callable: the C++ fast path serves every call, so the hot
    path pays only the signature lookup (~µs). Measured: taking over
    dispatch with AOT-compiled executables cost 15–60% wall on the
    chained CPU-proxy decode loop, so accounting deliberately never
    touches execution.
  - **Retrace sentinel**: a NEW signature is a compile (jit's cache and
    this signature table miss together, by construction of the key). It
    increments ``mcpx_engine_compiles_total{executable}``, adds the first
    call's wall (trace + lower + compile or cache load + dispatch) to
    ``mcpx_engine_compile_seconds_total{executable}``, counts as one of the
    open start-up phase's ``executables`` (telemetry/startup.py) and logs the
    signature delta against the previous call, with those seconds, once the
    call has returned — recompile storms (a
    shape/dtype leaking into a jitted call per request) were until now
    only caught by compile-count *tests*; in production the counter +
    the delta line name exactly which argument leaf changed, live.
  - **Lazy cost harvest**: at signature-miss time only the ABSTRACT arg
    spec (``jax.ShapeDtypeStruct`` per leaf, shardings preserved, no
    buffers held) is recorded. The XLA numbers are materialised on first
    READ — a ``GET /costs`` scrape, a traced span's attribution, the
    warmup tail — by AOT-compiling from the stored spec and harvesting
    ``cost_analysis()``/``memory_analysis()``; the compiled object is
    discarded immediately (analysis is all we keep). That second compile
    happens at most once per (executable, signature). /costs scrapes pay
    it off the event loop and the warmup tail pre-materialises every
    warmed signature; the one read that CAN land on the serving loop is a
    traced span whose signature warmup didn't cover — bounded at once per
    signature, right after the jit dispatch path itself compiled the same
    program (so on TPU the AOT twin is usually a persistent-XLA-cache
    hit). Backends that publish no costs materialise to a labeled
    ``cost_basis="unavailable"``, never a guess. What the pass costs is
    stamped into ``mcpx_engine_cost_analysis_seconds_total``, and at warm-up
    it is the start-up timeline's ``warmup.cost_table`` phase.
  - Disabled (``telemetry.cost_accounting=false``), ``wrap`` returns the
    jitted callable unchanged: a true pass-through, matching the repo's
    config-gated-subsystem convention.

:func:`device_peaks` gives the chip's datasheet peaks;
:func:`hbm_stats`/:func:`update_hbm_gauges` expose per-device
``memory_stats()`` as HBM-pressure gauges. Consumer: the ``GET /costs``
endpoint (docs/observability.md §Roofline & cost accounting), whose
``totals`` a caller differences around a phase it timed itself. No span
carries a roofline number (ISSUE 40): XLA's estimate over a host wall two
segments deep is not a device measurement, and writing it made the engine
worker compile.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

log = logging.getLogger("mcpx.costs")

__all__ = [
    "CostRegistry",
    "TrackedExecutable",
    "device_peaks",
    "hbm_stats",
    "update_hbm_gauges",
]

# bf16 FLOP/s and HBM bytes/s per chip, by jax device_kind substring —
# datasheet numbers (v5e: Google Cloud documentation, "TPU v5e"). The one
# peaks table: the engine's span roofline and GET /costs read it through
# device_peaks() (benchmarks/chip/peaks.py is the chip benchmark's own
# copy). A hard-coded peak on an unknown chip would
# print a confidently-wrong roofline, so an accelerator that is not listed
# is an error, and the CPU backend has no peak at all.
_TPU_PEAKS: tuple[tuple[str, float, float], ...] = (
    ("v5 lite", 197e12, 819e9),
    ("v5litepod", 197e12, 819e9),
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v4", 275e12, 1228e9),
    ("v6e", 918e12, 1640e9),
    ("v6 lite", 918e12, 1640e9),
)


def device_peaks() -> dict:
    """The default backend's devices as JAX reports them (platform,
    device_kind, count) and their datasheet peaks. The CPU backend has no
    peaks and says so in ``basis``; any other device that ``_TPU_PEAKS``
    does not list raises ``ConfigError``. Touches jax only through
    ``jax.devices()`` — callers gate on an engine being present so a
    heuristic-only server's ``/costs`` scrape never initialises a backend."""
    import jax

    from mcpx.core.errors import ConfigError

    devs = jax.devices()
    out: dict[str, Any] = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "flops_per_chip": None,
        "hbm_bytes_s_per_chip": None,
        "basis": "none: the cpu backend has no datasheet peak",
    }
    if devs[0].platform == "cpu":
        return out
    kind = devs[0].device_kind.lower()
    for sub, flops, bw in _TPU_PEAKS:
        if sub in kind:
            out["flops_per_chip"] = flops
            out["hbm_bytes_s_per_chip"] = bw
            out["basis"] = "datasheet"
            return out
    raise ConfigError(
        f"no datasheet peaks for device_kind {devs[0].device_kind!r} "
        f"(platform {devs[0].platform!r}): add it to "
        "mcpx.telemetry.costs._TPU_PEAKS with its source"
    )


def model_cost(model_cfg) -> dict:
    """What a token costs by the model's shape, for ``/costs``: parameters
    HELD by this engine against parameters a token READS (a sparse
    feed-forward reads ``n_experts_per_tok`` of its experts a layer, not all
    it holds), and model FLOPs a token ~ 2 x the latter: the basis an MFU is
    to be taken on, never 2 x the parameters held."""
    return {
        "params_held": model_cfg.n_params,
        "params_active_per_token": model_cfg.n_active_params,
        "flops_per_token": 2 * model_cfg.n_active_params,
    }


def hbm_stats() -> list[dict]:
    """Per-device ``memory_stats()`` snapshot (bytes in use / limit / peak).
    Backends without allocator stats (XLA:CPU) report ``available: false``
    instead of guessing — the labeled-fallback convention."""
    import jax

    out: list[dict] = []
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except Exception:  # mcpx: ignore[broad-except] - per-scrape telemetry; a backend without stats reports available=false below
            ms = None
        if not ms:
            out.append({"device": str(d), "available": False})
            continue
        out.append(
            {
                "device": str(d),
                "available": True,
                "bytes_in_use": ms.get("bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            }
        )
    return out


def update_hbm_gauges(metrics: Any) -> None:
    """Refresh the ``mcpx_hbm_bytes_*`` gauges from live ``memory_stats()``
    (scrape-time: called by ``GET /metrics``/``GET /costs`` when an engine
    is attached — per-device HBM pressure without a profiler session)."""
    for row in hbm_stats():
        if not row.get("available"):
            continue
        dev = row["device"]
        if row.get("bytes_in_use") is not None:
            metrics.hbm_bytes_in_use.labels(device=dev).set(row["bytes_in_use"])
        if row.get("bytes_limit") is not None:
            metrics.hbm_bytes_limit.labels(device=dev).set(row["bytes_limit"])


# --------------------------------------------------------------- signatures
def _leaf_sig(x: Any) -> tuple:
    """Cheap per-leaf signature: (shape, dtype, weak_type) for arrays, the
    type name alone for python scalars (jit shares executables across
    scalar VALUES of one weak type — keying on the value would mint a fake
    'retrace' per distinct temperature)."""
    if x is None or isinstance(x, (bool, int, float, complex, str)):
        return ("py", type(x).__name__)
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype), bool(getattr(x, "weak_type", False)))
    return ("obj", type(x).__name__)


def _abstract_leaf(x: Any) -> Any:
    """ShapeDtypeStruct twin of one argument leaf (sharding preserved so a
    mesh-sharded engine's lazy compile sees the program serving actually
    ran) — holds NO device buffers, which is what lets the registry keep a
    lazy lowering spec per signature without pinning HBM."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x  # python scalars / statics pass through lower() as-is
    import jax

    # Only COMMITTED arrays pin their sharding into the spec: an
    # uncommitted array (a fresh PRNGKey on device 0) is free for jit to
    # place against the mesh-sharded arguments, and baking its incidental
    # single-device sharding in would make the lazy lower reject the very
    # argument mix the real call served.
    sharding = getattr(x, "sharding", None)
    if not getattr(x, "_committed", False):
        sharding = None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sig_repr(sig: tuple) -> str:
    statics, _, leaves = sig
    parts = [f"{k}={v!r}" for k, v in statics]
    parts += [
        "x".join(map(str, s[0])) + f":{s[1]}" if isinstance(s[0], tuple) else str(s)
        for s in leaves
    ]
    return "(" + ", ".join(parts) + ")"


def _sig_delta(old: tuple, new: tuple) -> str:
    """Human-readable diff of two signatures — the retrace sentinel's log
    payload: WHICH static/leaf changed, not just 'it recompiled'."""
    deltas: list[str] = []
    os_, _, ol = old
    ns_, _, nl = new
    if os_ != ns_:
        deltas.append(f"statics {dict(os_)} -> {dict(ns_)}")
    if len(ol) != len(nl):
        deltas.append(f"arity {len(ol)} -> {len(nl)} leaves")
    else:
        for i, (a, b) in enumerate(zip(ol, nl)):
            if a != b:
                deltas.append(f"leaf[{i}] {a} -> {b}")
    return "; ".join(deltas) or "structure changed"


@dataclass
class ExecCost:
    """One (executable, signature)'s cost facts + call count. Cost fields
    are ``cost_basis="pending"`` until :meth:`ensure` materialises them
    (lazily, off the serving hot path)."""

    signature: str
    owner: Any = field(default=None, repr=False)  # the TrackedExecutable
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    temp_bytes: Optional[float] = None
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    cost_basis: str = "pending"
    calls: int = 0
    # Abstract (args, kwargs) lowering spec — ShapeDtypeStructs, no buffers.
    lower_spec: Any = field(default=None, repr=False)
    lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def ensure(self) -> "ExecCost":
        """Materialise the XLA numbers (idempotent, thread-safe): one AOT
        compile from the stored abstract spec, harvest cost_analysis()/
        memory_analysis(), discard the compiled object. At most once per
        signature; callers are read paths (/costs off the event loop, the
        warmup tail, and, only while ``telemetry.ledger`` bills requests
        their flops, the worker's ledger accounting), never the dispatch
        path and, since ISSUE 40, no traced span."""
        if self.cost_basis != "pending":
            return self
        with self.lock:
            if self.cost_basis != "pending":
                return self
            owner = self.owner
            spec = self.lower_spec
            basis = "unavailable"
            t0 = time.monotonic()
            try:
                if owner is None or spec is None:
                    raise RuntimeError("no lowering spec retained")
                spec_args, spec_kwargs = spec
                compiled = owner._jitted.lower(*spec_args, **spec_kwargs).compile()
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                if isinstance(ca, dict) and ca:
                    self.flops = float(ca["flops"]) if "flops" in ca else None
                    self.bytes_accessed = (
                        float(ca["bytes accessed"])
                        if "bytes accessed" in ca
                        else None
                    )
                    if self.flops is not None:
                        basis = "xla_cost_analysis"
                try:
                    ma = compiled.memory_analysis()
                except Exception:  # mcpx: ignore[broad-except] - memory_analysis is optional per backend; absence is the labeled fallback
                    ma = None
                if ma is not None:
                    self.temp_bytes = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
                    self.argument_bytes = float(
                        getattr(ma, "argument_size_in_bytes", 0) or 0
                    )
                    self.output_bytes = float(
                        getattr(ma, "output_size_in_bytes", 0) or 0
                    )
            except Exception as e:  # noqa: BLE001 - accounting must never fail a read path
                log.warning(
                    "cost analysis unavailable for executable '%s' signature "
                    "%s (%s: %s)",
                    getattr(owner, "name", "?"), self.signature,
                    type(e).__name__, e,
                )
            # compiled (if any) goes out of scope here: analysis is all we
            # keep — no device program retained per signature.
            self.lower_spec = None
            self.cost_basis = basis
            metrics = getattr(getattr(owner, "_registry", None), "_metrics", None)
            if metrics is not None:
                # What this second lowering and compile cost: the warm-up's
                # ``warmup.cost_table`` phase is the sum of these.
                metrics.engine_cost_analysis_seconds.inc(time.monotonic() - t0)
        return self

    def to_dict(self) -> dict:
        return {
            "signature": self.signature,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "cost_basis": self.cost_basis,
            "calls": self.calls,
        }


class TrackedExecutable:
    """Callable shim over one ``jax.jit`` function: per-signature compile
    detection + lazy cost bookkeeping, with EXECUTION always delegated to
    the unmodified jitted callable (the C++ fast dispatch path). Calls
    happen on the engine worker thread; ``snapshot()`` readers only see
    GIL-atomic dict/scalar reads."""

    def __init__(
        self,
        name: str,
        jitted: Callable,
        registry: "CostRegistry",
        static_argnames: Iterable[str] = (),
    ) -> None:
        self.name = name
        self._jitted = jitted
        self._registry = registry
        self._static = frozenset(static_argnames)
        self._entries: dict[tuple, ExecCost] = {}
        self._last_sig: Optional[tuple] = None
        # The entry used by the most recent call — the engine reads it
        # right after dispatching to attribute span rooflines. Worker
        # thread only, like the dispatch itself.
        self.last_entry: Optional[ExecCost] = None

    # The signature must key exactly what jit keys on (shape/dtype/weak
    # type per leaf, static values, tree structure): too coarse and a real
    # retrace hides; too fine and the sentinel cries wolf.
    def _sig(self, args: tuple, kwargs: dict) -> tuple:
        import jax

        statics = tuple(
            sorted((k, v) for k, v in kwargs.items() if k in self._static)
        )
        dyn_kwargs = {k: v for k, v in kwargs.items() if k not in self._static}
        leaves, treedef = jax.tree_util.tree_flatten((args, dyn_kwargs))
        return (statics, treedef, tuple(_leaf_sig(x) for x in leaves))

    def __call__(self, *args, **kwargs):
        sig = self._sig(args, kwargs)
        entry = self._entries.get(sig)
        if entry is None:
            return self._registry._first_call(self, sig, args, kwargs)
        entry.calls += 1
        self.last_entry = entry
        return self._jitted(*args, **kwargs)

    @property
    def compiles(self) -> int:
        return len(self._entries)


class CostRegistry:
    """Registry of cost-tracked engine executables: the compile sentinel,
    the per-executable cost table, and the cumulative executed-work totals
    (``GET /costs``)."""

    def __init__(
        self,
        metrics: Any = None,
        *,
        enabled: bool = True,
        name: str = "engine",
        startup: Any = None,  # mcpx.telemetry.startup.StartupTimeline
    ) -> None:
        self.enabled = enabled
        self.name = name
        self._metrics = metrics
        # The engine's start-up timeline: a new signature counts as one of
        # the ``executables`` of whatever phase is open (none after started).
        self._startup = startup
        self._tracked: list[TrackedExecutable] = []
        self._lock = threading.Lock()
        # Sentinel arming: before arm() — engine startup/warmup, where
        # multi-bucket compiles are EXPECTED — new signatures log at INFO.
        # After arm() (the engine reports ready) every new signature is a
        # compile in the SERVING path and logs the WARNING retrace line.
        # The counter metric increments either way; arming only sets the
        # log severity, so a healthy cold start can't train operators to
        # ignore the storm signal.
        self.armed = False

    def arm(self) -> None:
        self.armed = True

    def wrap(
        self,
        name: str,
        jitted: Callable,
        *,
        static_argnames: Iterable[str] = (),
    ) -> Callable:
        """Wrap one jitted callable. Disabled -> the callable unchanged
        (byte-identical pass-through, nothing tracked)."""
        if not self.enabled:
            return jitted
        t = TrackedExecutable(name, jitted, self, static_argnames)
        with self._lock:
            self._tracked.append(t)
        return t

    # Called from TrackedExecutable on a NEW signature (worker thread).
    def _first_call(self, t: TrackedExecutable, sig: tuple, args: tuple, kwargs: dict):
        """The first call at a new signature, where the compile happens:
        register it, run it through the unmodified jitted callable, and stamp
        the call's wall (trace + lower + compile or cache load + dispatch)
        into ``mcpx_engine_compile_seconds_total{executable}`` and the
        sentinel's log line, which is written once the call has returned.
        The path of a known signature never comes here."""
        n, last_sig = len(t._entries) + 1, t._last_sig
        entry = self._on_compile(t, sig, args, kwargs)
        entry.calls += 1
        t.last_entry = entry
        t0 = time.monotonic()
        try:
            return t._jitted(*args, **kwargs)
        finally:
            seconds = time.monotonic() - t0
            if self._metrics is not None:
                self._metrics.engine_compile_seconds.labels(executable=t.name).inc(seconds)
            if last_sig is None:
                log.info(
                    "%s executable '%s' compiled signature #1 in %.3f s: %s",
                    self.name, t.name, seconds, entry.signature,
                )
            elif not self.armed:
                # Startup/warmup: multi-bucket compiles are the expected cold
                # path, not a retrace — INFO, so the WARNING below stays a
                # real signal.
                log.info(
                    "%s executable '%s' compiled signature #%d (startup) in %.3f s: %s",
                    self.name, t.name, n, seconds, _sig_delta(last_sig, sig),
                )
            else:
                # The sentinel line: every post-ready compile names the exact
                # argument delta that caused it and what it cost. A recompile
                # storm reads as a stream of these with the same leaf index
                # churning.
                log.warning(
                    "%s executable '%s' RETRACED in the serving path "
                    "(compile #%d, %.3f s): %s",
                    self.name, t.name, n, seconds, _sig_delta(last_sig, sig),
                )

    def _on_compile(
        self, t: TrackedExecutable, sig: tuple, args: tuple, kwargs: dict
    ) -> ExecCost:
        """Book a new signature: its entry with the abstract lowering spec,
        the compile counter, and one more of the open start-up phase's
        ``executables``."""
        import jax

        entry = ExecCost(signature=_sig_repr(sig), owner=t)
        # Abstract twins of the arguments (no buffers held): the lazy
        # lowering spec ensure() compiles from at read time.
        try:
            entry.lower_spec = jax.tree_util.tree_map(_abstract_leaf, (args, kwargs))
        except Exception:  # noqa: BLE001 - spec capture is best-effort; ensure() then reports unavailable
            log.debug("lowering-spec capture failed for '%s'", t.name, exc_info=True)
        if self._metrics is not None:
            self._metrics.engine_compiles.labels(executable=t.name).inc()
        if self._startup is not None:
            self._startup.add("executables", 1)
        t._last_sig = sig
        t._entries[sig] = entry
        return entry

    # ------------------------------------------------------------- readers
    def snapshot(self, materialize: bool = True) -> dict:
        """Cross-thread snapshot for GET /costs: per-
        executable compile counts + per-signature costs, plus cumulative
        executed-work totals (Σ cost × calls) whose deltas give a timed
        phase's XLA-derived flops/bytes. ``materialize`` ensures pending
        entries' costs first (one lazy compile each — call off the event
        loop; ``False`` reads whatever is already materialised)."""
        executables: dict[str, Any] = {}
        total_flops = 0.0
        total_bytes = 0.0
        unaccounted = 0
        with self._lock:
            tracked = list(self._tracked)
        for t in tracked:
            sigs = []
            for e in list(t._entries.values()):
                if materialize:
                    e.ensure()
                sigs.append(e.to_dict())
                if e.flops is not None:
                    total_flops += e.flops * e.calls
                else:
                    unaccounted += e.calls
                if e.bytes_accessed is not None:
                    total_bytes += e.bytes_accessed * e.calls
            executables[t.name] = {"compiles": t.compiles, "signatures": sigs}
        return {
            "enabled": self.enabled,
            "executables": executables,
            "totals": {
                "flops_executed": total_flops,
                "bytes_executed": total_bytes,
                "unaccounted_calls": unaccounted,
            },
        }

    def release(self) -> None:
        """Engine aclose: drop the jit dispatch caches' device programs (a
        successor engine must fit in HBM) and any unmaterialised lowering
        specs, keeping the compile/cost history readable."""
        with self._lock:
            tracked = list(self._tracked)
        for t in tracked:
            for e in list(t._entries.values()):
                e.lower_spec = None
                if e.cost_basis == "pending":
                    e.cost_basis = "unavailable"
            clear = getattr(t._jitted, "clear_cache", None)
            if clear is not None:
                try:
                    clear()
                except Exception:  # noqa: BLE001 - best-effort HBM release
                    log.debug("clear_cache failed for '%s'", t.name, exc_info=True)
