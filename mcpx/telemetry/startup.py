"""The start-up timeline: every second between the process's start and the
control plane's ``started`` gets a phase, and every phase says what JAX did
inside it.

One ``StartupTimeline`` an engine. It is a ``tracing.TraceRecord`` of
``tracing.Span``s on the spans' clock (``time.monotonic``), written with
``tracing.enabled`` on or off (a few dozen stamps a process, none on the
serving path) and kept OUTSIDE the tracer's sampled ring, so it is never
evicted. ``GET /traces/startup`` serves it in a request trace's two formats,
``GET /healthz`` its ``startup`` block (``snapshot``), and ``finish`` writes
the ``mcpx_startup_*`` gauges once, at ``started``.

Top-level phases, which tile the wall from the process's start to
``started``: ``startup.import`` (the process's start as the OS gives it ->
``InferenceEngine.__init__`` entered; once a process, absent where the
platform has no ``/proc`` and for an engine built later in a process's life:
never guessed; its ``modules`` is ``len(sys.modules)`` where it closes, the
import closure's size, which repeats exactly where its seconds do not),
``startup.build`` (-> the worker's ``_setup`` entered),
``startup.backend``, ``startup.weights``, ``startup.pools``,
``startup.warmup`` (children ``warmup.grammar_tables``, ``warmup.prefill``
and ``warmup.admit`` a bucket, ``warmup.segment``, ``warmup.merge``,
``warmup.cost_table``) and ``startup.registry_grammar`` (the control
plane's). The engine and ``ControlPlane.startup`` open them; this module
knows no phase but the first two.

What JAX did inside a phase comes from ``jax.monitoring`` listeners
registered once a process. An event belongs to the phase that is the
innermost open one WHEN IT ENDS, whichever thread compiled (phases follow
one another in time); with no phase open it is counted under none. A phase's
attributes include its children's:

  lower_s        /jax/core/compile/jaxpr_to_mlir_module_duration
  backend_s      /jax/core/compile/backend_compile_duration (a compile on a
                 cache miss, a load on a hit: JAX times both under this name)
  cache_load_s   /jax/compilation_cache/cache_retrieval_time_sec (inside
                 backend_s)
  cache_requests /jax/compilation_cache/compile_requests_use_cache: compiles
                 that asked the persistent cache
  cache_hits     /jax/compilation_cache/cache_hits: served from it
  cache_misses   /jax/compilation_cache/cache_misses: compiled and WRITTEN
                 to it (JAX counts a miss where it writes the entry);
                 requests - hits - misses compiled anew and were not written,
                 being under the cache's floors (a second to compile, the
                 entry's least size), and will compile anew at every start
  executables    new signatures the engine's cost registry saw
                 (``CostRegistry._on_compile``)
  other_s        the phase's wall less lower_s and backend_s: Python tracing,
                 Pallas lowering in Python, dispatch, the run

``/jax/core/compile/jaxpr_trace_duration`` is NOT summed: its events nest (a
jitted function traced inside another's trace reports both), so a sum of them
is no time. In a process that starts several engines at once (a replica
pool) a JAX event lands on every timeline with a phase open: the seconds are
the process's, ``executables`` alone is the engine's own.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import weakref
from typing import Any, Iterator, Optional

from mcpx.telemetry.tracing import Span, TraceRecord

__all__ = ["StartupTimeline", "PHASE_ATTRS"]

_DURATION_ATTR = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_COUNT_ATTR = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# Every phase carries these from its first stamp on, zero until an event lands.
PHASE_ATTRS = (
    "lower_s", "backend_s", "cache_load_s", "cache_requests", "cache_hits", "cache_misses",
    "executables",
)
_ZEROS = dict.fromkeys(PHASE_ATTRS, 0)

_lock = threading.Lock()
_listening = False
_import_claimed = False
# Timelines with a phase open: what an event of JAX's is handed to. Weak, so
# an engine that was built and never started (its ``startup.build`` stays
# open) goes with its engine.
_open_timelines: "weakref.WeakSet[StartupTimeline]" = weakref.WeakSet()


def _process_start_monotonic() -> Optional[float]:
    """The process's start on the spans' clock: ``/proc/self/stat``'s start
    time (clock ticks since boot) against the boot clock. None where the
    platform has neither."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # The command's name may hold spaces and parentheses: count the
            # fields after its LAST closing one (state is field 3, starttime 22).
            fields = f.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic() - age if age >= 0.0 else None


def _claim_process_start() -> Optional[float]:
    """``_process_start_monotonic()`` for the first caller of a process, None
    for every later one: an engine built later in a process's life did not
    spend the process's life importing."""
    global _import_claimed
    with _lock:
        if _import_claimed:
            return None
        _import_claimed = True
    return _process_start_monotonic()


def _on_duration(event: str, duration_secs: float, **_kw: Any) -> None:
    attr = _DURATION_ATTR.get(event)
    if attr is not None:
        _hand_out(attr, duration_secs)


def _on_event(event: str, **_kw: Any) -> None:
    attr = _COUNT_ATTR.get(event)
    if attr is not None:
        _hand_out(attr, 1)


def _hand_out(attr: str, amount: float) -> None:
    with _lock:
        timelines = tuple(_open_timelines)
    for timeline in timelines:
        timeline.add(attr, amount)


def _listen() -> None:
    """Register the two listeners, once a process. They stay registered: in a
    healthy server no event fires after ``started`` (the C++ dispatch path
    records none), and one that does finds no phase open."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def _read_cache(cache_dir: Optional[str]) -> dict:
    """The persistent compilation cache as it stands: its directory (None =
    no cache), how many files and bytes it holds, and the size it is capped
    at (``jax_compilation_cache_max_size``; -1 = no cap)."""
    import jax

    files = n_bytes = 0
    if cache_dir:
        try:
            with os.scandir(cache_dir) as entries:
                for entry in entries:
                    if entry.is_file():
                        files += 1
                        n_bytes += entry.stat().st_size
        except OSError:
            pass  # a directory JAX has not made yet holds nothing
    return {
        "dir": cache_dir,
        "files": files,
        "bytes": n_bytes,
        "max_bytes": int(jax.config.jax_compilation_cache_max_size),
    }


class StartupTimeline:
    """One engine's start-up, from the process's start (or, for an engine
    built later, from its ``__init__``) to ``finish``. Phases are opened and
    closed by whoever runs them, on any thread; the lock covers the stack of
    open phases and the sums the listeners write."""

    def __init__(self, metrics: Any = None) -> None:
        _listen()
        now = time.monotonic()
        self._metrics = metrics
        self._lock = threading.Lock()
        self._open: list[tuple[Span, Span]] = []  # (phase, its parent), innermost last
        self.record = TraceRecord()
        self.record.name = "startup"
        t_proc = _claim_process_start()
        root = Span(self.record, "startup", None, t0=now if t_proc is None else min(t_proc, now))
        root.attrs.update(_ZEROS)
        self.record.spans.append(root)
        self.record.t0_wall = time.time() - (time.monotonic() - root.t0)
        self.ready_s: Optional[float] = None
        self._cache: dict = {}
        if t_proc is not None:
            self._close_attrs(
                root.child("startup.import", t0=root.t0, t1=now, modules=len(sys.modules), **_ZEROS)
            )
        self._build: Optional[Span] = self.begin("startup.build", t0=now)

    # ----------------------------------------------------------------- phases
    def begin(self, name: str, *, t0: Optional[float] = None, **attrs: Any) -> Span:
        """Open a phase under the innermost open one (the root if none)."""
        with self._lock:
            parent = self._open[-1][0] if self._open else self.record.root
            sp = parent.child(name, t0=t0, **attrs, **_ZEROS)
            self._open.append((sp, parent))
        with _lock:
            _open_timelines.add(self)
        return sp

    def end(self, sp: Span, error: Optional[BaseException] = None) -> None:
        """Close a phase: stamp it, hand its sums to its parent, and write
        ``other_s``. ``error`` marks it failed, with the exception's type."""
        with self._lock:
            pair = next((p for p in self._open if p[0] is sp), None)
            if pair is None:
                return  # closed already (a failed phase is ended where it failed)
            self._open.remove(pair)
            sp.end()
            if error is not None:
                sp.status = "error"
                sp.set(error=True, error_type=type(error).__name__)
            for key in PHASE_ATTRS:
                pair[1].attrs[key] += sp.attrs[key]
            self._close_attrs(sp)
            idle = not self._open
        if idle:
            with _lock:
                _open_timelines.discard(self)

    @staticmethod
    def _close_attrs(sp: Span) -> None:
        sp.attrs["other_s"] = (sp.t1 - sp.t0) - sp.attrs["lower_s"] - sp.attrs["backend_s"]

    @contextlib.contextmanager
    def phase(self, name: str, **attrs: Any) -> Iterator[Span]:
        """A phase around a block, which is also a profiler event
        ``mcpx.startup.<phase>`` on the calling thread's line: a capture that
        spans a start shows it beside the device's ops on one clock. An
        escaping exception marks the phase failed and is never swallowed."""
        from jax.profiler import TraceAnnotation

        sp = self.begin(name, **attrs)
        try:
            with TraceAnnotation("mcpx.startup." + name.removeprefix("startup.")):
                yield sp
        except BaseException as e:
            self.end(sp, error=e)
            raise
        self.end(sp)

    def end_build(self) -> None:
        """The worker's ``_setup`` is entered: ``startup.build`` (tokenizer,
        grammar tables, planner, control plane, app, listening) is over."""
        if self._build is not None:
            self.end(self._build)
            self._build = None

    def add(self, attr: str, amount: float) -> None:
        """``amount`` more of ``attr`` on the innermost open phase; nothing
        with none open."""
        with self._lock:
            if self._open:
                attrs = self._open[-1][0].attrs
                attrs[attr] = attrs.get(attr, 0) + amount

    # ------------------------------------------------------------------ cache
    def note_cache(self, cache_dir: Optional[str]) -> dict:
        """Read the compilation cache (at ``startup.backend``, and again at
        ``finish``): the newest reading stands in the block's own keys, the
        first stays under ``at_start`` once there are two."""
        reading = _read_cache(cache_dir)
        if self._cache:
            first = self._cache.get("at_start") or {
                k: self._cache[k] for k in ("files", "bytes")
            }
            reading["at_start"] = first
        self._cache = reading
        return reading

    # ----------------------------------------------------------------- finish
    def finish(self) -> None:
        """``started``: stamp ``ready_s``, read the cache again and write the
        ``mcpx_startup_*`` gauges, which are constant from here on. Phases
        still open (a start that was cut short) are left as they are."""
        if self.ready_s is not None:
            return
        root = self.record.root
        root.end()
        self.ready_s = root.t1 - root.t0
        self._close_attrs(root)
        if self._cache:
            self.note_cache(self._cache["dir"])
        self.record.sealed = True
        if self._metrics is not None:
            self._write_gauges(self._metrics)

    def _write_gauges(self, m: Any) -> None:
        seconds: dict[str, float] = {}
        for sp in self.record.spans[1:]:
            if sp.t1:  # per-bucket phases (warmup.prefill, warmup.admit) sum a kind
                label = sp.name.removeprefix("startup.")
                seconds[label] = seconds.get(label, 0.0) + (sp.t1 - sp.t0)
        for label, s in seconds.items():
            m.startup_phase_seconds.labels(phase=label).set(s)
        warmup = next((sp for sp in self.record.spans if sp.name == "startup.warmup"), None)
        if warmup is not None:
            for stage in ("lower", "backend", "cache_load"):
                m.startup_warmup_jax_seconds.labels(stage=stage).set(warmup.attrs[stage + "_s"])
        totals = self.record.root.attrs
        hits, misses = totals["cache_hits"], totals["cache_misses"]
        m.startup_cache_events.labels(event="hit").set(hits)
        m.startup_cache_events.labels(event="miss").set(misses)
        m.set_startup(
            ready_s=self.ready_s,
            executables=totals["executables"],
            # Absent, not 0, where no compile asked the cache (the CPU backend).
            cache_hit_ratio=hits / (hits + misses) if hits + misses else None,
        )

    # ---------------------------------------------------------------- readers
    def trace(self, *, chrome: bool = False) -> dict:
        """``GET /traces/startup``: the timeline in a request trace's two
        formats, copied under the lock (while a start is under way ``end``
        adds keys to the attributes a body would otherwise share)."""
        with self._lock:
            body = self.record.to_chrome() if chrome else self.record.to_dict()
            return json.loads(json.dumps(body))

    def snapshot(self) -> dict:
        """``GET /healthz``'s ``startup`` block: present from the first phase
        on, so while the engine warms too."""
        root = self.record.root
        phases = []
        with self._lock:  # ``end`` adds keys to a phase's attributes
            current = self._open[-1][0].name if self._open else None
            for sp in sorted(self.record.spans[1:], key=lambda s: s.t0):
                row = {
                    "name": sp.name,
                    "t0_s": round(sp.t0 - root.t0, 3),
                    "t1_s": round(sp.t1 - root.t0, 3) if sp.t1 else None,
                }
                for key, v in sp.attrs.items():
                    row[key] = round(v, 3) if isinstance(v, float) else v
                phases.append(row)
        return {
            "t0_unix": round(self.record.t0_wall, 3),
            "current": current,
            "phases": phases,
            "ready_s": None if self.ready_s is None else round(self.ready_s, 3),
            "cache": dict(self._cache),
        }
