"""Metric arithmetic of the chip benchmark: quantiles, the completion-rate
estimator, span self time and segment clustering. Pure functions over plain
numbers — no clock, no I/O, nothing from the program under test.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence


def quantile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-quantile (0..1) of ``values``, linearly interpolated between
    the two nearest order statistics (position ``q * (n - 1)``); never
    nearest-rank, so a quantile between two latency lumps moves smoothly
    with the lumps' weights instead of jumping by a lump. None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile q={q} outside [0, 1]")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def completion_rate(times: Iterable[float]) -> Optional[float]:
    """Completions per second from the completion instants inside a window,
    without aliasing against a bursty completion process.

    Completions arrive in bursts at segment harvests. Counting N over the
    fixed window length swings by one burst with the phase of the window
    against the harvest period. Here the span is measured from the first
    completion instant to the last, and the completions counted are those
    after the first: whole harvest periods under whole bursts, so the
    window's phase drops out. It reads high by about one burst over the
    window (the first burst's later members are counted and the period
    before it is not); that offset is the same at every phase. Blind to a
    stall at the window's edges: see ``uncovered_edges``. None with fewer
    than two distinct completion instants."""
    ts = sorted(times)
    if len(ts) < 2 or ts[-1] <= ts[0]:
        return None
    t_first = ts[0]
    n_after = sum(1 for t in ts if t > t_first)
    return n_after / (ts[-1] - t_first)


def uncovered_edges(times: Iterable[float], t0: float, t1: float) -> Optional[tuple[float, float]]:
    """What ``completion_rate`` cannot see: ``(edges, widest)`` where
    ``edges`` is the part of the window [t0, t1] before its first and after
    its last completion instant, and ``widest`` the widest gap between two
    consecutive completions inside it (one harvest period, in a steady
    run). The rate and the latency samples are of completed plans only, so
    a server that stalls at an edge of the window (plans in flight, none
    completing) reads the same rate and the same quantiles; a run whose
    ``edges`` exceed two ``widest`` is marked not ``correct``. None with
    fewer than two completions."""
    ts = sorted(times)
    if len(ts) < 2:
        return None
    widest = max(b - a for a, b in zip(ts, ts[1:]))
    return (ts[0] - t0) + (t1 - ts[-1]), widest


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length covered by the union of ``(start, end)`` intervals."""
    total = 0.0
    cur_a: Optional[float] = None
    cur_b = 0.0
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if cur_a is None:
            cur_a, cur_b = a, b
        elif a <= cur_b:
            cur_b = max(cur_b, b)
        else:
            total += cur_b - cur_a
            cur_a, cur_b = a, b
    if cur_a is not None:
        total += cur_b - cur_a
    return total


def span_self_ms(span: dict, spans: Sequence[dict]) -> float:
    """A span's self time: its duration minus the part of its interval that
    its direct children cover (their union, clipped to the span)."""
    a = float(span["start_ms"])
    b = a + float(span["duration_ms"])
    kids = [
        (max(a, float(c["start_ms"])), min(b, float(c["start_ms"]) + float(c["duration_ms"])))
        for c in spans
        if c.get("parent_id") == span["span_id"]
    ]
    return max(0.0, (b - a) - union_length(kids))


def cluster_by_start(items: Sequence[tuple[float, object]], gap: float) -> list[list]:
    """Group ``(start, payload)`` items whose starts lie within ``gap`` of the
    previous one: the per-row ``engine.segment`` spans of one dispatched
    segment carry the same start, read through per-trace clocks that agree
    to about a millisecond."""
    groups: list[list] = []
    last: Optional[float] = None
    for start, payload in sorted(items, key=lambda it: it[0]):
        if last is None or start - last > gap:
            groups.append([])
        groups[-1].append(payload)
        last = start
    return groups
