"""When to dispatch the next decode segment: just in time, not at once.

The engine worker is pipelined: while segment N+1 computes, it prepares
N+2. Dispatching N+2 the moment N's results are delivered leaves out every
request that arrives during N+1's period: a caller's re-send lands
milliseconds after the harvest that answered it, misses N+2 and waits a
whole period in ``queue.Queue`` for N+3, while a slab row rides along
empty. So the worker HOLDS N+2: it waits on its queue, admitting arrivals,
until N+1 is nearly ready, and dispatches N+2 just before that, so the
device's queue never empties and nothing starts later than it could have.

How LONG the segment is belongs to the same bookkeeping. A plan is charged
whole segments: one waiting behind the segment in flight, then as many as
its tokens need, and its reply leaves only at a segment's end. A short
segment wastes less of a row's time; what bounds it from below is the
worker itself, which has to admit, dispatch and harvest once a segment
without ever letting the device's queue empty. ``segment_forwards``
chooses the length at each dispatch from the same estimates; the
configured window (``decode_steps_per_tick x steps_per_dispatch``) is its
ceiling.

This module is the part of that with no device in it: ``SegmentPacer``
models the device's FIFO from what the worker observes (what it enqueued
and when, and each segment's ready stamp), keeps running estimates of a
forward's period, an admission's prefill chain and the worker's own costs
of one admission, one dispatch and one harvest, and predicts when the
newest segment in flight will be ready; ``hold_until`` and
``segment_forwards`` are the decisions. The worker loop that acts on them
is ``InferenceEngine._worker``.
"""

from __future__ import annotations

import math
import statistics
import time
from collections import deque
from typing import Callable, Optional

__all__ = ["SegmentPacer", "hold_until", "segment_forwards"]


def hold_until(
    now: float,
    *,
    in_flight: bool,
    free_rows: int,
    backlog: int,
    ready_at: Optional[float],
    margin: Optional[float],
) -> Optional[float]:
    """Until when the worker may wait for arrivals before it dispatches
    the next segment; None = dispatch now (the order before ISSUE 29).

    A hold needs a segment ``in_flight`` that still decodes live rows (the
    device has work, and the next segment could not start before it ends
    anyway), a free slab row (an arrival could join), no ``backlog`` of
    pending requests that admission just left behind (want of pages, a
    full grammar table, an incompatible slab: waiting admits none of
    them), and an estimate: ``ready_at``, when the newest segment in
    flight is predicted ready, and ``margin``, the worker's own cost of
    one admission plus one dispatch. The wait ends ``margin`` before
    ``ready_at``: an arrival seen by then is admitted and the segment
    still reaches the device before the one ahead of it ends; a later one
    joins the next segment, as every request did before."""
    if not in_flight or free_rows <= 0 or backlog > 0:
        return None
    if ready_at is None or margin is None:
        return None
    until = ready_at - margin
    return until if until > now else None


# How short a decode segment may get, as a multiple of what the pacer
# measures (PERF.md, PR 31, has the chip runs that chose it). The
# segment's own forwards must last HOST_COVER times the worker's host work
# for one segment (the medians of an admission, a dispatch, a harvest):
# three times, because the work comes in lumps (a second admission in one
# segment, one admission in fifty at several times the median, everything
# at twice its cost while a profiler session is open) and the device's
# queue is only one segment deep behind the one that runs. At 2.0 every
# benchmark cell ran one tick of 4, the four-chip one on the edge of two
# with its device waiting 7 ms at a time for an admission's copies; at 3.0
# olmo2-1b runs 8 forwards with the need at 1.5 ticks, well short of the 12
# that read worse than no change there.
#
# The prefill chain in front of a segment does NOT lengthen it. Until PR 36
# a segment also had to outlast that chain; the rule never set a length
# in the cells it was chosen on (PR 31), and a longer segment does not make
# the chain in front of it shorter: it retires more rows, whose one
# admission pads to a larger cohort bucket, whose chain then asks for a
# long segment again. In the first cell where the rule bound, 8 forwards
# pinned beat 16 on the rate and the median and beat the rule's own mix
# (PERF.md, PR 36).
HOST_COVER = 3.0


def segment_forwards(
    *,
    tick: int,
    ceiling: int,
    forward_s: Optional[float],
    host_s: Optional[float],
) -> int:
    """How many forwards the next decode segment may run: the fewest whole
    ticks whose device time covers the worker's host work for a segment
    ``HOST_COVER`` times, never more than ``ceiling`` (the configured
    window, a whole number of ``tick``s itself) and never less than one
    tick. With no estimate of a forward's period or of the host's costs
    yet, the ceiling: the length every segment had before ISSUE 31."""
    tick = max(1, tick)
    ceiling = max(tick, ceiling)
    if forward_s is None or forward_s <= 0 or host_s is None:
        return ceiling
    ticks = max(1, math.ceil(HOST_COVER * host_s / (forward_s * tick)))
    return min(ceiling, ticks * tick)


class _Recent:
    """Median of the last few samples: a running estimate that one
    outlier (a compile, a stalled host) does not move."""

    def __init__(self, keep: int = 5) -> None:
        self._xs: "deque[float]" = deque(maxlen=keep)

    def add(self, x: float) -> None:
        self._xs.append(x)

    @property
    def value(self) -> Optional[float]:
        return statistics.median(self._xs) if self._xs else None

    @property
    def low(self) -> Optional[float]:
        """The smallest of them: of samples that are each an upper bound,
        the tightest."""
        return min(self._xs) if self._xs else None


class SegmentPacer:
    """The device's queue as the worker knows it, and the estimates that
    turn it into a predicted ready time. Single writer: the engine worker.

    The worker reports what it enqueues, ``admitted`` (an admission's
    prefill chain) and ``dispatched`` (a segment of so many forwards), each
    with the host wall it took, and ``ready`` when a segment's blocking
    fetch returns. Between two ready stamps the device ran the prefills
    chained in front of the segment and the segment's forwards, so the
    period over the forwards is an upper bound on a forward's period,
    exact when no prefill was in front, and the period less its forwards
    is a sample of a prefill chain.

    A forward's period is the SMALLEST of the last few such bounds, clean
    or not: what the device does at the load it carries now. Until PR 36 a
    clean sample outranked every other however old it was; under callers
    who never leave a row idle every segment has an admission in front, no
    period is clean, and the estimate stayed where the last lull left it.
    Where a forward costs what its live tokens touch (a sparse-expert
    block: 3.7 ms with one row live, 5-6 with a full slab; PERF.md, PR 36)
    that was a third under the truth, the segment came out half as long
    again as the worker needs, and which lull a run happened to see set
    the length it served."""

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        self.clock = clock
        self._forward = _Recent()  # period / forwards, prefills inside or not
        self._prefill = _Recent()
        self._admit = _Recent()
        self._dispatch = _Recent()
        self._harvest = _Recent()
        # Device work enqueued since the last ready stamp, oldest first:
        # (host time enqueued, forwards), forwards 0 = a prefill chain.
        self._queued: "deque[tuple[float, int]]" = deque()
        self._t_ready: Optional[float] = None

    # ------------------------------------------------------------ estimates
    @property
    def forward_s(self) -> Optional[float]:
        return self._forward.low

    @property
    def prefill_s(self) -> float:
        return self._prefill.value or 0.0

    @property
    def margin_s(self) -> Optional[float]:
        """One admission plus one dispatch, on the host."""
        admit, dispatch = self._admit.value, self._dispatch.value
        if admit is None or dispatch is None:
            return None
        return admit + dispatch

    @property
    def host_s(self) -> Optional[float]:
        """The worker's own work for one segment: an admission, a dispatch
        and a harvest's bookkeeping (nothing until one was timed)."""
        margin = self.margin_s
        return None if margin is None else margin + (self._harvest.value or 0.0)

    def window(self, tick: int, ceiling: int) -> int:
        """Forwards the next segment may run (``segment_forwards`` on the
        current estimates)."""
        return segment_forwards(
            tick=tick,
            ceiling=ceiling,
            forward_s=self.forward_s,
            host_s=self.host_s,
        )

    def ready_at(self) -> Optional[float]:
        """Predicted ready time of the newest segment in flight: the queue
        replayed from the last ready stamp, each item starting when the
        one before it ends or when it was enqueued, whichever is later."""
        forward = self.forward_s
        if forward is None:
            return None
        finish, newest = self._t_ready, None
        for t, forwards in self._queued:
            start = t if finish is None else max(finish, t)
            finish = start + (forward * forwards if forwards else self.prefill_s)
            if forwards:
                newest = finish
        return newest

    # ------------------------------------------------------------- reports
    def admitted(self, t0: float, t1: float) -> None:
        self._admit.add(t1 - t0)
        self._queued.append((t1, 0))

    def dispatched(self, t0: float, t1: float, forwards: int) -> None:
        self._dispatch.add(t1 - t0)
        self._queued.append((t1, max(1, forwards)))

    def harvested(self, t0: float, t1: float) -> None:
        """The host's bookkeeping after a segment's fetch returned."""
        self._harvest.add(t1 - t0)

    def ready(self, t_ready: float, forwards: int) -> None:
        """The oldest segment in flight was fetched at ``t_ready`` after
        ``forwards`` forwards (fewer than dispatched when every row
        finished early)."""
        prefills, t_disp = 0, None
        while self._queued:
            t, fw = self._queued.popleft()
            if fw:
                t_disp = t
                break
            prefills += 1
        t_prev, self._t_ready = self._t_ready, t_ready
        if t_disp is None or forwards <= 0:
            return
        # Pipelined = dispatched before the segment ahead of it was ready:
        # only then did the device run back to back, so that the wall
        # between the two stamps is the work between them.
        pipelined = t_prev is not None and t_disp <= t_prev
        period = t_ready - (t_prev if pipelined else t_disp)
        if period <= 0:
            return
        if prefills and not pipelined:
            return  # from idle: the period holds the host's admission too
        forward = self.forward_s  # before this period moves it
        self._forward.add(period / forwards)
        if prefills and forward is not None:
            self._prefill.add(max(0.0, period - forward * forwards) / prefills)

    def reset(self) -> None:
        """The in-flight segments were dropped (a failed dispatch)."""
        self._queued.clear()
        self._t_ready = None
