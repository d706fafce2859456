"""Just-in-time dispatch of the next decode segment (ISSUE 29) and its
length (ISSUE 31), the part with no device in it: the hold's decision and
the segment's length as pure functions, and the pacer's model of the
device queue on an injected clock. Nothing here depends on how fast
anything decodes."""

import math

import pytest

from mcpx.engine.pacing import (
    HOST_COVER,
    SegmentPacer,
    hold_until,
    segment_forwards,
)

OK = dict(in_flight=True, free_rows=3, backlog=0, ready_at=10.0, margin=0.025)


@pytest.mark.parametrize(
    "change, want",
    [
        ({}, 9.975),  # ready_at - margin
        ({"in_flight": False}, None),  # idle engine, first request, depth 1
        ({"free_rows": 0}, None),  # full slab: nobody could join
        ({"backlog": 2}, None),  # admission left requests behind (pages)
        ({"ready_at": None}, None),  # no period estimate yet
        ({"margin": None}, None),  # no admission or dispatch timed yet
        ({"ready_at": 5.02}, None),  # less than the margin left
        ({"ready_at": 4.0}, None),  # predicted ready in the past: late already
        ({"margin": 0.0}, 10.0),
        ({"free_rows": 1}, 9.975),
    ],
)
def test_hold_decision(change, want):
    got = hold_until(5.0, **{**OK, **change})
    assert got == (pytest.approx(want) if want is not None else None)


class Clock:
    def __init__(self) -> None:
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def steady(pacer: SegmentPacer, t: float, segments: int, period: float, fw: int) -> float:
    """Dispatch ``segments`` pipelined segments of ``fw`` forwards, one
    ready every ``period`` from ``t``; two in flight throughout. Returns
    the last ready stamp."""
    pacer.dispatched(t - 0.002, t, fw)
    for _ in range(segments):
        pacer.dispatched(t + 0.001, t + 0.003, fw)
        t += period
        pacer.ready(t, fw)
    return t


def test_no_estimate_no_prediction_then_a_clean_period_gives_one():
    pacer = SegmentPacer(Clock())
    assert pacer.ready_at() is None and pacer.margin_s is None
    # From idle: an admission, then its segment. Its period holds the
    # prefill and starts at the dispatch: nothing is learnt from it.
    pacer.admitted(0.000, 0.020)
    pacer.dispatched(0.020, 0.023, 16)
    assert pacer.ready_at() is None
    pacer.dispatched(0.024, 0.026, 16)  # the second, behind the first
    pacer.ready(0.200, 16)
    assert pacer.forward_s is None and pacer.ready_at() is None
    # The second ran back to back with no prefill in front: clean.
    pacer.ready(0.360, 16)
    assert pacer.forward_s == pytest.approx(0.010)
    assert pacer.margin_s == pytest.approx(0.020 + 0.0025)
    # A third dispatched now is predicted from the last ready stamp, or
    # from its own enqueue where the device had already run dry.
    pacer.dispatched(0.400, 0.402, 16)
    assert pacer.ready_at() == pytest.approx(0.402 + 0.160)


def test_prediction_replays_the_queue_with_prefills_in_front():
    pacer = SegmentPacer(Clock())
    t = steady(pacer, 1.0, 4, 0.160, 16)  # 10 ms a forward, clean
    assert pacer.forward_s == pytest.approx(0.010)
    # One segment is in flight (dispatched before the last ready stamp):
    # ready one period after that stamp.
    assert pacer.ready_at() == pytest.approx(t + 0.160)
    # Two admissions during the hold chain behind it, in front of the NEXT
    # segment: the prediction for the one in flight does not move...
    pacer.admitted(t + 0.004, t + 0.024)
    pacer.admitted(t + 0.030, t + 0.050)
    assert pacer.ready_at() == pytest.approx(t + 0.160)
    # ...and the next one's holds them, at no cost until one was measured.
    pacer.dispatched(t + 0.135, t + 0.138, 16)
    assert pacer.ready_at() == pytest.approx(t + 0.320)
    pacer.ready(t + 0.160, 16)
    # Its period holds two prefill chains of 12 ms each.
    pacer.ready(t + 0.160 + 0.184, 16)
    assert pacer.prefill_s == pytest.approx(0.012)
    assert pacer.forward_s == pytest.approx(0.010)  # 11.5 with prefills: no cap
    t += 0.344
    pacer.dispatched(t - 0.010, t - 0.008, 16)  # was in flight at that stamp
    pacer.admitted(t + 0.002, t + 0.020)
    pacer.dispatched(t + 0.130, t + 0.132, 8)
    # In flight: 16 forwards from the stamp; behind it a prefill and 8.
    assert pacer.ready_at() == pytest.approx(t + 0.160 + 0.012 + 0.080)


def test_deeper_pipeline_predicts_the_newest_in_flight():
    pacer = SegmentPacer(Clock())
    t = steady(pacer, 1.0, 3, 0.080, 8)
    pacer.dispatched(t + 0.001, t + 0.002, 8)  # two in flight now
    assert pacer.ready_at() == pytest.approx(t + 0.160)


def test_one_outlier_does_not_move_the_estimates_and_reset_forgets_the_queue():
    pacer = SegmentPacer(Clock())
    t = steady(pacer, 1.0, 4, 0.160, 16)
    pacer.dispatched(t + 0.001, t + 0.003, 16)
    t += 2.0  # a stalled host: one period of seconds
    pacer.ready(t, 16)
    assert pacer.forward_s == pytest.approx(0.010)
    pacer.admitted(t, t + 0.020)
    pacer.admitted(t + 0.02, t + 0.040)
    pacer.admitted(t + 0.04, t + 1.5)  # one slow admission
    assert pacer.margin_s == pytest.approx(0.020 + 0.002)
    pacer.reset()  # a failed dispatch dropped what was in flight
    assert pacer.ready_at() is None
    assert pacer.forward_s == pytest.approx(0.010)  # what was learnt stays
    pacer.dispatched(t + 2.0, t + 2.002, 16)
    assert pacer.ready_at() == pytest.approx(t + 2.002 + 0.160)


def test_periods_with_prefills_inside_bound_the_estimate_like_clean_ones():
    pacer = SegmentPacer(Clock())
    pacer.dispatched(0.0, 0.002, 16)
    pacer.ready(0.100, 16)  # not pipelined (no stamp before it): clean, 98 ms
    t = 0.100
    for _ in range(3):  # every later period has an admission inside
        pacer.admitted(t - 0.060, t - 0.050)
        pacer.dispatched(t - 0.050, t - 0.048, 16)
    for k in range(3):
        pacer.ready(t + 0.080 * (k + 1), 16)
    # 80 ms over 16 forwards with a prefill inside: 5 ms bounds the stale
    # clean 6.125 from above, and no prefill cost is read off a period
    # shorter than the estimate says its forwards take.
    assert pacer.forward_s == pytest.approx(0.005)
    assert pacer.prefill_s == 0.0
    # An early exit (every row finished) reports fewer forwards than
    # dispatched; zero forwards teaches nothing.
    pacer.dispatched(t, t + 0.001, 16)
    pacer.ready(t + 0.300, 0)
    assert pacer.forward_s == pytest.approx(0.005)


@pytest.mark.parametrize("loaded_s, want", [(0.0065, 8), (0.0037, 12)])
def test_a_lull_is_forgotten_once_the_load_is_back(loaded_s, want):
    """One row live reads a forward far under a full slab's (a sparse
    block reads what its tokens touch). The estimate follows the periods
    the device runs NOW, prefills inside or not: the lull's clean sample
    leaves with the fifth loaded period after it, and the segment's length
    with it (until PR 36 it stayed, and the lull a run met set the length
    it served: 12 forwards where the worker needs 8)."""
    pacer = SegmentPacer(Clock())
    t = steady(pacer, 1.0, 5, 16 * 0.0037, 16)  # a lull: 3.7 ms a forward
    assert pacer.forward_s == pytest.approx(0.0037)
    for _ in range(5):  # callers back: an admission in front of every segment
        pacer.admitted(t + 0.004, t + 0.015)  # 11 ms + 2 ms of host work
        pacer.dispatched(t + 0.015, t + 0.017, 16)
        t += 16 * loaded_s + 0.008  # its forwards and an 8 ms chain
        pacer.ready(t, 16)
    assert pacer.forward_s == pytest.approx(loaded_s + 0.0005)
    assert pacer.host_s == pytest.approx(0.013)
    assert pacer.window(4, 16) == want == 4 * math.ceil(
        HOST_COVER * 0.013 / (4 * (loaded_s + 0.0005))
    )


# ------------------------------------------------- the segment's length
# A tick of 4 forwards under a window of 16; a clean forward of 8 ms, so a
# tick is 32 ms of device time.
SIZED = dict(tick=4, ceiling=16, forward_s=0.008, host_s=0.0)


def _host(ticks: float) -> float:
    """Host work a segment that ``HOST_COVER`` turns into ``ticks`` ticks."""
    return ticks * 0.032 / HOST_COVER


@pytest.mark.parametrize(
    "change, want",
    [
        ({"forward_s": None}, 16),  # no period seen yet: the configured window
        ({"host_s": None}, 16),  # no admission or dispatch timed yet
        ({"forward_s": None, "host_s": None, "ceiling": 64}, 64),
        ({"host_s": _host(1.6)}, 8),  # the host's work sets the floor
        ({"host_s": _host(2.0)}, 8),  # exactly covered: no tick more
        ({"host_s": _host(2.1)}, 12),
        ({"host_s": _host(1.3)}, 8),
        ({"host_s": _host(2.5)}, 12),
        ({"host_s": _host(3.0)}, 12),
        ({"host_s": _host(9.0)}, 16),  # never above the ceiling
        ({"host_s": _host(40.0)}, 16),
        ({}, 4),  # nothing to cover: never under one tick
        ({"host_s": _host(0.2)}, 4),
        ({"forward_s": 0.016, "host_s": _host(1.6)}, 4),  # a slower forward covers sooner
        ({"forward_s": 0.004, "host_s": _host(1.6)}, 16),
        ({"ceiling": 4, "host_s": _host(3.0)}, 4),  # steps_per_dispatch 1: the tick
        ({"tick": 1, "ceiling": 16, "host_s": _host(1.6)}, 7),  # ticks of one forward
        ({"tick": 3, "ceiling": 12, "host_s": _host(1.6)}, 9),  # whole ticks of three
    ],
)
def test_segment_length_decision(change, want):
    assert segment_forwards(**{**SIZED, **change}) == want


@pytest.mark.parametrize("tick, steps", [(1, 1), (1, 64), (4, 4), (4, 16), (3, 5)])
def test_segment_length_is_whole_ticks_between_one_tick_and_the_window(tick, steps):
    ceiling = tick * steps
    seen = set()
    for forward_ms in (0.5, 2, 6.35, 12.76, 15.4, 40):
        for host_ms in (0, 1, 10, 26, 60, 400):
            n = segment_forwards(
                tick=tick, ceiling=ceiling, forward_s=forward_ms / 1e3,
                host_s=host_ms / 1e3,
            )
            assert tick <= n <= ceiling and n % tick == 0
            seen.add(n)
    assert tick in seen and ceiling in seen  # both ends are reached


def test_the_pacer_sizes_the_segment_from_what_the_worker_reported():
    pacer = SegmentPacer(Clock())
    assert pacer.host_s is None and pacer.window(4, 16) == 16  # nothing known
    t = steady(pacer, 1.0, 4, 0.160, 16)  # 10 ms a forward; a dispatch 2 ms
    assert pacer.host_s is None and pacer.window(4, 16) == 16  # no admission yet
    pacer.admitted(t + 0.004, t + 0.024)
    # 20 ms + 2 ms of host work a segment against ticks of 40 ms.
    assert pacer.host_s == pytest.approx(0.022)
    want = 4 * math.ceil(HOST_COVER * 0.022 / 0.040)
    assert pacer.window(4, 64) == want == segment_forwards(
        tick=4, ceiling=64, forward_s=0.010, host_s=0.022
    )
    pacer.harvested(t + 0.030, t + 0.050)  # the harvest's bookkeeping counts
    assert pacer.host_s == pytest.approx(0.042)
    longer = 4 * math.ceil(HOST_COVER * 0.042 / 0.040)
    assert pacer.window(4, 64) == longer > want
    assert pacer.window(4, 8) == 8  # under a lower ceiling
    assert pacer.window(1, 64) == math.ceil(HOST_COVER * 0.042 / 0.010)
    # The length it was told is the length it predicts with.
    pacer.dispatched(t + 0.051, t + 0.053, longer)
    assert pacer.ready_at() == pytest.approx(t + 0.160 + longer * 0.010)


@pytest.mark.parametrize("chain_s", [0.012, 0.100, 0.400])
def test_a_prefill_chain_enters_the_prediction_and_not_the_length(chain_s):
    """The chain in front of a segment delays its predicted ready time; it
    does not lengthen the segment (until PR 36 a segment had to outlast the
    chain, and a chain that grows with the rows a long segment retires
    then kept the segment long)."""
    pacer = SegmentPacer(Clock())
    t = steady(pacer, 1.0, 4, 0.080, 8)  # 10 ms a forward; a dispatch 2 ms
    pacer.admitted(t + 0.004, t + 0.014)  # 10 ms + 2 ms of host work
    pacer.dispatched(t + 0.014, t + 0.016, 8)
    pacer.ready(t + 0.080, 8)
    pacer.ready(t + 0.160 + chain_s, 8)  # its period held the chain
    assert pacer.prefill_s == pytest.approx(chain_s)
    assert pacer.window(4, 16) == 4 == 4 * math.ceil(HOST_COVER * 0.012 / 0.040)
    t += 0.160 + chain_s
    pacer.dispatched(t - 0.010, t - 0.008, 8)  # in flight at that stamp
    pacer.admitted(t + 0.002, t + 0.012)
    pacer.dispatched(t + 0.012, t + 0.014, 4)
    assert pacer.ready_at() == pytest.approx(t + 0.080 + chain_s + 0.040)
