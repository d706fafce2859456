"""Just-in-time dispatch of the next decode segment (ISSUE 29) in the
engine worker, on the CPU at ``model=test`` with the period estimate
injected: a request that arrives while a segment is in flight rides the
NEXT dispatched segment; a control op or the stop sentinel arriving during
a hold is honoured; and where nothing is in flight, the slab is full or
there is no estimate, the worker never waits and the loop is the one it
was. The pure decision and the pacer's arithmetic are in test_pacing.py."""

import asyncio
import threading

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.core.errors import EngineError
from mcpx.engine.engine import InferenceEngine
from mcpx.engine.pacing import SegmentPacer
from mcpx.telemetry import tracing
from mcpx.telemetry.tracing import Tracer


class WholeWindow(SegmentPacer):
    """A pacer that never shortens a segment (ISSUE 31): every dispatch
    runs the configured window, so that ``LONG`` below stays long whatever
    this CPU measures."""

    def window(self, tick, ceiling):
        return ceiling


class HoldsTillDone(WholeWindow):
    """Every segment in flight is predicted ready far ahead, at no margin:
    the worker holds whenever the decision's other conditions allow it,
    until its look at the device finds the segment done."""

    forward_s = 0.002  # how often the hold looks at the device
    margin_s = 0.0

    def ready_at(self):
        return self.clock() + 30.0


class NoEstimate(WholeWindow):
    """A worker that has not seen a clean period yet."""

    def ready_at(self):
        return None


def make_engine(rows: int, **engine) -> InferenceEngine:
    return InferenceEngine(
        MCPXConfig.from_dict(
            {
                "model": {"size": "test", "max_seq_len": 512},
                "engine": {
                    "use_pallas": True,
                    "interpret": True,
                    "max_batch_size": rows,
                    "max_decode_len": 256,
                    "temperature": 0.0,
                    **engine,
                },
            }
        )
    )


async def traced(eng, tracer, text: str, n: int) -> list:
    root = tracer.start_request("/plan")
    with tracing.activate(root):
        await eng.generate(
            eng.tokenizer.encode(text), max_new_tokens=n, constrained=False,
            temperature=0.0,
        )
    tracer.finish(root)
    return tracer.get(root.record.trace_id).spans


def segments(spans: list) -> list:
    return sorted(
        (s for s in spans if s.name == "engine.segment"), key=lambda s: s.attrs["seq"]
    )


class HoldGate:
    """Stops the worker at the door of each hold until the test has put
    what it wants into the engine's queue: the arrival is then there
    whatever the CPU's speed. ``entered`` carries the dispatch count the
    worker saw at each entry."""

    def __init__(self, eng: InferenceEngine, loop: asyncio.AbstractEventLoop) -> None:
        self.entered: "asyncio.Queue[int]" = asyncio.Queue()
        self.go = threading.Event()
        self.armed = True
        real = eng._hold_wait

        def gated(until: float):
            if self.armed:
                loop.call_soon_threadsafe(self.entered.put_nowait, eng._dispatch_seq)
                assert self.go.wait(60), "the test never released the hold"
                self.go.clear()
            return real(until)

        eng._hold_wait = gated

    async def next_hold(self) -> int:
        return await asyncio.wait_for(self.entered.get(), 60)

    def release(self, armed: bool = True) -> None:
        self.armed = armed
        self.go.set()


async def enqueued(eng: InferenceEngine, coro) -> asyncio.Task:
    """Start ``coro`` (which enqueues on the engine) and return once its
    item is in the engine's queue."""
    task = asyncio.ensure_future(coro)
    for _ in range(2000):
        if not eng._queue.empty():
            return task
        await asyncio.sleep(0.001)
    raise AssertionError("nothing reached the engine's queue")


# Long segments (64 forwards): the one in flight outlasts the few
# milliseconds the test needs to enqueue behind the gate.
LONG = dict(steps_per_dispatch=16)


# One free row of two, an admission a moment ago: the small-cohort
# hysteresis of _admit would leave a lone arrival pending.
GATED = dict(admit_min_free=2, admit_max_wait_s=60.0)


@pytest.mark.parametrize(
    "control_op_first, engine",
    [(False, {}), (True, {}), (False, GATED)],
    ids=["request", "after-a-pin", "past-the-small-cohort-gate"],
)
def test_an_arrival_during_a_hold_rides_the_next_dispatched_segment(
    control_op_first, engine
):
    """A is decoding alone on a slab of two rows; the worker holds the
    next segment; B arrives. B is admitted during the hold and its first
    engine.segment span carries the seq right after the one in flight,
    with itself as the one hold_joined row; the worker saw it at once
    (unseen_ms is a sliver, where it used to be a period). With a control
    op arriving first in the same hold (a prefix pin), the op is applied
    and the hold goes on: B still rides that segment. So it does where
    admission's small-cohort hysteresis would have left it pending: during
    a hold there is nothing to decode instead."""

    async def go():
        eng = make_engine(rows=2, **LONG, **engine)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 8)
            eng._pacer = HoldsTillDone()
            gate = HoldGate(eng, asyncio.get_running_loop())
            a = asyncio.ensure_future(traced(eng, tracer, "the long request", 200))
            in_flight = await gate.next_hold()
            if control_op_first:
                pin = await enqueued(eng, eng.pin_prefix(eng.tokenizer.encode("warm")))
                gate.release()
                await asyncio.wait_for(pin, 60)  # applied inside the hold
                assert await gate.next_hold() == in_flight  # and it goes on
            b = await enqueued(eng, traced(eng, tracer, "a late one", 24))
            gate.release(armed=False)
            b_spans, a_spans = await b, await a
        finally:
            await eng.aclose()
        first = segments(b_spans)[0].attrs
        assert first["seq"] == in_flight + 1
        assert (first["prefill_rows"], first["hold_joined_rows"]) == (1, 1)
        # A rode every segment, this one included: none was skipped or
        # dispatched early without B.
        assert [s.attrs["seq"] for s in segments(a_spans)][:2] == [in_flight, in_flight + 1]
        wait = next(s for s in b_spans if s.name == "engine.queue_wait")
        assert wait.attrs["unseen_ms"] <= 0.5 * wait.duration_ms + 5.0
        assert wait.attrs["free_row_ms"] == pytest.approx(wait.duration_ms, abs=0.01)

    asyncio.run(asyncio.wait_for(go(), 240))


def test_a_burst_still_arriving_at_an_idle_engine_rides_the_first_segment():
    """Nothing is in flight, so nothing is held; but the requests that
    land while the first of a burst is being admitted are admitted too
    before the first dispatch: all three carry the same first seq."""

    async def go():
        eng = make_engine(rows=4)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 8)
            eng._pacer = NoEstimate()  # no hold could do it instead
            loop = asyncio.get_running_loop()
            entered, go_on = asyncio.Event(), threading.Event()
            real, first = eng._admit, [True]

            def admit(*args, **kwargs):
                if first[0]:  # the burst's first member is being admitted
                    first[0] = False
                    loop.call_soon_threadsafe(entered.set)
                    assert go_on.wait(60)
                return real(*args, **kwargs)

            eng._admit = admit
            while eng._inflight:  # the warm request's lagged last segment
                await asyncio.sleep(0.001)
            a = asyncio.ensure_future(traced(eng, tracer, "first of a burst", 40))
            await asyncio.wait_for(entered.wait(), 60)
            b = await enqueued(eng, traced(eng, tracer, "second of a burst", 40))
            c = asyncio.ensure_future(traced(eng, tracer, "third of a burst", 40))
            while eng._queue.qsize() < 2:
                await asyncio.sleep(0.001)
            go_on.set()
            spans = [await a, await b, await c]
        finally:
            await eng.aclose()
        firsts = {segments(s)[0].attrs["seq"] for s in spans}
        assert len(firsts) == 1
        assert eng.queue_stats()["worker_profile"]["phases"]["hold"]["count"] == 0

    asyncio.run(asyncio.wait_for(go(), 240))


def test_the_small_cohort_gate_stands_where_nothing_is_held():
    """The same lone arrival with no estimate, so no hold: the hysteresis
    is what it was, and B waits until A's retirement frees the slab."""

    async def go():
        eng = make_engine(rows=2, **GATED)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 8)
            eng._pacer = NoEstimate()
            a = asyncio.ensure_future(traced(eng, tracer, "the long request", 120))
            while eng._dispatch_seq < 3:  # A is decoding
                await asyncio.sleep(0.001)
            b_spans, a_spans = await traced(eng, tracer, "a late one", 8), await a
        finally:
            await eng.aclose()
        admitted = next(s for s in b_spans if s.name == "engine.queue_wait").t1
        retired = next(s for s in a_spans if s.name == "engine.decode").t1
        assert admitted >= retired
        assert eng.queue_stats()["worker_profile"]["phases"]["hold"]["count"] == 0

    asyncio.run(asyncio.wait_for(go(), 240))


def test_the_stop_sentinel_ends_a_hold():
    """aclose() while the worker is holding: the sentinel wakes it, the
    worker leaves, and the request in flight resolves (delivered by the
    last harvest, or failed as closed), never left hanging."""

    async def go():
        eng = make_engine(rows=2, **LONG)
        await eng.start()
        tracer = Tracer(None, enabled=True, sample_rate=1.0)
        await traced(eng, tracer, "warm the shapes", 8)
        eng._pacer = HoldsTillDone()
        gate = HoldGate(eng, asyncio.get_running_loop())
        a = asyncio.ensure_future(traced(eng, tracer, "the long request", 200))
        await gate.next_hold()
        closing = asyncio.ensure_future(eng.aclose())
        while eng._queue.empty():  # the sentinel is in the queue
            await asyncio.sleep(0.001)
        gate.release(armed=False)
        await asyncio.wait_for(closing, 60)
        assert not eng._thread.is_alive()
        with pytest.raises(EngineError, match="closed"):
            await asyncio.wait_for(a, 60)

    asyncio.run(asyncio.wait_for(go(), 240))


@pytest.mark.parametrize(
    "rows, engine, pacer",
    [
        (4, {"pipeline_depth": 1}, HoldsTillDone),  # nothing is ever in flight
        (1, {}, HoldsTillDone),  # in flight means full: nobody could join
        (4, {}, NoEstimate),  # no period seen yet
    ],
    ids=["depth-1", "full-slab", "no-estimate"],
)
def test_without_a_reason_to_hold_the_worker_never_waits(rows, engine, pacer):
    """The loop of before: no ``hold`` phase, no joined row, and the same
    greedy tokens as an engine that does hold."""

    async def run(eng, pacer):
        await eng.start()
        try:
            eng._pacer = pacer()
            ids = [eng.tokenizer.encode(t) for t in ("one request", "another", "a third one")]
            out = await asyncio.gather(
                *(
                    eng.generate(i, max_new_tokens=40, constrained=False, temperature=0.0)
                    for i in ids
                )
            )
            return [r.token_ids for r in out], eng.queue_stats()["worker_profile"]
        finally:
            await eng.aclose()

    async def go():
        tokens, wp = await run(make_engine(rows, **engine), pacer)
        assert wp["phases"]["hold"]["count"] == 0
        assert wp["hold_joined_rows"] == 0
        held_tokens, held = await run(make_engine(4), HoldsTillDone)
        assert held["phases"]["hold"]["count"] > 0
        assert tokens == held_tokens and all(len(t) > 0 for t in tokens)

    asyncio.run(asyncio.wait_for(go(), 240))


def test_the_worker_dispatches_and_reports_the_length_the_pacer_chose():
    """ISSUE 31: the pacer is asked at every dispatch how many forwards
    the segment may run, under the configured window (4 ticks of 4) as its
    ceiling; the segment runs no more than that, the worker reports that
    length back (``ready_at()`` predicts the segment that was sent, not
    the configured one), and the engine.segment spans and the worker's
    profile carry what was asked and the ceiling it was asked under."""

    class TwoTicks(SegmentPacer):
        forward_s = 0.010  # the estimate the prediction is made with

        def __init__(self):
            super().__init__()
            self.asked, self.told = [], []

        def window(self, tick, ceiling):
            self.asked.append((tick, ceiling))
            return 2 * tick

        def dispatched(self, t0, t1, forwards):
            super().dispatched(t0, t1, forwards)
            self.told.append((forwards, self.ready_at() - t1))

    async def go():
        eng = make_engine(rows=2)  # decode_steps_per_tick 4 x steps_per_dispatch 4
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 8)
            before = dict(eng.queue_stats()["worker_profile"])
            pacer = eng._pacer = TwoTicks()
            spans = await traced(eng, tracer, "a request of some length", 60)
            after = eng.queue_stats()["worker_profile"]
        finally:
            await eng.aclose()
        assert pacer.asked and set(pacer.asked) == {(4, 16)}
        assert [f for f, _ in pacer.told] == [8] * len(pacer.asked)
        # From idle the device's queue held nothing in front: the segment
        # is predicted ready 8 forwards after its dispatch, not 16.
        assert pacer.told[0][1] == pytest.approx(8 * 0.010, abs=1e-6)
        segs = segments(spans)
        assert len(segs) >= 3
        for s in segs:
            assert (s.attrs["window"], s.attrs["window_max"]) == (8, 16)
            assert 1 <= s.attrs["forwards"] <= 8
        assert any(s.attrs["forwards"] == 8 for s in segs)
        n = len(pacer.asked)
        assert after["window_forwards"] - before["window_forwards"] == 8 * n
        assert after["window_max_forwards"] - before["window_max_forwards"] == 16 * n
        assert before["window_forwards"] <= before["window_max_forwards"]

    asyncio.run(asyncio.wait_for(go(), 240))
