"""The engine worker's timeline as the traces carry it (ISSUE 24): the
per-segment device period and host phases on ``engine.segment`` spans, why
a request waited on ``engine.queue_wait``, and the worker's phases as
``mcpx.worker.<phase>`` events in the profiler's own trace. CPU,
``model=test``, the ragged kernel interpreted."""

import asyncio
import glob

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.engine.engine import InferenceEngine
from mcpx.telemetry import tracing
from mcpx.telemetry.flight import PROFILE_PHASES, SEGMENT_PARTS
from mcpx.telemetry.tracing import Tracer
from tests.test_hold_dispatch import HoldsTillDone

TIMELINE = ("seq", "prefill_rows", "hold_joined_rows", "period_ms", "sync_ms",
            "idle_ms", "hold_ms", "host_ms", *SEGMENT_PARTS)


def make_engine(rows: int) -> InferenceEngine:
    return InferenceEngine(
        MCPXConfig.from_dict(
            {
                "model": {"size": "test", "max_seq_len": 256},
                "engine": {
                    "use_pallas": True,
                    "interpret": True,
                    "max_batch_size": rows,
                    "max_decode_len": 64,
                    "temperature": 0.0,
                },
            }
        )
    )


async def traced(eng, tracer, text: str, n: int) -> list:
    """One traced unconstrained generate of exactly ``n`` tokens' budget;
    returns the request's spans."""
    root = tracer.start_request("/plan")
    with tracing.activate(root):
        await eng.generate(
            eng.tokenizer.encode(text), max_new_tokens=n, constrained=False,
            temperature=0.0,
        )
    tracer.finish(root)
    return tracer.get(root.record.trace_id).spans


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


@pytest.mark.parametrize("held", [False, True], ids=["own-estimate", "held"])
def test_segment_spans_carry_the_segments_timeline_and_it_tiles(held):
    """Every engine.segment span of one harvest carries the same timeline
    (seq, prefill_rows, hold_joined_rows, period_ms, sync_ms, idle_ms,
    hold_ms, host_ms and its parts), and host_ms + sync_ms + idle_ms +
    hold_ms is the wall between two consecutive ready stamps: checked
    against the spans' own ends, which the worker stamps a few statements
    after the ready stamp. Once with the pacer's own estimate (on a CPU
    that may hold or not), once with every segment held (ISSUE 29)."""

    async def go():
        eng = make_engine(rows=4)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            # Warm the executables first: a compile inside a segment's
            # window is host time too, but makes the windows lopsided.
            await traced(eng, tracer, "warm the shapes", 40)
            if held:
                eng._pacer = HoldsTillDone()
            per_request = await asyncio.gather(
                traced(eng, tracer, "first request of three", 56),
                traced(eng, tracer, "the second request", 56),
                traced(eng, tracer, "a third", 56),
            )
        finally:
            await eng.aclose()
        by_seq: dict[int, list] = {}
        for spans in per_request:
            segs = named(spans, "engine.segment")
            assert len(segs) >= 3
            for s in segs:
                assert set(TIMELINE) <= set(s.attrs)
                by_seq.setdefault(s.attrs["seq"], []).append(s)
        # One harvest, one timeline: identical on all its rows, like the
        # span's start (the readers take one row per cluster of starts).
        assert any(len(rows) == 3 for rows in by_seq.values())
        for rows in by_seq.values():
            for s in rows[1:]:
                assert s.t0 == rows[0].t0 and s.t1 == rows[0].t1
                assert {k: s.attrs[k] for k in TIMELINE} == {
                    k: rows[0].attrs[k] for k in TIMELINE
                }
        seqs = sorted(by_seq)
        assert seqs == list(range(seqs[0], seqs[-1] + 1))  # none skipped
        first = by_seq[seqs[0]][0].attrs
        assert first["prefill_rows"] == 3  # the gathered cohort's prefills
        tiled = wall = 0.0
        for prev, cur in zip(seqs, seqs[1:]):
            a = by_seq[cur][0].attrs
            parts = sum(a[k] for k in SEGMENT_PARTS)
            assert 0.0 <= parts <= a["host_ms"] + 0.01
            assert 0.0 <= a["sync_ms"] and 0.0 <= a["idle_ms"]
            assert 0.0 <= a["hold_ms"]
            assert 0 <= a["hold_joined_rows"] <= a["prefill_rows"]
            window = a["host_ms"] + a["sync_ms"] + a["idle_ms"] + a["hold_ms"]
            # Dispatched before the previous segment was ready or after:
            # either way the period ends at this ready stamp and starts no
            # earlier than the previous one.
            assert 0.0 < a["period_ms"] <= window + 0.01
            ends = (by_seq[cur][0].t1 - by_seq[prev][0].t1) * 1e3
            assert window == pytest.approx(ends, rel=0.02, abs=5.0)
            tiled += window
            wall += ends
        assert tiled == pytest.approx(wall, rel=0.02)
        if held:
            # Three rows of four taken: segments behind the first were
            # held, so the worker waited in ``hold`` while the device
            # decoded, and the tiling above counted that wait.
            assert sum(by_seq[q][0].attrs["hold_ms"] for q in seqs[1:]) > 0.0
            assert eng.queue_stats()["worker_profile"]["phases"]["hold"]["count"] > 0

    asyncio.run(asyncio.wait_for(go(), 240))


@pytest.mark.parametrize(
    "metric, num, den, moves",
    [
        ("engine.hold_ms_per_forward", "hold_ms", "forwards", "plans_per_s"),
        ("engine.hold_joined_share", "hold_joined_rows", "prefill_rows", "plan_p50_ms"),
    ],
)
def test_the_hold_metrics_read_attributes_the_engine_writes(metric, num, den, moves):
    """The benchmark's two hold metrics (ISSUE 29) are data files over the
    harness's ``span_attr_ratio``: each names engine.segment attributes
    that ``_segment_timeline`` and ``_harvest`` really write, once a
    segment, and ``BENCHMARK.json`` lists it for every cell."""
    import json
    import os

    from mcpx.engine.engine import InferenceEngine as E

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "chip", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert (spec["name"], spec["reader"], spec["layer"], spec["moves"]) == (
        metric, "span_attr_ratio", "engine", moves
    )
    assert spec["args"] == {
        "name": "engine.segment", "num": num, "den": den,
        "num_per": "segment", "den_per": "segment",
    }
    written = set(
        E._segment_timeline(1, 2, 1, 0.0, 1.0, 0.5, dict.fromkeys(PROFILE_PHASES, 0.0))
    ) | {"forwards", "tokens"}
    assert {num, den} <= written
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == metric)
    assert entry == {
        "name": metric, "unit": spec["unit"], "better": "higher",
        "source": "program_span", "layer": "engine", "moves": moves,
    }


def test_queue_wait_says_whether_a_row_was_free_and_the_worker_looking():
    """A slab of 2 rows, 3 requests at once: the third waits for a row.
    Its engine.queue_wait span says so: free_row_ms covers only the time
    before the first admission took both rows and the time from a row's
    release to its own admission, and unseen_ms (in queue.Queue, before
    the worker's drain pass moved it) is a sliver of the wait."""

    async def go():
        eng = make_engine(rows=2)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 24)
            first = asyncio.ensure_future(traced(eng, tracer, "first of three", 48))
            second = asyncio.ensure_future(traced(eng, tracer, "second of three", 48))
            await asyncio.sleep(0)  # both enqueued ahead of the third
            third = await traced(eng, tracer, "third of three", 8)
            a, b = await first, await second
        finally:
            await eng.aclose()
        qw = {k: named(s, "engine.queue_wait")[0] for k, s in
              (("a", a), ("b", b), ("c", third))}
        for s in qw.values():
            assert 0.0 <= s.attrs["unseen_ms"] <= s.duration_ms + 1e-3
            assert 0.0 <= s.attrs["free_row_ms"] <= s.duration_ms + 1e-3
        # The first two found the slab empty: a row was free all the while.
        for k in "ab":
            assert qw[k].attrs["free_row_ms"] == pytest.approx(
                qw[k].duration_ms, abs=0.01
            )
        c = qw["c"]
        assert c.t1 > max(qw["a"].t1, qw["b"].t1)  # admitted after them
        released = min(named(s, "engine.decode")[0].t1 for s in (a, b))
        assert released < c.t1
        taken = max(qw["a"].t1, qw["b"].t1)  # the admission that filled the slab
        free_before = max(0.0, taken - c.t0) * 1e3
        free_after = (c.t1 - released) * 1e3
        assert c.attrs["free_row_ms"] == pytest.approx(
            free_before + free_after, abs=5.0
        )
        full_ms = (released - taken) * 1e3  # both rows taken
        assert full_ms > 20.0
        assert c.attrs["free_row_ms"] <= c.duration_ms - full_ms + 5.0
        assert c.attrs["unseen_ms"] < 0.5 * c.duration_ms

    asyncio.run(asyncio.wait_for(go(), 240))


def test_worker_phases_land_on_one_line_of_a_profiler_trace(tmp_path):
    """A jax.profiler capture around a few segments holds the worker's
    phases as ``mcpx.worker.<phase>`` events (names from PROFILE_PHASES)
    and one ``mcpx.segment`` step event per dispatched segment with its
    seq as step_num, all on ONE line of a host plane: that line is the
    worker's (Python's thread name does not reach the profiler)."""
    import jax

    async def go():
        eng = make_engine(rows=2)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 24)
            jax.profiler.start_trace(str(tmp_path))
            try:
                spans = await traced(eng, tracer, "a few segments, profiled", 40)
            finally:
                jax.profiler.stop_trace()
        finally:
            await eng.aclose()
        return spans

    spans = asyncio.run(asyncio.wait_for(go(), 240))
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [e for e in line.events if e.name.startswith("mcpx.")]
            if events:
                lines[(plane.name, line.name)] = events
    assert len(lines) == 1, sorted(lines)
    ((plane_name, _line),) = lines
    assert plane_name.startswith("/host:")
    events = next(iter(lines.values()))
    names = {e.name for e in events}
    assert {"mcpx.worker.sync", "mcpx.worker.dispatch_submit", "mcpx.segment"} <= names
    assert {n.removeprefix("mcpx.worker.") for n in names if n != "mcpx.segment"} <= set(
        PROFILE_PHASES
    )
    # A segment's step event carries the engine.segment spans' seq, inside
    # its dispatch_submit event; the sync events lie inside harvest events.
    steps = {dict(e.stats)["step_num"] for e in events if e.name == "mcpx.segment"}
    assert {s.attrs["seq"] for s in named(spans, "engine.segment")} <= steps

    def inside(inner: str, outer: str) -> bool:
        # But for the last: the request resolves INSIDE the final harvest,
        # so the capture may stop before that harvest event has ended.
        outers = [e for e in events if e.name == outer]
        inners = sorted((e for e in events if e.name == inner), key=lambda e: e.start_ns)
        return len(inners) > 1 and all(
            any(o.start_ns <= e.start_ns and e.end_ns <= o.end_ns for o in outers)
            for e in inners[:-1]
        )

    assert inside("mcpx.segment", "mcpx.worker.dispatch_submit")
    assert inside("mcpx.worker.sync", "mcpx.worker.harvest")
