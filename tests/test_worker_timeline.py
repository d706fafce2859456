"""The engine worker's timeline as the traces carry it (ISSUE 24): the
per-segment device period and host phases on ``engine.segment`` spans, why
a request waited on ``engine.queue_wait``, and the worker's phases as
``mcpx.worker.<phase>`` events in the profiler's own trace. CPU,
``model=test``, the ragged kernel interpreted."""

import asyncio
import functools
import glob
import math

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.engine.engine import InferenceEngine
from mcpx.telemetry import tracing
from mcpx.telemetry.flight import PROFILE_PHASES, SEGMENT_PARTS
from mcpx.telemetry.tracing import Tracer
from tests.test_hold_dispatch import HoldsTillDone

ROW_FORWARDS = ("row_forwards", "row_forwards_live", "row_forwards_done",
                "row_forwards_empty")
TIMELINE = ("seq", "prefill_rows", "hold_joined_rows", "period_ms", "sync_ms",
            "idle_ms", "hold_ms", "host_ms", *SEGMENT_PARTS, "ready_gap_ms",
            "starved_ms", *ROW_FORWARDS)
PLACEMENT = ("first_seq", "last_seq", "segments", "live_forwards", "ridden_forwards",
             "missed_dispatches", "admit_host_ms", "behind_ms")


def make_engine(rows: int, **sections) -> InferenceEngine:
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
                **sections,
            }
        )
    )


async def traced(eng, tracer, text: str, n: int, constrained: bool = False) -> list:
    """One traced generate of exactly ``n`` tokens' budget, unconstrained
    unless asked otherwise; returns the request's spans."""
    root = tracer.start_request("/plan")
    with tracing.activate(root):
        await eng.generate(
            eng.tokenizer.encode(text), max_new_tokens=n, constrained=constrained,
            temperature=0.0,
        )
    tracer.finish(root)
    return tracer.get(root.record.trace_id).spans


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


def assert_row_forwards_add_up(a: dict, rows: int) -> None:
    """The integer identity on one engine.segment span's attributes."""
    assert a["row_forwards"] == rows * a["forwards"]
    assert a["row_forwards"] == sum(a[k] for k in ROW_FORWARDS[1:])
    assert min(a[k] for k in ROW_FORWARDS) >= 0


@pytest.mark.parametrize("held", [False, True], ids=["own-estimate", "held"])
def test_segment_spans_carry_the_segments_timeline_and_it_tiles(held):
    """Every engine.segment span of one harvest carries the same timeline
    (seq, prefill_rows, hold_joined_rows, period_ms, sync_ms, idle_ms,
    hold_ms, host_ms and its parts, ready_gap_ms, starved_ms, the
    row-forwards by state), and host_ms + sync_ms + idle_ms + hold_ms is
    the wall between two consecutive ready stamps: ``ready_gap_ms``, cut at
    the worker's own stamps. Against the spans' clock the stamps are held
    by ORDER alone: until ISSUE 40 the sum was compared, at 2% or 5 ms,
    with the difference of two span ends, which the worker stamps a few
    statements after the ready stamp, and on a loaded machine one end in a
    hundred came 10 ms late. Once with the pacer's own estimate (on a CPU
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
        # the gathered requests' prefills, each counted on one segment
        assert sum(by_seq[q][0].attrs["prefill_rows"] for q in seqs) == 3
        # A ready stamp is the first one plus the gaps since. On the spans'
        # clock it lies before its own span's end (stamped a few statements
        # later) and after the start of the span behind it (the worker
        # dispatches the next segment, THEN fetches): one first stamp has
        # to fit every such bracket, however late a loaded machine ran.
        since_first, earliest, latest = 0.0, -math.inf, by_seq[seqs[0]][0].t1
        for prev, cur in zip(seqs, seqs[1:]):
            a = by_seq[cur][0].attrs
            parts = sum(a[k] for k in SEGMENT_PARTS)
            assert 0.0 <= parts <= a["host_ms"] + 0.01
            assert 0.0 <= a["sync_ms"] and 0.0 <= a["idle_ms"]
            assert 0.0 <= a["hold_ms"]
            assert 0 <= a["hold_joined_rows"] <= a["prefill_rows"]
            window = a["host_ms"] + a["sync_ms"] + a["idle_ms"] + a["hold_ms"]
            assert window == pytest.approx(a["ready_gap_ms"], abs=0.01)
            # Dispatched before the previous segment was ready or after:
            # either way the period ends at this ready stamp and starts no
            # earlier than the previous one; and what the device waited
            # for its first work lies before that start.
            assert 0.0 < a["period_ms"] <= a["ready_gap_ms"] + 0.001
            assert 0.0 <= a["starved_ms"] <= a["ready_gap_ms"] - a["period_ms"] + 0.002
            earliest = max(earliest, by_seq[cur][0].t0 - since_first)
            since_first += a["ready_gap_ms"] / 1e3
            latest = min(latest, by_seq[cur][0].t1 - since_first)
        assert earliest <= latest + 1e-5
        # Pipelined, two deep: every segment but the first of a burst was
        # on the device's queue before the one ahead of it was ready.
        assert all(by_seq[q][0].attrs["starved_ms"] == 0.0 for q in seqs[1:])
        for q in seqs:
            assert_row_forwards_add_up(by_seq[q][0].attrs, rows=4)
        if held:
            # Three rows of four taken: segments behind the first were
            # held, so the worker waited in ``hold`` while the device
            # decoded, and the tiling above counted that wait.
            assert sum(by_seq[q][0].attrs["hold_ms"] for q in seqs[1:]) > 0.0
            assert eng.queue_stats()["worker_profile"]["phases"]["hold"]["count"] > 0

    asyncio.run(asyncio.wait_for(go(), 240))


@functools.lru_cache(maxsize=None)
def placed_plans() -> dict:
    """One engine of 4 rows, served once for the tests below: three traced
    unconstrained requests at once (one token a live forward: no grammar,
    so nothing forced and no draft), then one traced constrained one (the
    prompt draft and the grammar's forced tokens ride along: more than one
    token a forward). Returns each request's spans by kind."""

    async def go():
        eng = make_engine(rows=4)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await traced(eng, tracer, "warm the shapes", 40)
            plain = await asyncio.gather(
                traced(eng, tracer, "first request of three", 56),
                traced(eng, tracer, "the second request", 40),
                traced(eng, tracer, "a third", 24),
            )
            drafted = await traced(eng, tracer, "plan: compose. JSON:", 48, constrained=True)
        finally:
            await eng.aclose()
        return {"plain": plain, "drafted": [drafted]}

    return asyncio.run(asyncio.wait_for(go(), 240))


def one(spans: list, name: str):
    (span,) = named(spans, name)
    return span


@pytest.mark.parametrize("kind", ["plain", "drafted"])
def test_row_forwards_by_state_add_up_on_every_segment(kind):
    """live + done + empty == rows x forwards, exactly, on every
    engine.segment span; a row is live in no more forwards than ran."""
    segments = [s for spans in placed_plans()[kind] for s in named(spans, "engine.segment")]
    assert segments
    for s in segments:
        a = s.attrs
        assert_row_forwards_add_up(a, rows=4)
        assert 0 < a["live_forwards"] <= a["forwards"]  # this row's own
        assert a["live_forwards"] <= a["row_forwards_live"]  # the slab's


@pytest.mark.parametrize("kind", ["plain", "drafted"])
def test_a_plans_live_forwards_are_its_rows_counter_summed_over_its_segments(kind):
    """engine.decode's placement: first_seq..last_seq are the segments
    whose spans the row wrote, none skipped; live_forwards is the sum of
    the row's device counter over them and ridden_forwards of their
    forwards. With no grammar (nothing forced, no draft) and greedy decode
    a live forward is one token; with the prompt draft on it is at least
    one."""
    for spans in placed_plans()[kind]:
        d = one(spans, "engine.decode").attrs
        assert set(PLACEMENT) <= set(d)
        segs = sorted(named(spans, "engine.segment"), key=lambda s: s.attrs["seq"])
        assert [s.attrs["seq"] for s in segs] == list(range(d["first_seq"], d["last_seq"] + 1))
        assert d["segments"] == len(segs)
        assert d["live_forwards"] == sum(s.attrs["live_forwards"] for s in segs)
        assert d["ridden_forwards"] == sum(s.attrs["forwards"] for s in segs)
        # (the admission's first sample counts in the first segment's delta)
        assert d["tokens"] == sum(s.attrs["tokens"] for s in segs)
        assert d["missed_dispatches"] >= 0
        if kind == "plain":
            assert d["tokens"] == d["live_forwards"]
        else:
            assert d["tokens"] > d["live_forwards"] > 0


@pytest.mark.parametrize("kind", ["plain", "drafted"])
def test_a_plans_wall_tiles_against_the_ready_stamps(kind):
    """queue wait + the admission on the host + behind_ms + the period_ms
    of segments first_seq..last_seq + deliver_ms tile engine.generate's
    duration: every piece is cut at the worker's own stamps (enqueue,
    admission's start and end, the device's start of first_seq, the ready
    stamps) but the span's two ends, a few statements outside them."""
    for spans in placed_plans()[kind]:
        gen, wait, d = (one(spans, n) for n in ("engine.generate", "engine.queue_wait", "engine.decode"))
        periods = [s.attrs["period_ms"] for s in named(spans, "engine.segment")]
        assert len(periods) == d.attrs["segments"]
        assert wait.attrs["since_ready_ms"] > 0.0  # the warm request's last harvest
        pieces = (wait.duration_ms, d.attrs["admit_host_ms"], d.attrs["behind_ms"],
                  sum(periods), gen.attrs["deliver_ms"])
        assert min(pieces) >= 0.0
        assert sum(pieces) <= gen.duration_ms + 0.05  # they lie inside the span
        assert sum(pieces) == pytest.approx(gen.duration_ms, rel=0.02, abs=5.0)


def test_the_gathered_plans_leave_in_the_order_of_their_budgets():
    """What repeats of three requests sent at once. Whether one 3 ms gather
    window caught all three (one first segment, no dispatch missed) is the
    host's scheduling, which a loaded machine decides: not asserted."""
    plain = [one(spans, "engine.decode").attrs for spans in placed_plans()["plain"]]
    assert all(d["missed_dispatches"] >= 0 for d in plain)
    # budgets of 56, 40 and 24 tokens: the shorter plans left earlier
    assert plain[0]["last_seq"] > plain[1]["last_seq"] > plain[2]["last_seq"]
    assert plain[0]["live_forwards"] == 56 and plain[2]["live_forwards"] == 24


def test_row_forwards_are_counted_with_tracing_off():
    """``mcpx_engine_row_forwards_total{state}`` moves with no tracer, no
    profiler and no span: three increments a harvest, from the vector the
    harvest's one fetch brings back anyway."""

    async def go():
        eng = make_engine(rows=4, tracing={"enabled": False})
        await eng.start()
        try:
            assert eng._profiler is None
            res = await eng.generate(
                eng.tokenizer.encode("no span rides this one"), max_new_tokens=24,
                constrained=False, temperature=0.0,
            )
        finally:
            await eng.aclose()
        return res, eng.metrics.registry.get_sample_value

    res, sample = asyncio.run(asyncio.wait_for(go(), 240))
    by_state = {s: sample("mcpx_engine_row_forwards_total", {"state": s})
                for s in ("live", "done", "empty")}
    assert by_state["live"] == res.generated_tokens == 24
    assert by_state["empty"] >= 3 * by_state["live"]  # three of four rows held nothing
    assert sum(by_state.values()) == 4 * sample("mcpx_engine_decode_forwards_total")


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
        E._segment_timeline(1, 2, 1, 0.0, 1.0, 0.5, dict.fromkeys(PROFILE_PHASES, 0.0), 0.6)
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
