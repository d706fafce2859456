"""Which admission-cohort sizes exist (ISSUES 55, 57): ONE table,
``engine.cohort_buckets``, read by warm-up, the registry-grammar warm and
admission alike. The automatic list gets the bucket it lacked between 1 and 8
rows on the whole-prompt route at the 128 prefill bucket and, behind a matched
prefix, at the 64 bucket; a cohort through it decodes what the
eight-row bucket decodes; nothing compiles once warm-up is over;
``mcpx_engine_prefill_slots_total`` and the ``engine.prefill`` span say which
bucket an admission took."""

import asyncio
import functools
import queue
import time

import pytest

from mcpx.core.config import MCPXConfig
from mcpx.engine.engine import InferenceEngine, cohort_buckets
from mcpx.telemetry import tracing
from mcpx.telemetry.tracing import Tracer

# What the table held before the small bucket, and holds still for whole
# prompts at every prefill bucket but 128 and for suffixes at every one but 64.
EIGHT_UP = {8: (1, 8), 16: (1, 8, 16), 32: (1, 8, 16, 32)}
FOUR_UP = {8: (1, 4, 8), 16: (1, 4, 8, 16), 32: (1, 4, 8, 16, 32)}


@pytest.mark.parametrize("suffix", [False, True], ids=["whole", "suffix"])
@pytest.mark.parametrize("T", [64, 128, 256])
@pytest.mark.parametrize("rows", [8, 16, 32])
def test_the_automatic_table(rows, T, suffix):
    """Halved down to 4 where whole prompts of 65-128 tokens land and where
    suffixes of up to 64 do, down to 8 elsewhere."""
    want = FOUR_UP if T == (64 if suffix else 128) else EIGHT_UP
    assert cohort_buckets(rows, (), T, suffix) == want[rows]


@pytest.mark.parametrize("suffix", [False, True], ids=["whole", "suffix"])
@pytest.mark.parametrize("T", [64, 128, 256])
@pytest.mark.parametrize("rows", [8, 16, 32])
def test_an_explicit_list_holds_at_every_bucket_and_route(rows, T, suffix):
    """``engine.batch_buckets`` as given, cut at the slab's rows, which
    always exist."""
    assert cohort_buckets(rows, (1, 2, 4), T, suffix) == (1, 2, 4, rows)
    assert cohort_buckets(rows, (1, 8, 64), T, suffix) == ((1, 8) if rows == 8 else (1, 8, rows))


def test_a_slab_under_eight_rows_keeps_its_two_buckets():
    for T in (64, 256):
        assert cohort_buckets(4, (), T, False) == (1, 4)
        assert cohort_buckets(2, (), T, True) == (1, 2)


def make_engine(model_cfg=None, mesh=None, **engine) -> InferenceEngine:
    cfg = MCPXConfig.from_dict(
        {
            "model": {"size": "test", "max_seq_len": 256},
            "engine": {
                "use_pallas": False,
                "max_batch_size": 8,
                "max_decode_len": 16,
                "kv_page_size": 16,
                "max_pages_per_seq": 16,
                "temperature": 0.0,
                **engine,
            },
        }
    )
    return InferenceEngine(cfg, model_cfg=model_cfg, mesh=mesh)


def test_an_engines_table_is_the_functions():
    """``_cohort_table``: every (A, T, route) the function names over the
    prefill buckets given, a suffix route only where the engine has one."""
    eng = make_engine()
    assert eng._cohort_table([64, 128, 256]) == {
        1: {64: (False, True), 128: (False, True), 256: (False, True)},
        4: {64: (True,), 128: (False,)},
        8: {64: (False, True), 128: (False, True), 256: (False, True)},
    }
    assert make_engine(prefix_cache=False)._cohort_table([128, 256]) == {
        1: {128: (False,), 256: (False,)}, 4: {128: (False,)}, 8: {128: (False,), 256: (False,)}}
    assert make_engine(batch_buckets=[1, 2])._cohort_table([128, 256]) == {
        A: {128: (False, True), 256: (False, True)} for A in (1, 2, 8)}


class _Gate:
    """Stands in for the engine's queue while a burst is gathered: takes the
    puts, and is empty to a worker that looks."""

    def __init__(self):
        self.items = []

    def put(self, item):
        self.items.append(item)

    def get(self, timeout=None):
        time.sleep(min(timeout or 0.0, 0.005))
        raise queue.Empty

    def get_nowait(self):
        raise queue.Empty

    def empty(self):
        return True

    def qsize(self):
        return 0


async def burst(eng, tracer, prompts, n_new=4):
    """``len(prompts)`` traced requests that reach the worker in ONE drain, so
    they are one admission cohort (the slab idle, rows enough): their puts are
    gathered and handed over under the queue's own lock. Returns
    [(token ids, spans)]."""

    async def one(p):
        root = tracer.start_request("/plan")
        with tracing.activate(root):
            res = await eng.generate(p, max_new_tokens=n_new)
        tracer.finish(root)
        return res.token_ids, tracer.get(root.record.trace_id).spans

    real, gate = eng._queue, _Gate()
    eng._queue = gate
    try:
        tasks = [asyncio.ensure_future(one(p)) for p in prompts]
        while len(gate.items) < len(prompts):
            await asyncio.sleep(0)
    finally:
        eng._queue = real
    with real.mutex:
        real.queue.extend(gate.items)
        real.not_empty.notify()
    return await asyncio.gather(*tasks)


def prefill_span(spans):
    (span,) = [s for s in spans if s.name == "engine.prefill"]
    return span


def compiles(eng) -> int:
    return sum(e["compiles"] for e in eng.costs.snapshot(materialize=False)["executables"].values())


def slots(eng) -> float:
    for line in eng.metrics.render().decode().splitlines():
        if line.startswith("mcpx_engine_prefill_slots_total"):
            return float(line.split()[-1])
    raise AssertionError("no mcpx_engine_prefill_slots_total")


HEAD = "a shared head of two whole pages"  # 32 tokens: a page-aligned match


def distinct(eng, n, length):
    """``n`` prompts of ``length`` tokens whose first page no other prompt of
    this file shares."""
    return [eng.tokenizer.encode(f"{length}.{n}.{i} " + "x" * length)[:length] for i in range(n)]


def behind_head(eng, n):
    return [eng.tokenizer.encode(HEAD + f" tail {chr(66 + i) * 5}") for i in range(n)]


@functools.lru_cache(maxsize=None)
def warmed() -> dict:
    """One 8-row engine warmed to the 128 bucket, then served cohorts of 1..8
    rows: whole prompts in the 64 and 128 buckets, and suffixes behind a
    resident head. What each admission took, and what compiled when."""

    async def go():
        eng = make_engine(warmup_compile=True, warmup_max_len=128)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            out = {
                "table": eng._cohort_table([64, 128]),
                "warmup": next(r for r in eng.startup.snapshot()["phases"]
                               if r["name"] == "startup.warmup"),
                "compiles": [compiles(eng)], "slots": [slots(eng)], "admissions": [],
            }
            seed = [eng.tokenizer.encode(HEAD + " seeds the tree")]
            for route, sizes, make in (("whole", range(1, 9), lambda n: distinct(eng, n, 40)),
                                       ("whole128", range(1, 9), lambda n: distinct(eng, n, 100)),
                                       ("seed", [1], lambda n: seed),
                                       ("suffix", range(1, 9), lambda n: behind_head(eng, n))):
                for n in sizes:
                    served = await burst(eng, tracer, make(n))
                    attrs = [prefill_span(spans).attrs for _, spans in served]
                    out["admissions"].append((route, n, attrs))
                    out["compiles"].append(compiles(eng))
                    out["slots"].append(slots(eng))
            return out
        finally:
            await eng.aclose()

    return asyncio.run(asyncio.wait_for(go(), 240))


def test_warm_up_compiles_the_table_and_nothing_else():
    """``startup.executables`` = the table's routes, an admit and an
    admit-merge a row bucket, the segment and the merge."""
    w = warmed()
    assert set(w["table"]) == {1, 4, 8} and w["table"][4] == {64: (True,), 128: (False,)}
    routes = sum(len(r) for shapes in w["table"].values() for r in shapes.values())
    assert routes == 4 + 2 + 4
    assert w["warmup"]["executables"] == routes + 2 * len(w["table"]) + 2 == w["compiles"][0]


def test_no_cohort_of_one_to_eight_rows_compiles_after_warm_up():
    """With a prefix hit and without, in both warmed prefill buckets."""
    w = warmed()
    assert len(w["admissions"]) == 25
    assert w["compiles"] == [w["compiles"][0]] * len(w["compiles"])


@pytest.mark.parametrize("route", ["whole", "whole128", "suffix"])
def test_the_span_says_which_bucket_the_cohort_took(route):
    """``cohort_rows`` is the cohort's size on every one of its rows' spans,
    ``cohort_bucket`` the table's bucket for it: 4 for a cohort of 2-4 whole
    prompts in the 128 bucket or suffixes in the 64 bucket behind a matched
    prefix, 8 for whole prompts in the 64 bucket."""
    seen = {n: attrs for r, n, attrs in warmed()["admissions"] if r == route}
    assert sorted(seen) == list(range(1, 9))
    for n, attrs in seen.items():
        assert [a["cohort_rows"] for a in attrs] == [n] * n
        want = 1 if n == 1 else 4 if (route != "whole" and n <= 4) else 8
        assert {a["cohort_bucket"] for a in attrs} == {want}, (route, n)
        assert all(a["prefix_hit"] == (route == "suffix") for a in attrs)


def test_the_slot_counter_counts_bucket_times_length_an_admission():
    """``mcpx_engine_prefill_slots_total``: A x T for every admission."""
    w = warmed()
    assert w["slots"][0] == 0.0
    grew = [b - a for a, b in zip(w["slots"], w["slots"][1:])]
    lengths = {"whole": 64, "whole128": 128, "seed": 64, "suffix": 64}
    want = [attrs[0]["cohort_bucket"] * lengths[route] for route, _, attrs in w["admissions"]]
    assert grew == want
    assert sum(want[:8]) == 64 * (1 + 8 * 7) and sum(want[8:16]) == 128 * (1 + 4 * 3 + 8 * 4)
    assert sum(want[17:]) == 64 * (1 + 4 * 3 + 8 * 4)


def _float32_model():
    # GQA with K=4 and float32, as test_engine's mesh test: the heads shard
    # over ``model`` and a psum's order cannot wobble the greedy argmax.
    from mcpx.models.gemma.config import GemmaConfig

    return GemmaConfig(vocab_size=384, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
                       head_dim=32, d_ff=256, dtype="float32", max_seq_len=256)


def _mesh(mesh_shape: tuple):
    import jax

    from mcpx.parallel.mesh import make_mesh

    data, model = mesh_shape
    return make_mesh(data=data, model=model, devices=jax.devices()[: data * model])


MESHES = pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])


@functools.lru_cache(maxsize=None)
def decoded(mesh_shape: tuple) -> dict:
    """Cohorts of 2, 3 and 4 rows through the table's bucket and through the
    eight-row bucket they took before it (``batch_buckets`` [1, 8])."""

    async def serve(**engine):
        eng = make_engine(model_cfg=_float32_model(), mesh=_mesh(mesh_shape), **engine)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            out = {}
            for n in (2, 3, 4):
                # 65-128 tokens: the prefill bucket at which the small bucket exists
                prompts = [eng.tokenizer.encode(f"{n}{i} plan request " + "of some length " * 5 + "JSON:")
                           for i in range(n)]
                served = await burst(eng, tracer, prompts, n_new=12)
                out[n] = ([toks for toks, _ in served],
                          [prefill_span(spans).attrs["cohort_bucket"] for _, spans in served])
            return out
        finally:
            await eng.aclose()

    async def go():
        return {"table": await serve(), "eight": await serve(batch_buckets=[1, 8])}

    return asyncio.run(asyncio.wait_for(go(), 240))


@pytest.mark.parametrize("n", [2, 3, 4])
@MESHES
def test_a_small_cohort_decodes_what_the_eight_row_bucket_decodes(mesh_shape, n):
    """Prefill rows are independent and padding rows are dropped at the
    merge: token for token the same plan through [4, T] as through [8, T]."""
    got = decoded(mesh_shape)
    toks4, buckets4 = got["table"][n]
    toks8, buckets8 = got["eight"][n]
    assert buckets4 == [4] * n and buckets8 == [8] * n
    assert toks4 == toks8 and all(len(t) > 0 for t in toks4)


# Cohorts with a row behind a matched prefix (ISSUE 57): (kind, rows). "one":
# ONE row of the cohort matched the resident head and its cohort-mates are
# whole prompts of 40 tokens, so the whole cohort goes down the suffix route
# at T 64; "all": every row matched, T 64 (the catalogue cells' case);
# "one128": the mates are whole prompts of 100 tokens, T 128 (the distinct
# cells' case, which the trim by shape leaves in 8 rows).
MIXED = [("one", 2), ("one", 3), ("one", 4), ("all", 2), ("all", 3), ("all", 4), ("all", 5),
         ("one128", 2), ("one128", 4)]
MATES = {"one": 40, "one128": 100}


def mixed_cohort(eng, kind, n):
    hits = n if kind == "all" else 1
    behind = [eng.tokenizer.encode(HEAD + f" {kind}{n}{i} tail {chr(66 + i) * 5}") for i in range(hits)]
    whole = [eng.tokenizer.encode(f"{kind}.{n}.{i} " + "y" * 100)[:MATES[kind]] for i in range(n - hits)]
    return behind + whole


@functools.lru_cache(maxsize=None)
def decoded_behind_a_prefix(mesh_shape: tuple) -> dict:
    """``MIXED`` through the table's buckets and through the eight-row bucket
    they took before (``batch_buckets`` [1, 8]), both engines warmed with
    ``warmup_compile`` on: tokens, span attributes, slots added, compiles."""

    async def serve(**engine):
        eng = make_engine(model_cfg=_float32_model(), mesh=_mesh(mesh_shape), warmup_compile=True,
                          warmup_max_len=128, **engine)
        await eng.start()
        try:
            tracer = Tracer(None, enabled=True, sample_rate=1.0)
            await burst(eng, tracer, [eng.tokenizer.encode(HEAD + " seeds the tree")])
            out = {"compiles": [compiles(eng)]}
            for kind, n in MIXED:
                before = slots(eng)
                served = await burst(eng, tracer, mixed_cohort(eng, kind, n), n_new=12)
                out[kind, n] = ([toks for toks, _ in served],
                                [prefill_span(spans).attrs for _, spans in served],
                                slots(eng) - before)
                out["compiles"].append(compiles(eng))
            return out
        finally:
            await eng.aclose()

    async def go():
        return {"table": await serve(), "eight": await serve(batch_buckets=[1, 8])}

    return asyncio.run(asyncio.wait_for(go(), 300))


@pytest.mark.parametrize("kind,n", MIXED, ids=[f"{k}-{n}" for k, n in MIXED])
@MESHES
def test_a_small_cohort_behind_a_prefix_takes_four_rows_and_decodes_the_same(mesh_shape, kind, n):
    """One matched row sends its cohort down the suffix route: at T 64 2-4
    rows take bucket 4 there (sharded over ``data`` 2 on the mesh), 5 keep 8,
    and so do 2-4 at T 128; ``A x T`` slots an admission; token for token what
    the eight-row bucket decodes."""
    got = decoded_behind_a_prefix(mesh_shape)
    toks4, attrs4, slots4 = got["table"][kind, n]
    toks8, attrs8, slots8 = got["eight"][kind, n]
    hits = n if kind == "all" else 1
    T = 128 if kind == "one128" else 64
    want = 4 if (n <= 4 and T == 64) else 8
    for attrs, bucket in ((attrs4, want), (attrs8, 8)):
        assert [a["cohort_rows"] for a in attrs] == [n] * n
        assert {a["cohort_bucket"] for a in attrs} == {bucket}
        assert [a["prefix_hit"] for a in attrs] == [True] * hits + [False] * (n - hits)
    assert (slots4, slots8) == (want * T, 8 * T)
    assert toks4 == toks8 and all(len(t) > 0 for t in toks4)


@MESHES
def test_no_cohort_behind_a_prefix_compiles_after_warm_up(mesh_shape):
    """The suffix prefill at (4, 64) is warm-up's: the compile counter is
    flat over the bursts, with the table and with a list."""
    got = decoded_behind_a_prefix(mesh_shape)
    for side in ("table", "eight"):
        assert len(got[side]["compiles"]) == 1 + len(MIXED)
        assert got[side]["compiles"] == [got[side]["compiles"][0]] * (1 + len(MIXED))
    assert got["table"]["compiles"][0] > got["eight"]["compiles"][0]
