import itertools
import threading
import time

import pytest

import loadgen

DISTINCT = {"loop": "closed", "clients": 8, "intents": "distinct", "block": 16}
REPEAT = {**DISTINCT, "intents": "repeat", "repeat_share": 0.7}
SESSION = {**DISTINCT, "intents": "session", "families": 8}
PACED = {"loop": "paced", "intents": "distinct", "rate_per_s": 4.0, "block": 16}


def first(gen, n):
    return list(itertools.islice(gen.draws(), n))


def test_generator_is_a_pure_function_of_traffic_and_seed():
    big = 2**31 + 12345  # the driver's seeds are large
    a, b = loadgen.Generator(REPEAT, big), loadgen.Generator(REPEAT, big)
    assert first(a, 300) == first(b, 300)
    assert a.registry == b.registry and len(a.registry) == 1000
    assert first(a, 300) != first(loadgen.Generator(REPEAT, big + 1), 300)
    p = loadgen.Generator(PACED, big)
    assert p.schedule(200) == loadgen.Generator(PACED, big).schedule(200)


def test_seed_deals_the_same_work_in_another_order():
    a, b = loadgen.Generator(DISTINCT, 1), loadgen.Generator(DISTINCT, 2**31 + 2)
    ra, rb = [a.fresh(j) for j in range(200)], [b.fresh(j) for j in range(200)]
    assert len(set(ra)) == 200 and ra != rb
    # block by block the same intents, each block in an order of the seed's own:
    # after n requests two seeds differ by at most the last, partial block
    for k in range(0, 192, 16):
        assert sorted(ra[k:k + 16]) == sorted(rb[k:k + 16]) and ra[k:k + 16] != rb[k:k + 16]
        assert sorted(ra[k:k + 16]) == sorted(a.item(i) for i in range(k, k + 16))
    # the stream is the traffic file's (pool_seed), never the seed's
    assert a.registry == b.registry and a.item(5) == b.item(5)
    assert loadgen.Generator({**DISTINCT, "pool_seed": 22}, 1).item(5) != a.item(5)
    # paced: the gaps of whole blocks sum the same under every seed
    pa, pb = loadgen.Generator(PACED, 1), loadgen.Generator(PACED, 2)
    assert pa.schedule(64)[-1] == pytest.approx(pb.schedule(64)[-1])
    assert pa.schedule(64) != pb.schedule(64)
    assert 64 / pa.schedule(64)[-1] == pytest.approx(4.0, rel=0.35)  # about rate_per_s


def test_repeat_share_and_session_families():
    draws = first(loadgen.Generator(REPEAT, 3), 4000)
    share = sum(not d.fresh for d in draws) / len(draws)
    assert 0.67 < share < 0.73
    assert all(0.0 <= d.pick < 1.0 for d in draws)
    assert all(d.fresh for d in first(loadgen.Generator(DISTINCT, 3), 100))
    g = loadgen.Generator(SESSION, 3)
    pool = [g.item(i) for i in range(48)]
    assert len({s.split(" for case ")[0] for s in pool}) == 8 and len(set(pool)) == 48
    d = loadgen.Generator(DISTINCT, 3)
    assert len({d.item(i).split(" for case ")[0] for i in range(48)}) > 40


def test_registry_matches_the_program_generator():
    """The copy must stay draw-for-draw what ``mcpx.utils.synth`` makes
    (tokenizer and grammar tables were fitted to it)."""
    from conftest import REPO
    import sys

    sys.path.insert(0, REPO)
    from mcpx.utils.synth import synth_registry

    theirs = [r.to_dict() for r in synth_registry(50, seed=7, local=False)]
    ours = loadgen.build_registry(50, 7)
    for a, b in zip(ours, theirs):
        assert {k: b[k] for k in a} == a


@pytest.mark.parametrize("bad", [
    {"loop": "spiral", "intents": "distinct", "clients": 1},
    {"loop": "closed", "intents": "novel", "clients": 1},
    {"loop": "closed", "intents": "distinct"},
    {"loop": "paced", "intents": "distinct"},
    {"loop": "closed", "intents": "repeat", "clients": 1},
    {"loop": "closed", "intents": "session", "clients": 1},
    {"loop": "closed", "intents": "distinct", "clients": 1, "surprise": 1},
    {"loop": "closed", "intents": "distinct", "clients": 1, "block": 0},
])
def test_bad_traffic_files_are_refused(bad):
    with pytest.raises(ValueError):
        loadgen.load_traffic(bad)


def fake_server(latency_s, log):
    def factory():
        def post(intent):
            log.append((time.monotonic(), intent))
            time.sleep(latency_s)
            return True, "", ""
        return post
    return factory


def test_closed_loop_keeps_n_in_flight_and_resends_only_answered_intents():
    seen, answered, violations, in_flight, peak = set(), set(), [], [0], [0]
    lock = threading.Lock()

    def factory():
        def post(intent):
            with lock:
                if intent in seen and intent not in answered:
                    violations.append(intent)
                seen.add(intent)
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            time.sleep(0.01)
            with lock:
                answered.add(intent)
                in_flight[0] -= 1
            return True, "", ""
        return post

    loop = loadgen.Loop(loadgen.Generator(REPEAT, 4), factory, clients=4)
    loop.start()
    time.sleep(0.5)
    assert loop.stop(2.0)
    samples = loop.snapshot()
    assert len(samples) > 60 and peak[0] == 4 and not violations
    assert 0.5 < sum(not s.fresh for s in samples) / len(samples) < 0.85
    assert loop.fresh_done == sum(s.fresh for s in samples)
    assert all(s.gen_late_ms < 50 for s in samples)


def test_paced_loop_times_from_the_due_instant_and_reports_lateness(monkeypatch):
    log = []
    gen = loadgen.Generator({**PACED, "rate_per_s": 200.0}, 1)
    monkeypatch.setattr(loadgen, "MAX_INFLIGHT", 2)  # 2 senders cannot keep 200/s
    loop = loadgen.Loop(gen, fake_server(0.03, log), clients=0)
    loop.start()
    time.sleep(0.6)
    assert loop.stop(2.0)
    samples = sorted(loop.snapshot(), key=lambda s: s.t_due)
    dues = [s.t_due - samples[0].t_due for s in samples]
    want = gen.schedule(len(samples))
    assert dues == pytest.approx([t - want[0] for t in want], abs=1e-6)  # due on the seeded schedule
    assert samples[-1].gen_late_ms > 100  # the generator fell behind, and says so
    assert samples[-1].latency_ms >= samples[-1].gen_late_ms + 25  # latency counts the wait
