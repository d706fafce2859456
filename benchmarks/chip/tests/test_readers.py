import pytest

import readers


def trace(started_at, spans):
    return {"started_at": started_at, "tree": spans}


def sp(sid, parent, name, start, dur, **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name, "start_ms": start,
            "duration_ms": dur, **({"attrs": attrs} if attrs else {})}


def evidence():
    # two plans sharing segment A (start 1000.1 s), one of them also in segment B
    t1 = trace(1000.0, [
        sp("r1", None, "/plan", 0, 400),
        sp("p1", "r1", "plan", 1, 398),
        sp("g1", "p1", "engine.generate", 5, 390, prompt_tokens=40),
        sp("q1", "g1", "engine.queue_wait", 5, 50),
        sp("f1", "g1", "engine.prefill", 55, 45, prefix_matched_tokens=16),
        sp("s1", "g1", "engine.segment", 100, 200, tokens=12, forwards=16),
        sp("s1b", "g1", "engine.segment", 300, 90, tokens=3, forwards=8),
    ])
    t2 = trace(1000.05, [
        sp("r2", None, "/plan", 0, 260),
        sp("p2", "r2", "plan", 2, 256),
        sp("g2", "p2", "engine.generate", 4, 250, prompt_tokens=30),
        sp("q2", "g2", "engine.queue_wait", 4, 10),
        sp("f2", "g2", "engine.prefill", 14, 30, prefix_matched_tokens=0),
        sp("s2", "g2", "engine.segment", 50.4, 200, tokens=16, forwards=16),
    ])
    t3 = trace(1000.2, [sp("r3", None, "/plan", 0, 4), sp("p3", "r3", "plan", 0.5, 3, cache="hit")])
    return readers.Evidence(
        gen_late_ms=[0.1, 0.2, 0.3, 5.0],
        traces=[t1, t2, t3],
        counters_before={"/cache": {"plan_cache": {"hits": 10, "misses": 30}}},
        counters_after={"/cache": {"plan_cache": {"hits": 80, "misses": 60}}},
        device={"busy_s": 7.5, "window_s": 8.0,
                "ops": {"copy.1 bf16[4,4] copy": 4.0, "copy.2 bf16[8] copy": 2.0,
                        "_ragged_paged_attention.12 bf16[8] custom-call": 0.8, "fusion.3 bf16[8] fusion": 0.7}},
        memory_in_use_bytes=4_850_000_000,
    )


def read(reader, **args):
    return readers.read_metric(evidence(), reader, args)


def test_each_reader_on_a_small_recorded_run():
    assert read("client_quantile", q=0.5) == pytest.approx(0.25)
    assert read("span_self_quantile", name="@root", q=0.5) == pytest.approx(2.0)  # 2, 4, 1 -> median 2
    assert read("span_self_quantile", name="plan", q=0.5) == pytest.approx(6.0)  # 8, 6, 3
    assert read("span_self_quantile", name="engine.queue_wait", q=0.5) == pytest.approx(30.0)
    assert read("span_cluster_size", name="engine.segment") == pytest.approx(1.5)  # 3 row-spans, 2 segments
    assert read("span_attr_ratio", name="engine.segment", num="tokens", den="forwards",
                num_per="span", den_per="segment") == pytest.approx(31 / 24)
    assert read("span_attr_ratio", name="engine.segment", num="@duration_ms", den="forwards",
                num_per="segment", den_per="segment") == pytest.approx(290 / 24)
    assert read("trace_attr_mean", terms=[["engine.generate", "prompt_tokens", 1],
                                          ["engine.prefill", "prefix_matched_tokens", -1]]) == pytest.approx(27.0)
    assert read("counter_delta_ratio", endpoint="/cache", num=["plan_cache.hits"],
                den=["plan_cache.hits", "plan_cache.misses"], scale=100.0) == pytest.approx(70.0)
    assert read("device_op_share", regex="(^| )copy(\\.|$| )") == pytest.approx(75.0)
    assert read("device_op_share", regex="ragged_paged_attention") == pytest.approx(10.0)
    assert read("device_idle_share") == pytest.approx(6.25)
    assert read("memory_in_use") == pytest.approx(4.85)
    assert readers.histogram(evidence(), "engine.segment", "tokens") == {3: 1, 12: 1, 16: 1}


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = readers.Evidence([], [], {}, {}, None, None)
    for name, args in [
        ("client_quantile", {"q": 0.99}), ("span_self_quantile", {"name": "plan", "q": 0.5}),
        ("span_cluster_size", {"name": "engine.segment"}),
        ("span_attr_ratio", {"name": "engine.segment", "num": "tokens", "den": "forwards"}),
        ("trace_attr_mean", {"terms": [["engine.generate", "prompt_tokens", 1]]}),
        ("counter_delta_ratio", {"endpoint": "/cache", "num": ["a"], "den": ["a"]}),
        ("device_op_share", {"regex": "copy"}), ("device_idle_share", {}), ("memory_in_use", {}),
    ]:
        assert readers.read_metric(empty, name, args) is None
    with pytest.raises(KeyError):
        readers.read_metric(empty, "no_such_reader", {})


def test_every_committed_metric_file_names_a_known_reader():
    import spec
    from conftest import REPO

    for w in spec.load_benchmark(REPO)["workloads"]:
        for m in spec.load_cell(w["name"], REPO).per_layer:
            assert m.reader in readers.vocabulary()
            readers.read_metric(evidence(), m.reader, m.args)  # arguments fit the reader


# ------------------------------------------------- readers are found, not listed
NEW_READER = ("import readers\n"
              "from stats import quantile\n"
              "def _helper(ev): return list(ev.gen_late_ms)\n"
              "def late_spread(ev, lo, hi):\n"
              "    xs = _helper(ev)\n"
              "    return quantile(xs, hi) - quantile(xs, lo) if xs else None\n")


def test_a_reader_file_is_found_by_name_and_read_like_the_built_in_ones(tmp_path):
    (tmp_path / "spread.py").write_text(NEW_READER)
    (tmp_path / "notes.txt").write_text("not a reader file")
    found = readers.vocabulary(str(tmp_path))
    assert set(found) == set(readers.READERS) | {"late_spread"}  # no helper, no imported function
    assert readers.read_metric(evidence(), "late_spread", {"lo": 0.0, "hi": 1.0}, found) == pytest.approx(4.9)
    assert readers.read_metric(readers.Evidence([], [], {}, {}, None, None), "late_spread",
                               {"lo": 0.0, "hi": 1.0}, found) is None
    assert readers.read_metric(evidence(), "client_quantile", {"q": 0.5}, found) == pytest.approx(0.25)
    assert set(readers.vocabulary(str(tmp_path / "absent"))) == set(readers.READERS)


def test_a_reader_defined_twice_and_an_unknown_reader_are_errors(tmp_path):
    (tmp_path / "a.py").write_text("def late_spread(ev): return 1.0\n")
    (tmp_path / "b.py").write_text("def late_spread(ev): return 2.0\n")
    with pytest.raises(ValueError, match="late_spread.*a.py.*b.py"):
        readers.vocabulary(str(tmp_path))
    (tmp_path / "b.py").write_text("def client_quantile(ev, q): return 2.0\n")
    with pytest.raises(ValueError, match="client_quantile.*readers.py.*b.py"):
        readers.vocabulary(str(tmp_path))
    (tmp_path / "b.py").unlink()
    with pytest.raises(KeyError, match="no_such.*late_spread.*reader_files"):
        readers.read_metric(evidence(), "no_such", {}, readers.vocabulary(str(tmp_path)))


def test_a_counter_path_reads_one_labelled_sample_of_the_metrics_endpoint():
    ev = readers.Evidence(
        [], [],
        {"/metrics": {'compiles_total{executable="admit"}': 4.0, 'compiles_total{executable="seg"}': 2.0,
                      "requests_total": 10.0},
         "/healthz": {"engine_queue": {"admitted": 5, "dispatched": 3}}},
        {"/metrics": {'compiles_total{executable="admit"}': 7.0, 'compiles_total{executable="seg"}': 3.0,
                      "requests_total": 30.0},
         "/healthz": {"engine_queue": {"admitted": 45, "dispatched": 13}}},
        None, None)
    assert readers.read_metric(ev, "counter_delta_ratio", {
        "endpoint": "/metrics", "num": ['compiles_total{executable="admit"}'],
        "den": ["requests_total"]}) == pytest.approx(3 / 20)
    assert readers.read_metric(ev, "counter_delta_ratio", {
        "endpoint": "/healthz", "num": ["engine_queue.admitted"],
        "den": ["engine_queue.dispatched"]}) == pytest.approx(4.0)
    assert readers.read_metric(ev, "counter_delta_ratio", {
        "endpoint": "/metrics", "num": ['compiles_total{executable="other"}'], "den": ["requests_total"]}) is None
    assert readers.read_metric(ev, "counter_delta_ratio", {
        "endpoint": "/costs", "num": ["a"], "den": ["a"]}) is None
