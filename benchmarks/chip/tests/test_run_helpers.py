import pytest

import run

NAMES = {"auth-fetch-0000", "user-fetch-0001"}


def body(nodes, edges, origin="llm"):
    return {"origin": origin, "graph": {"nodes": nodes, "edges": edges}}


def node(name, service=None):
    return {"name": name, "service": service or name}


@pytest.mark.parametrize("status,b,ok", [
    (200, body([node("auth-fetch-0000"), node("user-fetch-0001")], [{"from": "auth-fetch-0000", "to": "user-fetch-0001"}]), True),
    (200, body([node("a", "auth-fetch-0000")], []), True),
    (503, {"error": "busy"}, False),
    (0, {"error": "timeout"}, False),
    (200, body([node("auth-fetch-0000")], [], origin="heuristic"), False),
    (200, body([], []), False),
    (200, body([node("ghost-0009")], []), False),
    (200, body([node("auth-fetch-0000")], [{"from": "auth-fetch-0000", "to": "nowhere"}]), False),
    (200, body([node("auth-fetch-0000"), node("user-fetch-0001")],
               [{"from": "auth-fetch-0000", "to": "user-fetch-0001"}, {"from": "user-fetch-0001", "to": "auth-fetch-0000"}]), False),
    (200, body([node("auth-fetch-0000"), node("auth-fetch-0000")], []), False),
    (200, {"origin": "llm"}, False),
])
def test_plan_validity_rules(status, b, ok):
    assert (run.plan_problem(status, b, NAMES, "llm") == "") is ok


def test_prom_total_sums_labelled_samples():
    text = ("# HELP mcpx_engine_compiles_total x\n"
            'mcpx_engine_compiles_total{executable="admit"} 4.0\n'
            'mcpx_engine_compiles_total{executable="segment"} 2.0\n'
            "mcpx_engine_compiles_total_created 1.7e9\n"
            "mcpx_engine_resets_total 0.0\n")
    assert run.prom_total(text, "mcpx_engine_compiles_total") == 6.0
    assert run.prom_total(text, "mcpx_engine_resets_total") == 0.0
    assert run.prom_total(text, "absent_total") == 0.0


EXPOSITION = ("# HELP mcpx_engine_compiles_total x\n"
              "# TYPE mcpx_engine_compiles_total counter\n"
              'mcpx_engine_compiles_total{executable="admit"} 4.0\n'
              'mcpx_engine_compiles_total{executable="segment",bucket="8 x 128"} 2.0\n'
              'mcpx_latency_bucket{le="0.5"} 7\n'
              "mcpx_engine_resets_total 0.0\n"
              "\n"
              "not a sample\n")


def test_the_metrics_endpoint_parses_to_one_value_a_labelled_sample():
    assert run.prom_samples(EXPOSITION) == {
        'mcpx_engine_compiles_total{executable="admit"}': 4.0,
        'mcpx_engine_compiles_total{executable="segment",bucket="8 x 128"}': 2.0,
        'mcpx_latency_bucket{le="0.5"}': 7.0,
        "mcpx_engine_resets_total": 0.0,
    }
    assert run.prom_samples("") == {}


class FakeClient:
    def __init__(self, answers):
        self.answers, self.asked = answers, []

    def request(self, method, path, body=None):
        self.asked.append((method, path))
        return self.answers.get(path, (404, {"error": "no such route"}, {}))


def metric(name, reader, **args):
    import spec

    return spec.Metric(name=name, unit="%", better="higher", source="program_counter",
                       reader=reader, args=args)


def test_counters_are_fetched_where_the_cells_metric_files_say():
    import dataclasses

    import spec
    from conftest import REPO

    cell = spec.load_cell("olmo2-1b.distinct-closed", REPO)
    assert run.counter_endpoints(cell) == ["/cache"]  # today: the same one request as before
    wider = dataclasses.replace(cell, per_layer=cell.per_layer + (
        metric("a", "counter_delta_ratio", endpoint="/metrics", num=["x"], den=["y"]),
        metric("b", "a_reader_of_some_file", endpoint="/healthz"),
        metric("c", "counter_delta_ratio", endpoint="/gone", num=["x"], den=["y"]),
        metric("d", "counter_delta_ratio", endpoint="/cache", num=["x"], den=["y"])))
    endpoints = run.counter_endpoints(wider)
    assert endpoints == ["/cache", "/gone", "/healthz", "/metrics"]
    ctl = FakeClient({
        "/cache": (200, {"plan_cache": {"hits": 3}}, {"Content-Type": "application/json; charset=utf-8"}),
        "/healthz": (200, {"engine_queue": {"depth": 2}}, {"Content-Type": "application/json"}),
        "/metrics": (200, {"error": EXPOSITION[:300], "text": EXPOSITION},
                     {"Content-Type": "text/plain; charset=utf-8"}),
    })
    got = run.fetch_counters(ctl, endpoints)
    assert [p for _, p in ctl.asked] == endpoints and set(got) == {"/cache", "/healthz", "/metrics"}
    assert got["/cache"] == {"plan_cache": {"hits": 3}} and got["/healthz"]["engine_queue"]["depth"] == 2
    assert got["/metrics"]['mcpx_engine_compiles_total{executable="admit"}'] == 4.0


def problems(pallas_paths, kernel_paths, **over):
    kw = dict(failed=[], n_good=50, drained=True, edges=(0.1, 0.5), ref={"ok": True}, resets=0,
              compiles=(17, 17), platform="tpu", rehearsal=False,
              pallas={"enabled": True, "interpret": False, "paths": pallas_paths},
              kernel_paths=kernel_paths)
    return run.correctness_problems(**{**kw, **over})


def test_correct_requires_the_kernel_paths_the_block_module_names():
    engaged = {"decode": {"engaged": True, "dispatches": 440}, "prefill": {"engaged": True, "dispatches": 0}}
    gemma_paths = {"decode": 1, "prefill": 0}
    assert problems(engaged, gemma_paths) == []
    assert any("'prefill' not engaged" in p for p in problems(
        {**engaged, "prefill": {"engaged": False, "reason": "off"}}, gemma_paths))
    assert any("'decode'" in p and "fewer than 1" in p for p in problems(
        {**engaged, "decode": {"engaged": True, "dispatches": 0}}, gemma_paths))
    # another block, another kernel path: nothing here names it
    assert any("'scan' not engaged" in p for p in problems(engaged, {"scan": 1}))
    assert problems({**engaged, "scan": {"engaged": True, "dispatches": 3}}, {"scan": 1, "decode": 1}) == []
