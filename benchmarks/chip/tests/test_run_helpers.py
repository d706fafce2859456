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
