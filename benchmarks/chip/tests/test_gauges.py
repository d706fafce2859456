"""The readers of ``reader_files/gauges.py``: a value as it stands after the
window, at a dotted path of a JSON body or one labelled ``/metrics`` sample;
and the three metric files that PR 27 adds, resolved through the vocabulary."""

import json
import os

import pytest

import readers
from conftest import CHIP_DIR

COSTS = {"device": {"hbm": [
    {"device": "TPU_0", "available": True, "bytes_in_use": 7_600_000_000},
    {"device": "TPU_1", "available": True, "bytes_in_use": 7_650_000_000},
    {"device": "TPU_2", "available": True, "bytes_in_use": 7_580_000_000},
    {"device": "TPU_3", "available": True, "bytes_in_use": 7_600_000_000},
]}}
METRICS = {"mcpx_engine_weights_init_seconds": 3.25,
           'mcpx_engine_weights_bytes{device="TPU_0"}': 6.98e9}


def evidence(after):
    return readers.Evidence(gen_late_ms=[], traces=[], counters_before={}, counters_after=after,
                            device=None, memory_in_use_bytes=None)


@pytest.fixture(scope="module")
def found():
    return readers.vocabulary()


def metric_file(name):
    with open(os.path.join(CHIP_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def test_the_two_readers_are_in_the_vocabulary_beside_the_nine(found):
    assert {"endpoint_value", "endpoint_spread"} <= set(found)
    assert set(readers.READERS) < set(found)


def test_endpoint_value_reads_a_sample_and_a_dotted_path(found):
    ev = evidence({"/metrics": METRICS, "/healthz": {"engine_queue": {"weights": {"init_s": 2.5}}}})
    read = found["endpoint_value"]
    assert read(ev, endpoint="/metrics", path="mcpx_engine_weights_init_seconds") == 3.25
    assert read(ev, endpoint="/metrics", path='mcpx_engine_weights_bytes{device="TPU_0"}',
                scale=1e-9) == pytest.approx(6.98)
    assert read(ev, endpoint="/healthz", path="engine_queue.weights.init_s") == 2.5


@pytest.mark.parametrize("after,path", [
    ({}, "mcpx_engine_weights_init_seconds"),  # endpoint not fetched
    ({"/metrics": {}}, "mcpx_engine_weights_init_seconds"),  # the parent serves no such sample
    ({"/metrics": {"a": {"b": "text"}}}, "a.b"),  # not a number
    ({"/metrics": {"a": True}}, "a"),  # nor is a boolean
])
def test_endpoint_value_finds_nothing_and_does_not_raise(found, after, path):
    assert found["endpoint_value"](evidence(after), endpoint="/metrics", path=path) is None


def test_endpoint_spread_is_fullest_minus_emptiest(found):
    read = found["endpoint_spread"]
    ev = evidence({"/costs": COSTS, "/x": {"list": [3, 1.5, 2]}})
    assert read(ev, endpoint="/costs", path="device.hbm", field="bytes_in_use",
                scale=1e-9) == pytest.approx(0.07)
    assert read(ev, endpoint="/x", path="list") == 1.5
    one = evidence({"/costs": {"device": {"hbm": COSTS["device"]["hbm"][:1]}}})
    assert read(one, endpoint="/costs", path="device.hbm", field="bytes_in_use") == 0.0


@pytest.mark.parametrize("body", [
    None,  # endpoint not fetched
    {"device": {}},  # no list at the path
    {"device": {"hbm": []}},
    {"device": {"hbm": [{"device": "cpu:0", "available": False}]}},  # a backend without statistics
    {"device": {"hbm": [{"bytes_in_use": 1}, {"available": False}]}},  # one device says nothing
])
def test_endpoint_spread_finds_nothing_and_does_not_raise(found, body):
    after = {} if body is None else {"/costs": body}
    assert found["endpoint_spread"](evidence(after), endpoint="/costs", path="device.hbm",
                                    field="bytes_in_use") is None


def test_the_three_metric_files_read_through_the_vocabulary(found):
    ev = evidence({"/metrics": METRICS, "/costs": COSTS})
    ev.device = {"window_s": 3.0, "busy_s": 2.9, "ops": {
        "all-reduce.7 bf16[8,8,4096] all-reduce": 0.09, "all-gather-start.3 (tuple) all-gather-start": 0.03,
        "all-gather-done.3 bf16[4,8,2,16,128] all-gather-done": 0.03,
        "fusion.12 bf16[8,8,7168] fusion": 1.0, "reduce-window.1 f32[8] reduce-window": 0.5,
        "collective-permute.2 bf16[8] collective-permute": 0.015,
        "async-collective-start (tuple) fusion": 0.03, "async-collective-done.1 bf16[8,8,4096] fusion": 0.0,
        "constant_dynamic-slice_fusion.4 bf16[1,16,128,4096] fusion": 0.2}}
    f = metric_file("device.collective_busy_share")
    assert readers.read_metric(ev, f["reader"], f["args"], found) == pytest.approx(100 * 0.195 / 3.0)
    f = metric_file("engine.weights_init_s")
    assert readers.read_metric(ev, f["reader"], f["args"], found) == 3.25
    f = metric_file("device.hbm_spread_gb")
    assert readers.read_metric(ev, f["reader"], f["args"], found) == pytest.approx(0.07)
    # against a program that lacks what PR 27 adds: nothing, and no raise
    parent = evidence({"/metrics": {}, "/costs": {"device": {"hbm": [{"available": False}]}}})
    for name in ("device.collective_busy_share", "engine.weights_init_s", "device.hbm_spread_gb"):
        f = metric_file(name)
        assert readers.read_metric(parent, f["reader"], f["args"], found) is None
