import json
import os

import pytest

import readers
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_reduction_on_the_recorded_trace():
    """``recorded_trace.json``: 120 ms of a v5e profile of the served path
    (PR 23, chip session 1). The numbers below were read once from it and
    pin the reduction: a change that moves them changed the yardstick."""
    rec = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    r = xplane.reduce_device(rec["planes"])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.119363043, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.119361461, rel=1e-9)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-9)  # self times tile busy
    top = dict(r["device_ops"])
    assert top["copy.85 bf16[16,16,257,16,128] copy"] == pytest.approx(0.027816326, rel=1e-6)
    assert top["ragged_paged_attention.10 bf16[8,1,16,1,128] custom-call"] == pytest.approx(0.004658962, rel=1e-6)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(g[1] < 1e-6 for g in r["idle_gaps"])  # the device never waited a microsecond
    ev = readers.Evidence([], [], {}, {}, r, None)
    assert readers.device_op_share(ev, "(^| )copy(\\.|$| )") == pytest.approx(90.19341103761906)
    assert readers.device_op_share(ev, "ragged_paged_attention") == pytest.approx(3.903186348893602)
    assert readers.device_idle_share(ev) == pytest.approx(100 * (1 - 0.119361461 / 0.119363043))


def test_nested_ops_count_self_time_and_gaps_are_named():
    planes = {
        "/device:TPU:0": [
            ["%while.1 = (s32[], bf16[8]) while((s32[]) %t), body=%b", 0.0, 100.0],
            ["%copy.2 = bf16[4,4]{1,0} copy(bf16[4,4] %x)", 10.0, 30.0],
            ["%fusion.3 = bf16[8]{0} fusion(bf16[8] %y)", 50.0, 40.0],
            ["%copy.2 = bf16[4,4]{1,0} copy(bf16[4,4] %x)", 150.0, 50.0],
        ],
        "/device:TPU:1": [["%copy.2 = bf16[4,4]{1,0} copy(bf16[4,4] %x)", 0.0, 200.0]],
        "/device:TPU:2": [],  # a chip the run did not use
    }
    r = xplane.reduce_device(planes)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((150 + 200) / 2 * 1e-9)  # mean over the chips used
    assert r["ops"]["while.1 (tuple) while"] == pytest.approx(30 / 2 * 1e-9)
    assert r["ops"]["copy.2 bf16[4,4] copy"] == pytest.approx((30 + 50 + 200) / 2 * 1e-9)
    assert r["idle_gaps"][0] == ["after while.1 (tuple) while", pytest.approx(50e-9)]
    assert xplane.reduce_device({})["busy_s"] == 0.0


def test_idle_at_the_profiled_slices_edges_counts():
    """The window is the profiled slice's wall where that is longer than the
    span from the first op to the last: a device that sat idle at an edge of
    the slice reads idle, not busy."""
    planes = {"/device:TPU:0": [["%copy.2 = bf16[4]{0} copy(bf16[4] %x)", 0.0, 6e9]]}
    r = xplane.reduce_device(planes, wall_s=8.0)
    assert r["window_s"] == pytest.approx(8.0) and r["busy_s"] == pytest.approx(6.0)
    assert r["idle_gaps"][0][1] == pytest.approx(2.0) and "edges" in r["idle_gaps"][0][0]
    assert readers.device_idle_share(readers.Evidence([], [], {}, {}, r, None)) == pytest.approx(25.0)
    tight = xplane.reduce_device(planes, wall_s=5.9)  # the trace outlasts the host's wall
    assert tight["window_s"] == pytest.approx(6.0) and tight["idle_gaps"] == []


@pytest.mark.parametrize("name,label", [
    ("%copy.176 = bf16[16,16,257,16,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[16,16,4112,128] %p)",
     "copy.176 bf16[16,16,257,16,128] copy"),
    ("%ragged_paged_attention.10 = bf16[8,1,16,1,128]{4,3,2,1,0:T(2,128)(2,1)S(1)} custom-call(bf16[8] %q)",
     "ragged_paged_attention.10 bf16[8,1,16,1,128] custom-call"),
    ("%while.44 = (s32[]{:T(128)}, bf16[8,1,2048]{2,0,1:T(8,128)(2,1)S(1)}) while((s32[]) %t)", "while.44 (tuple) while"),
    ("%not_reduce_fusion.1 = pred[]{:T(512)} fusion(pred[] %a)", "not_reduce_fusion.1 pred[] fusion"),
    ("jit_segment", "jit_segment"),
])
def test_op_labels(name, label):
    assert xplane.op_label(name) == label
