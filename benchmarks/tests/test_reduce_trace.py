"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small xplane recorded on a TPU v5e (PR 25: the toy
training cell, a tenth of a second of steps)."""
import os

import pytest

from benchmarks import reduce_trace as rt

RECORDED = os.path.join(os.path.dirname(__file__), "recorded.xplane.pb")


def test_union_and_subtract():
    assert rt.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert rt.union_seconds([]) == 0.0
    c = rt.merged([(0, 2), (5, 6)])
    assert rt.subtract_seconds(c, rt.merged([(1, 5.5)])) == pytest.approx(1.5)
    assert rt.subtract_seconds(c, []) == pytest.approx(3.0)


def test_reduce_made_up_planes():
    planes = [{
        "chip": 0,
        "modules": [("jit_step(17)", 0.0, 1.0), ("jit_step(17)", 2.0, 3.0)],
        "ops": [("%fusion.1 = f32[] fusion()", 0.0, 0.4),
                ("%all-reduce.2 = f32[] all-reduce()", 0.3, 0.9),
                ("%fusion.1 = f32[] fusion()", 2.0, 2.5)],
    }]
    r = rt.reduce_planes(planes, 1)
    assert r["busy_s"] == pytest.approx(1.4)
    assert r["window_s"] == pytest.approx(2.5)
    assert r["collective_s"] == pytest.approx(0.6)
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["top_ops"][0] == ["fusion", pytest.approx(0.9)]
    assert r["modules"]["jit_step"][1] == (2.0, 3.0, pytest.approx(0.5))
    (name, gap), = r["idle_gaps"]
    assert name == "after jit_step / before jit_step"
    assert gap == pytest.approx(1.1)


def test_recorded_trace_from_the_chip():
    r = rt.reduce(RECORDED, chips=1)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["top_ops"] and r["top_ops"][0][1] > 0
    steps = [k for k in r["modules"] if "step" in k]
    assert steps, sorted(r["modules"])
    # every dispatch's busy time lies inside the dispatch
    for s, e, busy in r["modules"][steps[0]]:
        assert 0 <= busy <= (e - s) * 1.0001
    assert r["collective_s"] == 0.0
