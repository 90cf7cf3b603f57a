"""The trace reduction, on a small trace recorded on a TPU v5e
(``record_trace_fixture.py``) and on hand-made intervals."""
import os

import pytest

from chipbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb")


def test_union_merges_and_clips():
    got = trace._union([(0, 2, "a"), (1, 3, "b"), (5, 6, "a"), (-4, -1, "c")],
                       0.5, 5.5)
    assert got == [(0.5, 3), (5, 5.5)]


def test_reduce_hand_made():
    data = {"devices": {"/device:TPU:0": {
                "modules": [(10.0, 20.0, "p"), (15.0, 30.0, "q"),
                            (60.0, 70.0, "p")],
                "ops": [(12.0, 21.0, "%b"), (25.0, 30.0, "%a"),
                        (120.0, 130.0, "%c")]}},
            "spans": [(0.0, 100.0, "chipbench.request"),
                      (35.0, 50.0, "chipbench.pause")]}
    r = trace.reduce(data, "chipbench.request")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["program_s"] == pytest.approx({"p": 20e-9, "q": 15e-9})
    # idle summed by label, longest first: the gap before the second p is
    # cut where the pause starts and ends
    assert r["idle_gaps"] == [
        ["chipbench.request | before window end", pytest.approx(30e-9)],
        ["chipbench.request | before p", pytest.approx(25e-9)],
        ["chipbench.pause | before p", pytest.approx(15e-9)]]
    assert r["device_ops"] == [["p/%b", pytest.approx(9e-9)],
                               ["q/%a", pytest.approx(5e-9)]]
    assert trace.reduce(data, "chipbench.other") is None


def test_module_and_op_names():
    assert trace.module_name("jit__geo_chunk(13194475611461467480)") == \
        "jit__geo_chunk"
    assert trace.op_name("%fusion.7 = f32[3]{0} fusion(...)") == "%fusion.7"


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no fixture")
def test_reduce_recorded_trace():
    r = trace.reduce(trace.extract(FIXTURE), "chipbench.request")
    assert r is not None and r["devices"] == 1
    assert set(r["program_s"]) == {"jit_toy"}
    # three executions inside a span that also sleeps 2 x 50 ms
    assert 0 < r["busy_s"] < r["window_s"] - 0.1
    idle = dict(r["idle_gaps"])
    assert idle["chipbench.pause | before jit_toy"] >= 0.045
    assert idle["chipbench.request | before jit_toy"] >= 0.045
    assert r["device_ops"] and all(k.startswith("jit_toy/%")
                                   for k, _ in r["device_ops"])
