"""The trace reduction on a small trace recorded on one v5e chip.

fixtures/toy.xplane.pb: three calls of a jitted toy (a `router/select`
scope: scores, softmax, top-k; a `moe/gemm` scope: two matmuls), each
followed by a 2 ms host sleep, inside one `bench/window` annotation."""
import os

import pytest

import trace_reduce
import xplane

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "toy.xplane.pb")


def test_planes_and_scope_paths():
    planes = xplane.read_planes(TOY)
    dev = [p for p in planes if p.name == "/device:TPU:0"][0]
    ops = [e for line in dev.lines if line.name == "XLA Ops" for e in line.events]
    assert len(ops) == 33
    scopes = {str(e.stats.get("tf_op", "")) for e in ops}
    assert any("router/select" in s for s in scopes)
    assert any("moe/gemm" in s for s in scopes)
    host = [e.name for p in planes if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    assert "bench/window" in host


def test_reduction():
    r = trace_reduce.reduce_trace(TOY, scopes=["router", "moe/gemm", "moe/dispatch"])
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(9.9697e-3, rel=1e-3)
    # three module runs of ~19.3 us each, all inside the window
    assert r["busy_s"] == pytest.approx(3 * 19.3e-6, rel=0.06)
    assert r["scope_s"]["moe/gemm"] > r["scope_s"]["router"] > 0
    assert r["scope_s"]["moe/dispatch"] == 0
    assert r["scope_s"]["moe/gemm"] + r["scope_s"]["router"] <= r["busy_s"]
    assert sum(v for _, v in r["idle_gaps"]) + r["busy_s"] == pytest.approx(r["window_s"],
                                                                           rel=1e-6)
    assert r["idle_gaps"][0][0] == "$time sleep"
    assert r["device_ops"][0][0] == "moe/gemm/nf,df->nd/dot_general"


def test_self_time_counts_nested_ops_once():
    E = xplane.Event
    outer, inner1, inner2, after = (E("while", 0, 100, {}), E("a", 10, 20, {}),
                                    E("b", 40, 30, {}), E("c", 120, 5, {}))
    got = {e.name: t for e, t in trace_reduce._self_times([inner2, outer, after, inner1])}
    assert got == {"while": 50, "a": 20, "b": 30, "c": 5}
    assert trace_reduce._union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
