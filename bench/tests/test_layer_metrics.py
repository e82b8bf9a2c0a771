"""The readers of the layer-map metrics: attention's share, the expert FFN
backward's roofline share, the unscoped share and the train step's build
time, on the recorded toy trace and on hand-made records."""
import json
import os

import pytest

import trace_reduce
from run import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(BENCH, "tests", "fixtures", "toy.xplane.pb")


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _rec(scope_s, busy_s=1.0, n_devices=1):
    return {"trace": {"busy_s": busy_s, "window_s": busy_s, "n_devices": n_devices,
                      "scope_s": scope_s}}


def test_unscoped_share_on_the_toy_trace():
    unscoped = _reader("unscoped_share.train")
    tr = trace_reduce.reduce_trace(TOY, scopes=unscoped.SCOPES)
    # the toy's ops sit under router/select and moe/gemm, disjointly
    assert tr["scope_s"]["router"] > 0 and tr["scope_s"]["moe"] > 0
    assert sum(tr["scope_s"].values()) <= tr["busy_s"] * tr["n_devices"]
    # no attention scope: a program without the layer map reads nothing
    assert unscoped.read({"trace": tr}) is None
    # with the map, the rest of busy time is the unscoped share
    tr["scope_s"]["attn"] = 0.25 * tr["busy_s"]
    tr["scope_s"]["router"] = tr["scope_s"]["moe"] = 0.25 * tr["busy_s"]
    assert unscoped.read({"trace": tr}) == pytest.approx(25.0)


def test_unscoped_share_sums_every_layer_scope():
    unscoped = _reader("unscoped_share.train")
    shares = dict(zip(unscoped.SCOPES, (0.01, 0.20, 0.04, 0.15, 0.45, 0.03, 0.02)))
    got = unscoped.read(_rec({s: 2 * v for s, v in shares.items()}, busy_s=1.0, n_devices=2))
    assert got == pytest.approx(10.0)


def test_attention_share():
    attn = _reader("attention_share.train")
    assert attn.SCOPES == ("attn",)
    assert attn.read(_rec({"attn": 0.3}, busy_s=2.0)) == pytest.approx(15.0)
    assert attn.read(_rec({"attn": 0.0})) is None
    assert attn.read({}) is None


def test_expert_ffn_bwd_roofline_at_train_16e():
    """The hand count: 1.77e11 FLOP a forward call, 0.90 ms at
    197 TFLOP/s; 8 layers x 2 microbatches x 3 steps at twice that is 86 ms,
    against the dgrads' 0.355 s and the wgrads' 0.406 s (the remat's 0.292 s
    is not backward work): 11.3%."""
    bwd = _reader("expert_ffn_bwd_roofline.train")
    assert bwd.SCOPES == ("moe/gemm/bwd/dgrad", "moe/gemm/bwd/wgrad")
    rec = _rec({"moe/gemm/bwd/dgrad": 0.355, "moe/gemm/bwd/wgrad": 0.406})
    rec.update(config=_json("configs", "minimind-moe-16e.json"),
               traffic=_json("traffic", "train_8k.json"),
               device_kind="TPU v5 lite", steps_traced=3)
    t_least = 2 * (2.0 * 16 * 2560 * 512 * 1408 * 3) / 197e12 * 8 * 2 * 3
    assert bwd.read(rec) == pytest.approx(100 * t_least / 0.761)
    assert bwd.read(rec) == pytest.approx(11.3, abs=0.05)
    # the recompute is not in the denominator
    rec["trace"]["scope_s"]["moe/gemm/bwd/remat"] = 0.292
    assert bwd.read(rec) == pytest.approx(100 * t_least / 0.761)
    rec["trace"]["scope_s"].update({"moe/gemm/bwd/dgrad": 0.0, "moe/gemm/bwd/wgrad": 0.0})
    assert bwd.read(rec) is None
    assert bwd.read({}) is None


def test_step_build_s_sums_the_train_step_build_spans(monkeypatch):
    from repro.telemetry import trace

    build = _reader("step_build_s.train")
    trace.clear()
    S = trace.Span
    for span in (S("setup/trace", 0, 2_000_000_000, None, {"program": "train_step"}),
                 S("setup/trace", 100, 200, None, {"program": "bip_dual_update"}),
                 S("setup/lower", 2_000_000_000, 3_500_000_000, None, {"program": "train_step"}),
                 S("setup/compile", 3_500_000_000, 4_000_000_000, None,
                   {"program": "train_step", "cache_hit": True}),
                 S("serve/fetch", 0, 9_000_000_000, None, {"program": "train_step"})):
        trace._record(span)
    assert build.read({}) == pytest.approx(4.0)
    trace.clear()
    # a program that records spans but not the train step's build: loud
    trace._record(S("setup/trace", 100, 200, None, {"program": "guarded_step"}))
    with pytest.raises(RuntimeError, match="train_step"):
        build.read({})
    trace.clear()
    # a program whose trace module keeps no spans (the parent's) reads nothing
    monkeypatch.delattr(trace, "spans")
    assert build.read({}) is None
