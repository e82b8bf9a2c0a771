"""The tracing plane: layer scopes of the train step, the grouped-FFN
backward phases, host spans in memory and their clock, set-up build spans,
and the serving engine's step phases."""
import dataclasses
import glob
import os
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.models import build_model
from repro.optim.adamw import from_model_config
from repro.optim.schedules import constant
from repro.serving import ContinuousBatchingEngine
from repro.telemetry import MemorySink, TrainTelemetry, trace
from repro.telemetry.trace import named_span, trace_span
from repro.training import compile_train_step, init_train_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the disjoint layer scopes every op of the train step sits under
LAYERS = ("embed", "attn", "norm", "router", "moe", "lm_head", "train/apply")
KERNELS = ("moe_gated_in", "moe_matmul", "bip_admm")


def _layers(op_name: str):
    """Layer scopes an op's scope path passes through (the trace reduction's
    rule: a whole path component, or a transform's argument)."""
    return [s for s in LAYERS
            if re.search(r"(^|[/(])" + re.escape(s) + r"(/|\)|$)", op_name)]


def _instructions(hlo: str):
    """(opcode, op_name) of every HLO instruction that carries an op name."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.match(r"\s*(?:ROOT )?%?\S+ = .*? ([a-z][\w-]*)\(", line)
        if m and op:
            out.append((op.group(1), m.group(1)))
    return out


def _tiny_train():
    """(model, optimizer config, state, batch) of a tiny minimind-moe as the
    benchmark runs it: both Pallas kernels (interpreted here), remat='block'."""
    cfg = configs.reduced_for_smoke("minimind_moe_16e", vocab_size=256, remat="block")
    cfg = dataclasses.replace(cfg, routing=dataclasses.replace(cfg.routing, use_kernel=True))
    model = build_model(cfg)
    opt = from_model_config(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab_size)
    return model, opt, state, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


@pytest.fixture(scope="module")
def tiny_step():
    """The tiny train step in 2 microbatches: the host spans of its first
    call and its compiled HLO."""
    model, opt, state, batch = _tiny_train()
    fn = compile_train_step(model, opt, constant(1e-3), state, batch,
                            microbatches=2, donate=False)
    trace.clear()
    _, mets = fn(state, batch)
    assert np.isfinite(float(mets["loss"]))
    spans = trace.spans()
    hlo = fn.lower(state, batch).compile().as_text()
    return spans, hlo


def test_every_op_of_the_train_step_sits_under_one_layer_scope(tiny_step):
    _, hlo = tiny_step
    insts = _instructions(hlo)
    dots = [n for op, n in insts if op in ("dot", "convolution")]
    calls = [n for op, n in insts if op == "custom-call"]
    # kernel bodies (interpreted here) as the program emits them; reducer
    # sub-computations carry relative names and run inside their caller
    kernel_ops = [n for _, n in insts if n.startswith("jit(train_step)/")
                  and any(f"/{k}/" in n for k in KERNELS)]
    assert dots and kernel_ops
    for k in KERNELS:
        assert any(f"/{k}/" in n for n in kernel_ops), k
    for name in dots + calls + kernel_ops:
        assert len(_layers(name)) == 1, (name, _layers(name))
    # and every layer of the map is there
    covered = {s for _, n in insts for s in _layers(n)}
    assert covered == set(LAYERS)


def test_train_step_holds_the_backward_phase_scopes(tiny_step):
    _, hlo = tiny_step
    names = {n for _, n in _instructions(hlo)}
    for phase, kernel in (("fwd", "moe_gated_in"), ("fwd", "moe_matmul"),
                          ("bwd/remat", "moe_matmul"), ("bwd/dgrad", "moe_matmul"),
                          ("bwd/wgrad", "moe_matmul")):
        assert any(f"moe/gemm/{phase}/{kernel}/" in n for n in names), (phase, kernel)
    assert any("router/score_adjust/bip_kernel/" in n and "/bip_admm/" in n for n in names)
    assert any("router/scores/" in n for n in names)
    assert any("moe/shared/" in n for n in names)


def _pallas_calls(jaxpr, prefix=""):
    """(scope path without transform wrappers, kernel name) of every
    pallas_call in a jaxpr, nested jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        ns = str(eqn.source_info.name_stack)
        path = "/".join(p for p in (prefix, ns) if p)
        if eqn.primitive.name == "pallas_call":
            bare = re.sub(r"\w+\(|\)", "", path)
            out.append((bare.rsplit("/", 1)[0], bare.rsplit("/", 1)[1]))
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _pallas_calls(sub.jaxpr, path)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _pallas_calls(sub, path)
    return out


def test_expert_ffn_backward_calls_fall_under_their_phase():
    e, c, d, f = 2, 128, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    args = [jax.random.normal(ks[0], (e, c, d)), jax.random.normal(ks[1], (e, d, f)),
            jax.random.normal(ks[2], (e, d, f)), jax.random.normal(ks[3], (e, f, d))]

    def loss(*a):
        with named_span("moe/gemm"):
            return ops.expert_ffn(*a).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args)
    calls = sorted(_pallas_calls(jaxpr.jaxpr))
    want = sorted([("moe/gemm/fwd", "moe_gated_in"), ("moe/gemm/fwd", "moe_matmul")]
                  + [("moe/gemm/bwd/remat", "moe_matmul")] * 2       # g, u
                  + [("moe/gemm/bwd/dgrad", "moe_matmul")] * 3       # dh, dx (two)
                  + [("moe/gemm/bwd/wgrad", "moe_matmul")] * 3)      # dwd, dwg, dwu
    assert calls == want


def test_setup_spans_name_the_train_step(tiny_step):
    spans, _ = tiny_step
    mine = [s for s in spans if s.attrs.get("program") == "train_step"]
    assert {s.name for s in mine} == {"setup/trace", "setup/lower", "setup/compile"}
    for s in mine:
        assert 0 < s.end_ns - s.start_ns < 600e9
    compile_span = next(s for s in mine if s.name == "setup/compile")
    assert isinstance(compile_span.attrs["cache_hit"], bool)
    order = [s.name for s in sorted(mine, key=lambda s: s.start_ns)]
    assert order == ["setup/trace", "setup/lower", "setup/compile"]


@pytest.mark.parametrize("variant", ["guarded", "telemetry"])
def test_every_train_step_variant_builds_as_train_step(variant):
    model, opt, state, batch = _tiny_train()
    if variant == "guarded":
        fn = compile_train_step(model, opt, constant(1e-3), state, batch,
                                donate=False, guarded=True)
        trace.clear()
        fn.lower(state, batch, np.zeros((3,), np.float32))
        built = {(s.name, s.attrs["program"]) for s in trace.spans()}
        assert {("setup/trace", "train_step"), ("setup/lower", "train_step")} <= built
        assert not any(p == "guarded_step" for _, p in built)
    else:
        fn = compile_train_step(model, opt, constant(1e-3), state, batch, donate=False,
                                telemetry=TrainTelemetry(sink=MemorySink(), flush_every=2))
    assert fn.__name__ == "train_step"


def test_trace_span_records_parent_attrs_and_drops_oldest(monkeypatch):
    trace.clear()
    with trace_span("outer/a", step=1) as handle:
        with trace_span("inner/b"):
            pass
    assert handle is None  # no attribute dict to change after the fact
    inner, outer = trace.spans()
    assert (inner.name, inner.parent) == ("inner/b", "outer/a")
    assert (outer.name, outer.parent, outer.attrs) == ("outer/a", None, {"step": 1})
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns

    # a thread starts with no open span: parents never leak across threads
    def in_thread():
        with trace_span("t/d"):
            pass

    with trace_span("outer/c"):
        t = threading.Thread(target=in_thread)
        t.start()
        t.join()
    assert [s.parent for s in trace.spans() if s.name == "t/d"] == [None]

    monkeypatch.setattr(trace, "_buffer", trace.collections.deque(maxlen=4))
    monkeypatch.setattr(trace, "MAX_SPANS", 4)
    trace.clear()
    for i in range(6):
        with trace_span("x/y", i=i):
            pass
    assert [s.attrs["i"] for s in trace.spans()] == [2, 3, 4, 5]
    assert trace.dropped() == 2
    trace.clear()
    assert trace.spans() == [] and trace.dropped() == 0


def _profile_start_ns(path: str) -> int:
    """`profile_start_time` of the trace's task-environment plane: the
    realtime clock reading the host plane's line times count from."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        import xplane
    finally:
        sys.path.pop(0)
    with open(path, "rb") as fh:
        buf = fh.read()
    for field, plane in xplane._fields(buf):
        if field != 1:
            continue
        stat_names, stats = {}, []
        for pf, pv in xplane._fields(plane):
            if pf == 5:  # stat_metadata map entries
                for ef, ev in xplane._fields(pv):
                    if ef == 2:
                        meta = dict(xplane._fields(ev))
                        stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
            elif pf == 6:
                stats.append(pv)
        for raw in stats:
            name, value = xplane._stat(raw, stat_names)
            if name == "profile_start_time":
                return int(value)
    raise AssertionError("trace has no profile_start_time")


def test_trace_span_shares_the_profiler_host_clock(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        import xplane
    finally:
        sys.path.pop(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace_span("clock/warm"):
            pass
        with trace_span("clock/probe", k=1):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = [e for p in xplane.read_planes(path) if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name.startswith("clock/probe")]
    assert len(host) == 1
    start_ns = _profile_start_ns(path) + host[0].start_ps / 1000
    probe = [s for s in trace.spans() if s.name == "clock/probe"][-1]
    assert abs(probe.start_ns - start_ns) < 50_000
    assert abs((probe.end_ns - probe.start_ns) - host[0].dur_ps / 1000) < 50_000


def test_serving_step_phases_are_ordered_host_spans():
    cfg = configs.reduced_for_smoke("minimind_moe_16e", vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ContinuousBatchingEngine(model, params, n_slots=2, chunk_size=4, max_seq_len=32)
    for _ in range(3):
        eng.submit(rng.integers(0, 128, (6,)), 3, ignore_eos=True)
    trace.clear()
    with trace_span("test/drive"):
        for _ in range(4):
            eng.step()
    phases = ["serve/admit", "serve/plan", "serve/dispatch", "serve/fetch", "serve/observe"]
    got = [s for s in trace.spans() if s.name.startswith("serve/")]
    assert [s.name for s in got] == phases * 4
    for i in range(4):
        step = got[5 * i:5 * i + 5]
        assert {s.parent for s in step} == {"test/drive"}
        for a, b in zip(step, step[1:]):
            assert a.end_ns <= b.start_ns
        # admission and planning open before the plan's counts exist
        assert [s.attrs for s in step[:2]] == [{"step": i}] * 2
        counts = {(s.attrs["step"], s.attrs["n_prefill"], s.attrs["n_decode"])
                  for s in step[2:]}
        assert len(counts) == 1 and counts.pop()[0] == i
    # the first step prefills both slots' first chunks; decode follows
    assert (got[2].attrs["n_prefill"], got[2].attrs["n_decode"]) == (8, 0)
    assert sum(s.attrs["n_decode"] for s in got[2::5]) > 0
