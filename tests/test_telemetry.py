"""Telemetry subsystem tests (DESIGN.md §Observability).

The load-bearing invariant is TRANSPARENCY: enabling the full telemetry
pipeline (in-graph MetricStream buffer threaded through the jit'd step,
async drain, sinks) must leave the TrainState trajectory bitwise identical
— including the hardest configuration (guarded step + BIP forecaster +
global-sync duals). Everything telemetry records is a value the step
already computed; the buffer is write-only and feeds nothing back.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.synthetic import SyntheticBatchStream
from repro.models import build_model
from repro.robustness.guards import GuardConfig
from repro.telemetry import (
    CSVSink,
    JSONLSink,
    MemorySink,
    MetricStream,
    ServingTelemetry,
    StreamingHistogram,
    TrainTelemetry,
    open_sink,
    profile_window,
)
from repro.training.loop import train_loop

N_STEPS = 8


@pytest.fixture(scope="module")
def moe():
    cfg = configs.reduced_for_smoke("minimind_moe_16e", vocab_size=256)
    return cfg, build_model(cfg)


@pytest.fixture(scope="module")
def hard_moe():
    # the transparency worst case: guarded step + forecaster + global-sync
    base = configs.reduced_for_smoke("minimind_moe_16e", vocab_size=256)
    cfg = dataclasses.replace(
        base,
        routing=dataclasses.replace(base.routing, sync="global", forecast=True),
    )
    return cfg, build_model(cfg)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _bitwise_equal(a, b) -> bool:
    la, lb = _leaves(a), _leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(x, y, equal_nan=True) for x, y in zip(la, lb)
    )


def _train(fixture, **kw):
    cfg, model = fixture
    kw.setdefault("batches", SyntheticBatchStream(cfg, 4, 32, N_STEPS))
    kw.setdefault("total_steps", N_STEPS)
    return train_loop(model, kw.pop("batches"), lr=1e-3, log_every=0, **kw)


# ------------------------------------------------------------ transparency


def test_telemetry_transparent_bitwise(hard_moe):
    """Guarded + forecast + global-sync run: MetricStream on vs off gives
    bitwise-identical TrainState trajectories."""
    guard = GuardConfig(policy="skip")
    s_plain, _ = _train(hard_moe, guard=guard)
    sink = MemorySink()
    tel = TrainTelemetry(sink=sink, flush_every=3)  # non-divisor: partial window
    s_tel, _ = _train(hard_moe, guard=guard, telemetry=tel)
    assert _bitwise_equal(s_plain, s_tel)
    steps = sorted(r["step"] for r in sink.records if r["kind"] == "train_step")
    assert steps == list(range(N_STEPS))  # drain lost nothing, dupes none


def test_telemetry_records_well_formed(moe, tmp_path):
    cfg, _ = moe
    path = str(tmp_path / "train.jsonl")
    sink = JSONLSink(path)
    tel = TrainTelemetry(sink=sink, flush_every=4, run_meta={"arch": cfg.name})
    _train(moe, telemetry=tel)
    sink.close()
    records = [json.loads(line) for line in open(path)]  # every line parses
    assert records[0]["kind"] == "run_meta"
    steps = [r for r in records if r["kind"] == "train_step"]
    assert len(steps) == N_STEPS
    n_layers = sum(1 for _, ffn in cfg.layer_kinds() if ffn == "moe")
    tokens_routed = 4 * 32 * cfg.routing.top_k  # batch x seq x k, per layer
    for r in steps:
        assert {"step", "step_time", "ce_loss", "load_per_layer",
                "max_vio_per_layer"} <= set(r)
        load = np.asarray(r["load_per_layer"])
        assert load.shape == (n_layers, cfg.routing.n_experts)
        # integer counts end-to-end: every token lands on exactly k experts
        assert load.dtype.kind in "iu" or np.all(load == load.astype(np.int64))
        assert load.sum() == n_layers * tokens_routed


# ------------------------------------------------------------- dtype audit


def test_expert_load_integer_counts():
    from repro.core.metrics import expert_load

    idx = jnp.asarray([[0, 1], [1, 2], [3, 3]], jnp.int32)
    load = expert_load(idx, 4)
    assert jnp.issubdtype(load.dtype, jnp.integer)
    assert load.tolist() == [1, 2, 1, 2]
    # the sentinel used by masked dispatch is dropped, not wrapped
    masked = jnp.asarray([[0, 4], [4, 4]], jnp.int32)
    assert expert_load(masked, 4).tolist() == [1, 0, 0, 0]


def test_metric_stream_rejects_float_load():
    shapes = {
        "load": jax.ShapeDtypeStruct((8,), jnp.float32),
        "loss": jax.ShapeDtypeStruct((), jnp.float32),
    }
    with pytest.raises(AssertionError, match="integer counts"):
        MetricStream.build(shapes, 4)
    ok = MetricStream.build(
        {"load": jax.ShapeDtypeStruct((8,), jnp.int32)}, 4
    )
    assert ok.layout["load"][1] == jnp.dtype(jnp.int32)


def test_metric_stream_ring_buffer_slots():
    stream = MetricStream({"x": ((), jnp.dtype(jnp.float32))}, 3)
    buf = stream.init_buffer()
    assert buf["_step"].tolist() == [-1, -1, -1]
    for i in range(4):  # wraps: slot 0 overwritten by step 3
        buf = stream.accumulate(
            buf, {"x": jnp.asarray(float(i))}, jnp.asarray(i, jnp.int32)
        )
    assert buf["_step"].tolist() == [3, 1, 2]
    assert buf["x"].tolist() == [3.0, 1.0, 2.0]


# ------------------------------------------------------------------- sinks


def test_sinks_roundtrip(tmp_path):
    rec = {"kind": "train_step", "step": 1, "v": np.float32(2.5),
           "arr": np.arange(3, dtype=np.int32)}
    jpath = str(tmp_path / "a.jsonl")
    with JSONLSink(jpath) as s:
        s.emit(rec)
    got = json.loads(open(jpath).read().strip())
    assert got["v"] == 2.5 and got["arr"] == [0, 1, 2]

    cpath = str(tmp_path / "b.csv")
    with CSVSink(cpath) as s:
        s.emit(rec)
        s.emit({"kind": "event", "step": 2, "what": "x"})
    files = sorted(p.name for p in tmp_path.glob("b.*.csv"))
    assert files == ["b.event.csv", "b.train_step.csv"]

    assert isinstance(open_sink(str(tmp_path / "c.csv")), CSVSink)
    assert isinstance(open_sink(str(tmp_path / "c.jsonl")), JSONLSink)
    assert open_sink(None) is None


# ------------------------------------------------------------------ tracing


def test_profile_window_parse():
    assert profile_window("3:10") == (3, 10)
    with pytest.raises(ValueError):
        profile_window("10:3")
    with pytest.raises(ValueError):
        profile_window("abc")


# ------------------------------------------------------------- serving SLO


def test_streaming_histogram_quantiles():
    h = StreamingHistogram()
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-2.0, sigma=1.0, size=20000)
    for x in xs:
        h.add(x)
    assert h.n == len(xs)
    for p in (0.5, 0.9, 0.99):
        true = np.quantile(xs, p)
        assert abs(h.quantile(p) - true) / true < 0.05
    assert abs(h.mean - xs.mean()) / xs.mean() < 1e-6
    h.add(float("nan"))
    h.add(-1.0)
    assert h.n == len(xs)  # non-finite / negative ignored
    d = h.to_dict()
    assert d["n"] == len(xs) and sum(d["bucket_count"]) == len(xs)


def test_serving_telemetry_slo_plane(moe):
    cfg, model = moe
    from repro.serving.engine import ContinuousBatchingEngine

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    sink = MemorySink()
    eng = ContinuousBatchingEngine(
        model, model.init(jax.random.PRNGKey(0)),
        n_slots=2, chunk_size=8, max_seq_len=64, clock=clk, sink=sink,
    )
    reqs = [eng.submit([1, 2, 3, 4, 5], 4, ignore_eos=True) for _ in range(3)]
    assert all(r is not None for r in reqs)
    while eng.scheduler.has_work:
        eng.step()
        clk.t += 0.5
    tel = eng.telemetry
    assert tel.n_finished == 3 and tel.ttft.n == 3 and tel.itl.n == 3
    # fake clock: prefill completes on the first step a slot runs, so the
    # admitted pair sees ttft 0.0 is impossible — submit precedes the step
    # by at least one 0.5s tick for the queued third request
    assert tel.ttft.quantile(0.99) >= 0.5 - 1e-9
    lifecycle = [r for r in sink.records if r["kind"] == "serve_request"]
    assert len(lifecycle) == 3
    assert all(r["finish_reason"] == "max_new_tokens" for r in lifecycle)
    summary = eng.telemetry.emit_summary()
    assert summary["n_finished"] == 3
    assert summary["decode_tokens"] == eng.decode_tokens
    assert sink.records[-1]["kind"] == "serve_summary"
    # engine counters are read-only views over telemetry
    assert eng.n_steps == tel.n_steps
    tel.reset()
    assert eng.n_steps == 0 and tel.ttft.n == 0


def test_serving_telemetry_counts_drops(moe):
    cfg, model = moe
    from repro.serving.engine import ContinuousBatchingEngine

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    eng = ContinuousBatchingEngine(
        model, model.init(jax.random.PRNGKey(0)),
        n_slots=1, chunk_size=8, max_seq_len=64,
        queue_timeout=1.0, clock=clk,
    )
    eng.submit([1, 2, 3], 30, ignore_eos=True)  # hogs the slot
    waiter = eng.submit([4, 5, 6], 4, ignore_eos=True)
    for _ in range(4):
        eng.step()
        clk.t += 1.0
    assert waiter.finish_reason == "timeout"
    # pre-existing counter contract: timeouts count as shed, not deadline
    assert eng.telemetry.n_shed == 1
    assert eng.telemetry.n_deadline_missed == 0
    # the timed-out waiter was never admitted: no queue-wait sample, no ttft
    assert eng.telemetry.queue_wait.n == 0
    assert eng.telemetry.ttft.n == 0
    assert eng.telemetry.n_finished == 1  # outcome still reported once


# --------------------------------------------------------------- TrainLog


def test_trainlog_step_time_quantiles(moe):
    _, log = _train(moe)
    s = log.summary()
    times = np.asarray(log.step_times[2:])
    assert s["step_time_p50"] == pytest.approx(np.percentile(times, 50))
    assert s["step_time_p99"] == pytest.approx(np.percentile(times, 99))
    assert s["mean_step_time"] == pytest.approx(times.mean())
    assert len(log.losses) == N_STEPS
    log.truncate(3)
    assert len(log.losses) == 3 and len(log.max_vio_steps) == 3


# ---------------------------------------------------------- metrics report


def test_metrics_report_summarize(moe, tmp_path):
    from repro.telemetry import metrics_report

    path = str(tmp_path / "run.jsonl")
    sink = JSONLSink(path)
    tel = TrainTelemetry(sink=sink, flush_every=4, run_meta={"arch": "x"})
    _train(moe, telemetry=tel)
    sink.close()
    records = metrics_report.load_records(path)
    summary = metrics_report.summarize(records)
    assert summary["n_steps"] == N_STEPS
    assert summary["final_loss"] is not None
    assert len(summary["AvgMaxVio_per_layer"]) >= 1
    assert np.all(np.asarray(summary["load_total_per_layer"]) > 0)
    html = str(tmp_path / "report.html")
    assert metrics_report.main([path, "--html", html]) == 0
    assert "load" in open(html).read()


def test_metrics_report_dedups_replayed_steps():
    from repro.telemetry.metrics_report import dedup_steps

    recs = [
        {"kind": "train_step", "step": 0, "ce_loss": 1.0},
        {"kind": "train_step", "step": 1, "ce_loss": 9.9},
        {"kind": "train_step", "step": 1, "ce_loss": 0.9},  # replay wins
    ]
    out = dedup_steps(recs)
    assert [r["step"] for r in out] == [0, 1]
    assert out[1]["ce_loss"] == 0.9


# ------------------------------------------------------------ bench harness


def test_bench_run_unknown_benchmark_lists_registry(capsys):
    from benchmarks import run as bench_run

    with pytest.raises(SystemExit) as exc:
        bench_run.main(["definitely_not_a_bench"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "unknown benchmark" in err
    assert "serve_throughput" in err and "paper_repro" in err
