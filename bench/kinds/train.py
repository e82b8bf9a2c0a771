"""Training cells: the jitted step of `repro.training.loop.compile_train_step`,
as `train_loop` builds it, driven back to back over a ring of batches.

Set-up builds one TrainState from the benchmark's seeded weights, compiles
the step, and drives it through the first `check_steps` steps on distinct
batches; those steps are what `correct` compares with the plain reference.
The same step and state then run the measured window. `--trace 1` adds a
short profiled stretch after the window for the per-layer metrics.
"""
from __future__ import annotations

import collections
import math
import os
import shutil
import sys
import time

import numpy as np

IN_FLIGHT = 2  # steps queued ahead of the one the host waits on


def _gap(prog, ref):
    """Worst leaf: |program - reference| over the larger of the reference
    leaf's norm and the median leaf's."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return math.inf
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, np.median(ref))))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers `correct` is decided by (PERF.md gives their limits)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) if np.all(np.isfinite(lp)) else math.inf
    raw = np.asarray(ref["grad1_raw"])
    moved = raw >= 1e-3 * np.median(raw)  # leaves Adam moves by more than round-off
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": _gap(prog["grad1"], ref["grad1"]),
        "change_norm_gap": _gap(np.asarray(prog["change"])[moved],
                                np.asarray(ref["change"])[moved]),
    }


class Trainer:
    """The system under test for one cell: the compiled step and its feed."""

    def __init__(self, spec):
        import jax

        import counts
        import program
        from run import generator

        from repro.models import build_model
        from repro.optim import adamw as program_adamw

        self.spec, self.mix, self.cfg = spec, spec.traffic, spec.config
        self.ref = counts.reference(self.cfg)
        mcfg = program.model_config(self.cfg, **self.mix["program"])
        self.model = build_model(mcfg)
        program.check_params(self.model, self.ref.param_shapes(self.cfg))
        opt = self.mix["optimizer"]
        self.opt_cfg = program_adamw.from_model_config(
            mcfg, b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"])
        cfg, mix = self.cfg, self.mix
        self.init = jax.jit(lambda k: self.ref.init_params(cfg, k))
        gen = generator(mix)
        self.make_ring = jax.jit(lambda k: gen.make(mix, cfg["vocab_size"], k))
        self.adam_init = jax.jit(program_adamw.adamw_init, static_argnums=1)
        self.norms = jax.jit(self.ref.leaf_norms)
        self.change = jax.jit(lambda p, k: self.ref.leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, self.ref.init_params(cfg, k))))
        self.step = None

    def start(self, seed: int):
        """(TrainState, batches, weight key) for `seed`; compiles the step
        on the first call."""
        import jax

        from repro.optim.schedules import linear_warmup_cosine
        from repro.training import loop
        from run import seed_key

        key = seed_key(seed)
        k_w, k_data = jax.random.split(key)
        params = self.init(k_w)
        ring = self.make_ring(k_data)
        batches = [{"tokens": ring[i, :, :-1], "labels": ring[i, :, 1:]}
                   for i in range(self.mix["ring"])]
        state = loop.TrainState(params=params, opt_state=self.adam_init(params, self.opt_cfg),
                                router_states=self.model.init_router_states())
        if self.step is None:
            opt = self.mix["optimizer"]
            self.step = loop.compile_train_step(
                self.model, self.opt_cfg,
                linear_warmup_cosine(opt["lr"], opt["warmup_steps"], opt["total_steps"]),
                state, batches[0], microbatches=self.mix["microbatches"],
                # train_loop's default key: a seed-derived key would be baked
                # into the program and miss the compile cache on every seed
                rng=jax.random.fold_in(jax.random.PRNGKey(0), 0x5eed))
        return state, batches, k_w

    def check_steps(self, state, batches, k_w):
        """The first steps through the window's own call, and what the
        comparison reads of them: losses, step 1's gradient as the optimizer
        took it (Adam's first moment / (1 - b1)), the change after them."""
        b1 = self.mix["optimizer"]["b1"]
        prog = {"losses": []}
        for i in range(self.mix["check_steps"]):
            state, mets = self.step(state, batches[i])
            prog["losses"].append(float(mets["loss"]))
            if i == 0:
                prog["grad1"] = [float(v) / (1.0 - b1) for v in self.norms(state.opt_state["mu"])]
        prog["change"] = [float(v) for v in self.change(state.params, k_w)]
        return state, prog


def run(ctx) -> dict:
    import jax

    import counts
    from run import memory_peak, metric_scopes

    spec, mix, cfg = ctx.spec, ctx.spec.traffic, ctx.spec.config
    tr = Trainer(spec)
    state, batches, k_w = tr.start(ctx.seed)
    state, prog = tr.check_steps(state, batches, k_w)
    step, i = tr.step, mix["check_steps"]

    # measured window: the host waits on the loss of the step IN_FLIGHT steps
    # back, so a host stall shorter than that many steps leaves the chip busy
    from run import COMPILES

    compiles0 = COMPILES[0]
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    n, failed, pending = 0, 0, collections.deque()
    while True:
        state, mets = step(state, batches[i % mix["ring"]])
        i += 1
        n += 1
        pending.append(mets["loss"])
        if len(pending) > IN_FLIGHT and not math.isfinite(float(pending.popleft())):
            failed += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    failed += sum(not math.isfinite(float(loss)) for loss in pending)
    elapsed = time.perf_counter() - t0
    print(f"window: {n} steps in {elapsed:.6f} s, {COMPILES[0] - compiles0} programs built "
          f"inside it", file=sys.stderr)
    tokens_per_s = n * mix["batch"] * mix["seq_len"] / elapsed

    record = {"chips": len(ctx.devices), "device_kind": ctx.devices[0].device_kind,
              "config": cfg, "traffic": mix, "tokens_per_s": tokens_per_s,
              "train_flops_per_token": counts.train_flops_per_token(cfg, mix["seq_len"])}
    trace = None
    if ctx.trace:
        import trace_reduce

        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for _ in range(mix["trace_steps"]):
                state, mets = step(state, batches[i % mix["ring"]])
                i += 1
            jax.block_until_ready(mets["loss"])
        jax.profiler.stop_trace()
        path = next(os.path.join(d, f) for d, _, fs in os.walk(ctx.trace_dir)
                    for f in fs if f.endswith(".xplane.pb"))
        trace = trace_reduce.reduce_trace(path, scopes=metric_scopes(spec))
        record.update(trace=trace, steps_traced=mix["trace_steps"])
    mem = memory_peak(ctx.devices)

    # the reference, once the program's state is freed
    ref_batches = batches[:mix["check_steps"]]
    del state, mets, pending, batches, step
    tr.step = None
    reference = tr.ref.TrainReference(cfg, mix["optimizer"])
    readings = reference.readings(tr.init(k_w), ref_batches, mix["microbatches"])
    checks = {k: (v, spec.limits[k]) for k, v in compare(prog, readings).items()}
    return {
        "attempted": n, "failed": failed, "memory_peak_bytes": mem,
        "end_to_end": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "record": record, "trace": trace, "checks": checks,
    }
