"""Training harness: TrainState, sharded/donated/microbatched train step,
checkpointed host-side driver.

The train step threads three pytrees: params, optimizer state, and the
per-MoE-layer router states (the BIP dual vector q / Loss-Free bias). The
host loop accumulates the paper's balance measurements (per-batch MaxVio per
layer -> AvgMaxVio / SupMaxVio) via BalanceTracker — exactly the quantities
in the paper's Tables 2-5.

Production shape (DESIGN.md §Training):

* **Sharding** — `compile_train_step(..., mesh=...)` resolves explicit
  `in_shardings`/`out_shardings` for every TrainState leaf and batch tensor
  from `repro.distributed.sharding` (FSDP params over the data axes, tensor/
  expert parallelism over 'model', replicated router duals) so GSPMD never
  has to guess a layout for the optimizer update.
* **Donation** — the TrainState argument is donated (`donate_argnums=(0,)`):
  params/mu/nu buffers are updated in place, so a step's live memory is one
  copy of the state plus transients, not two.
* **Mixed precision** — master params and Adam moments stay fp32 (or the
  per-config `adam_*_dtype` policy); the forward/backward computes in
  `cfg.compute_dtype` (bf16 for the full-size configs) because every weight
  is cast at its use site inside the model. Gradients therefore come back in
  the fp32 master dtype and the update math runs in fp32 (`optim.adamw`).
* **Gradient accumulation** — `microbatches=k` reshapes the global batch to
  (k, B/k, ...) and runs a `lax.scan` of forward/backward per microbatch,
  accumulating gradients in the parameter dtype; router states thread
  *sequentially* through microbatches (the BIP dual price q updates between
  microbatches, exactly as it would across smaller true steps).
* **Router dual sync** — `cfg.routing.sync` rides into the compiled sharded
  step through the model: 'global' makes every BIP gate run the fused
  multi-threshold dual update with psum'd counts over the mesh's data axes
  inside the step (`ref_bip.bip_dual_update_global`), so the carried q is
  the single-device paper trajectory; 'local' solves per-shard duals and
  pmean-averages them into the warm start (DESIGN.md §Global-sync). The
  replicated router-state sharding spec
  (`distributed.sharding.router_state_specs`) is the same either way, and
  covers every state leaf — including the dual-forecaster EMAs
  ('q_ema'/'q_err') that `cfg.routing.forecast` adds, which thread through
  microbatches and steps exactly like q.
* **Checkpointing** — `train_loop(ckpt_dir=..., ckpt_every=N, resume=True)`
  saves the full TrainState (params, Adam moments, step counter, router
  states — the dual q plus, under `cfg.routing.forecast`, the forecaster
  EMAs) through `checkpoint.store` and resumes bit-exactly: the data
  stream is deterministic per step index and the forecaster state restores
  with the duals, so a restored run replays the remaining schedule on
  identical batches with identical warm-start brackets.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import BalanceTracker
from repro.models.model import Model
from repro.optim import adamw as _adamw
from repro.telemetry.metrics import MetricSeries, TrainTelemetry
from repro.telemetry.trace import named_span


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    router_states: Any


def init_train_state(model: Model, key, opt_cfg: _adamw.AdamWConfig) -> TrainState:
    params = model.init(key)
    return TrainState(
        params=params,
        opt_state=_adamw.adamw_init(params, opt_cfg),
        router_states=model.init_router_states(),
    )


def _split_micro(batch: Dict[str, jnp.ndarray], k: int) -> Dict[str, jnp.ndarray]:
    return jax.tree.map(
        lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch
    )


def _reduce_micro_mets(mets: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Collapse (k, ...)-stacked per-microbatch metrics to per-step values.

    MaxVio is reduced with max (the conservative per-step number: the worst
    microbatch — matches SupMaxVio semantics); dispatch counts SUM (the
    step's total per-expert load, keeping integer dtype); state-magnitude
    telemetry (dual |q|, forecaster error) takes the LAST microbatch — the
    carried state after the step, matching what a ckpt would hold; scalars
    average; perplexity is recomputed from the averaged CE so it stays
    exp(mean nll)."""
    out = {}
    for name, v in mets.items():
        if name == "max_vio_per_layer":
            out[name] = jnp.max(v, axis=0)
        elif name == "load_per_layer":
            out[name] = jnp.sum(v, axis=0)
        elif name in ("q_abs_max_per_layer", "forecast_err_per_layer"):
            out[name] = v[-1]
        elif name != "perplexity":
            out[name] = jnp.mean(v, axis=0)
    if "ce_loss" in out:
        out["perplexity"] = jnp.exp(out["ce_loss"])
    return out


# control-vector layout for the guarded train step: a (3,) float32 array of
# per-step scalars the host can set without recompiling.
CTRL_INJECT_NAN = 0  # > 0: fault injection — scale the loss (hence grads) by NaN
CTRL_FORCE_SKIP = 1  # > 0: select the pre-step state (planned skip / replay)
CTRL_LR_SCALE = 2    # multiplier on the scheduled LR (guard's reduce-LR ladder)


def default_controls() -> np.ndarray:
    return np.array([0.0, 0.0, 1.0], np.float32)


def make_train_step(
    model: Model,
    opt_cfg: _adamw.AdamWConfig,
    lr_fn: Callable[[jnp.ndarray], jnp.ndarray],
    *,
    microbatches: int = 1,
    rng: Optional[jnp.ndarray] = None,
    guarded: bool = False,
):
    """Returns train_step(state, batch) -> (state, metrics). Pure; jit-ready.

    With microbatches=k the batch's leading axis must divide by k; the
    forward/backward runs as a k-trip lax.scan with gradient accumulation so
    the residual/activation footprint is that of B/k sequences.

    `rng` (optional) is a base PRNG key; each step derives its key by
    folding in the optimizer's step counter (and the microbatch index under
    accumulation), so the per-step randomness seen by dropout-style
    regularizers is a pure function of checkpointed state — resume-stable
    by construction.

    `guarded=True` changes the signature to train_step(state, batch,
    controls) with `controls` a (3,) float32 vector (see CTRL_*), and adds
    the in-graph anomaly guard: `step_ok = isfinite(loss) &
    isfinite(grad_norm) & ~force_skip`, with EVERY output leaf (params,
    Adam moments incl. the step counter, router states) selected back to
    its pre-step value when false. A NaN/Inf step therefore cannot poison
    the state, and a skipped step is bit-identical to the step never having
    run — the invariant the rollback-recovery determinism test relies on.
    Metrics gain 'step_ok'.
    """

    def _fwd_bwd(params, batch, router, key, nan_coef=None):
        def f(p):
            loss, aux = model.loss_fn(p, batch, router, key)
            if nan_coef is not None:
                # fault seam (robustness/faults.NanGrad): nan_coef is 1.0
                # normally, NaN when the injector fires — grads = coef * dL
                loss = loss * nan_coef
            return loss, aux

        with named_span("train/fwd_bwd"):
            return jax.value_and_grad(f, has_aux=True)(params)

    def _apply(state: TrainState, grads, new_router, mets, lr_scale=None):
        lr = lr_fn(state.opt_state["step"].astype(jnp.float32))
        if lr_scale is not None:
            lr = lr * lr_scale
        with named_span("train/apply"):
            new_params, new_opt, info = _adamw.adamw_update(
                grads, state.opt_state, state.params, lr, opt_cfg
            )
        mets = dict(mets)
        mets.update(info)
        return (
            TrainState(params=new_params, opt_state=new_opt, router_states=new_router),
            mets,
        )

    def _run(state: TrainState, batch: Dict[str, jnp.ndarray], nan_coef, lr_scale):
        step_key = (
            None if rng is None else jax.random.fold_in(rng, state.opt_state["step"])
        )
        if microbatches <= 1:
            (loss, (new_router, mets)), grads = _fwd_bwd(
                state.params, batch, state.router_states, step_key, nan_coef
            )
            mets = dict(mets)
            mets["loss"] = loss
            return _apply(state, grads, new_router, mets, lr_scale)

        mb = _split_micro(batch, microbatches)
        # accumulate in the parameter dtype: fp32 accumulation doubles the
        # carry footprint for bf16-param models (arctic) with negligible
        # benefit at <=16 microbatches
        acc_dt = model.cfg.param_dtype

        def body(carry, inp):
            one, mb_idx = inp
            grads_acc, router = carry
            key = None if step_key is None else jax.random.fold_in(step_key, mb_idx)
            (loss, (router, mets)), grads = _fwd_bwd(
                state.params, one, router, key, nan_coef
            )
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(acc_dt), grads_acc, grads
            )
            mets = dict(mets)
            mets["loss"] = loss
            return (grads_acc, router), mets

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), state.params)
        (grads, new_router), mets = jax.lax.scan(
            body, (zero, state.router_states), (mb, jnp.arange(microbatches))
        )
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        return _apply(state, grads, new_router, _reduce_micro_mets(mets), lr_scale)

    if not guarded:

        def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
            return _run(state, batch, None, None)

        return train_step

    def guarded_step(
        state: TrainState, batch: Dict[str, jnp.ndarray], controls: jnp.ndarray
    ):
        controls = controls.astype(jnp.float32)
        nan_coef = jnp.where(controls[CTRL_INJECT_NAN] > 0, jnp.nan, 1.0)
        new_state, mets = _run(
            state, batch, nan_coef, controls[CTRL_LR_SCALE]
        )
        ok = (
            jnp.isfinite(mets["loss"])
            & jnp.isfinite(mets["grad_norm"])
            & (controls[CTRL_FORCE_SKIP] <= 0)
        )
        # anomaly => keep the PRE-step state for every leaf (params, Adam
        # moments + step counter, router duals/forecaster): elementwise
        # select, so donation aliasing still holds and a healthy step pays
        # one predicated copy
        final = jax.tree.map(
            lambda new, old: jnp.where(ok, new, old), new_state, state
        )
        mets["step_ok"] = ok
        return final, mets

    return guarded_step


def compile_train_step(
    model: Model,
    opt_cfg: _adamw.AdamWConfig,
    lr_fn,
    state: TrainState,
    batch: Dict[str, Any],
    *,
    mesh=None,
    microbatches: int = 1,
    donate: bool = True,
    st_specs=None,
    b_specs=None,
    rng: Optional[jnp.ndarray] = None,
    guarded: bool = False,
    telemetry: Optional[TrainTelemetry] = None,
):
    """jit the train step, with explicit shardings when a mesh is given.

    `state`/`batch` may be concrete arrays or ShapeDtypeStructs — only their
    tree structure and shapes are consulted. On a mesh, every TrainState leaf
    and batch tensor gets the PartitionSpec from `distributed.sharding` as an
    explicit in/out sharding (out == in, so the donated buffers alias
    leaf-for-leaf and the state layout is fixed-point across steps); metrics
    come back replicated. Callers that already resolved the spec trees (e.g.
    train_loop, which also places the arrays with them) pass st_specs /
    b_specs so there is one resolution per run.

    `guarded=True` compiles the 3-arg guarded step (see make_train_step);
    the control vector is replicated on a mesh.

    `telemetry` (a TrainTelemetry) instruments the step: the metric layout
    is derived via `jax.eval_shape` on the UN-instrumented step, and the
    compiled signature gains two trailing args — the in-graph MetricStream
    buffer and the step index — returning (state, mets, buffer). The
    buffer is NOT donated (the host holds async copies of drained windows)
    and is replicated on a mesh; every scattered value is one the step
    already computed, so instrumentation adds no collectives and no syncs.
    """
    step = make_train_step(
        model, opt_cfg, lr_fn, microbatches=microbatches, rng=rng, guarded=guarded
    )
    donate_argnums = (0,) if donate else ()

    raw_step = step
    if telemetry is not None:
        eval_args = (state, batch)
        if guarded:
            eval_args = eval_args + (jax.ShapeDtypeStruct((3,), jnp.float32),)
        _, mets_shapes = jax.eval_shape(raw_step, *eval_args)
        telemetry.ensure_built(mets_shapes)
        stream = telemetry.stream

        def step(*args):
            *inner, buf, step_idx = args
            new_state, mets = raw_step(*inner)
            buf = stream.accumulate(buf, mets, step_idx)
            return new_state, mets, buf

    # one program name for every variant: traces and build spans name it so
    step.__name__ = step.__qualname__ = "train_step"
    if mesh is None:
        return jax.jit(step, donate_argnums=donate_argnums)

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.sharding import batch_specs, train_state_specs

    if st_specs is None:
        st_specs = train_state_specs(state, model.cfg, mesh)
    if b_specs is None:
        b_all = batch_specs(model.cfg, mesh, jax.tree.leaves(batch)[0].shape[0])
        b_specs = {k: b_all[k] for k in batch}
    as_sharding = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
    )
    repl = NamedSharding(mesh, PartitionSpec())
    in_shardings = (as_sharding(st_specs), as_sharding(b_specs))
    if guarded:
        in_shardings = in_shardings + (repl,)
    out_shardings = (as_sharding(st_specs), None)
    if telemetry is not None:
        buf_shardings = jax.tree.map(lambda _: repl, telemetry.buf)
        in_shardings = in_shardings + (buf_shardings, repl)
        out_shardings = out_shardings + (buf_shardings,)
    return jax.jit(
        step,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=donate_argnums,
    )


class TrainLog:
    """Host-side record of one run, including the paper's balance metrics.

    Backed by one `telemetry.MetricSeries` column store instead of the
    historical parallel lists; `losses` / `perplexities` / `step_times` /
    `max_vio_steps` survive as read-only views so every existing caller
    (tests, benchmarks, launchers) keeps working unchanged. `events` stays
    a plain settable list — the guard ladder assigns it wholesale.
    """

    def __init__(self) -> None:
        self.series = MetricSeries()
        self.per_layer: List[BalanceTracker] = []
        self.model_tracker: BalanceTracker = BalanceTracker()
        self.events: List[Dict[str, Any]] = []

    def __len__(self) -> int:
        return len(self.series)

    @property
    def losses(self) -> List[float]:
        return list(self.series.column("ce_loss"))

    @property
    def perplexities(self) -> List[float]:
        return list(self.series.column("perplexity"))

    @property
    def step_times(self) -> List[float]:
        return list(self.series.column("step_time"))

    @property
    def max_vio_steps(self) -> List[np.ndarray]:
        return [v for v in self.series.column("max_vio") if v is not None]

    def truncate(self, n: int) -> None:
        """Drop records past the first `n` steps and rebuild the balance
        trackers from the survivors — a rollback rewinds the log so replayed
        steps are not double-counted in AvgMaxVio/SupMaxVio."""
        self.series.truncate(max(0, n))
        self.per_layer = []
        self.model_tracker = BalanceTracker()
        for vios in self.max_vio_steps:
            if not self.per_layer:
                self.per_layer = [BalanceTracker() for _ in range(vios.size)]
            for t, v in zip(self.per_layer, vios):
                t.add(float(v))
            self.model_tracker.add(float(vios.max()))

    def record(self, mets: Dict[str, Any], dt: float) -> None:
        rec: Dict[str, Any] = {
            "ce_loss": float(mets["ce_loss"]),
            "perplexity": float(mets["perplexity"]),
            "step_time": dt,
        }
        vios = np.asarray(mets.get("max_vio_per_layer", np.zeros(0)))
        if vios.size:
            rec["max_vio"] = vios
            if not self.per_layer:
                self.per_layer = [BalanceTracker() for _ in range(vios.size)]
            for t, v in zip(self.per_layer, vios):
                t.add(float(v))
            # model-level MaxVio for the batch = max over layers (conservative)
            self.model_tracker.add(float(vios.max()))
        self.series.append(rec)

    def summary(self) -> Dict[str, Any]:
        times = self.step_times
        out = {
            "final_loss": self.losses[-1] if len(self.series) else None,
            "final_ppl": self.perplexities[-1] if len(self.series) else None,
            "mean_step_time": None,
            "step_time_p50": None,
            "step_time_p99": None,
            **self.model_tracker.summary(),
        }
        if len(times) > 2:
            # skip the first two steps (compile + warm caches) so the
            # quantiles describe steady-state throughput
            steady = np.asarray(times[2:], dtype=np.float64)
            out["mean_step_time"] = float(steady.mean())
            out["step_time_p50"] = float(np.percentile(steady, 50))
            out["step_time_p99"] = float(np.percentile(steady, 99))
        if self.per_layer:
            out["AvgMaxVio_per_layer"] = [t.avg_max_vio for t in self.per_layer]
        if self.events:
            out["guard_events"] = list(self.events)
        return out


def train_loop(
    model: Model,
    batches: Iterable[Dict[str, jnp.ndarray]],
    *,
    key=None,
    lr: float = 3e-4,
    warmup_steps: int = 20,
    total_steps: int = 200,
    opt_overrides: Optional[Dict] = None,
    log_every: int = 0,
    state: Optional[TrainState] = None,
    mesh=None,
    microbatches: int = 1,
    donate: bool = True,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    resume: bool = False,
    async_ckpt: bool = True,
    guard=None,
    faults=None,
    telemetry: Optional[TrainTelemetry] = None,
) -> Tuple[TrainState, TrainLog]:
    """Host driver. With `mesh` the state/batches are placed with the specs
    from `distributed.sharding` and the step compiles with explicit
    shardings + donation; without one it is the plain single-device jit.

    `batches` is any iterable of batch dicts; when it is a `BatchStream`
    (has state_dict/load_state_dict — `data.ShardedTextLoader`,
    `data.SyntheticBatchStream`, or a `data.Prefetcher` around either),
    its cursor is checkpointed alongside the TrainState and `resume=True`
    seeks it in O(1) instead of regenerating + discarding the consumed
    prefix. Plain iterables keep the replay-skip fallback.

    Checkpoints are written asynchronously by default (`async_ckpt=True`):
    the save snapshots device buffers and overlaps the host gather + npz
    write with the next steps, barriering at the following save
    (checkpoint/store.py). Iteration stops at `total_steps` even when the
    stream is infinite (real-corpus loaders loop epochs forever).

    `resume=True` restores the newest VALID checkpoint under `ckpt_dir`
    (corrupt/truncated files are skipped with a warning) and continues
    bit-exactly — including the router duals q and the data cursor.

    Robustness (DESIGN.md §Robustness):

    * `guard` (a `robustness.GuardConfig`) compiles the guarded step —
      non-finite loss/grads leave the state bit-untouched — and runs the
      host-side skip -> reduce-LR -> rollback ladder. A rollback restores
      the newest valid checkpoint, rewinds the data cursor through the
      stream's `load_state_dict`, truncates the log, and replays; the
      anomalous step is force-skipped on replay, so recovery is
      deterministic (bit-identical to a run that skipped the step
      in place). Rollback requires a checkpoint manager AND a rewindable
      BatchStream; without them the ladder raises `TrainingDiverged`.
    * `faults` (a `robustness.FaultPlan`) drives the injection seams: the
      NaN scalar into the guarded step, and post-save checkpoint
      corruption for chaos tests.
    * SIGTERM (preemption) triggers one final SYNCHRONOUS checkpoint and a
      clean return — installed only on the main thread and restored on
      exit.

    `telemetry` (a `telemetry.TrainTelemetry`) threads the in-graph metric
    buffer through the compiled step, records per-step wall time, drains
    windows asynchronously to the sink, and streams guard/fault/lifecycle
    events as they happen. The partial final window is flushed in the
    `finally` block; closing the sink is the caller's job.
    """
    from repro.optim.schedules import linear_warmup_cosine

    key = key if key is not None else jax.random.PRNGKey(0)
    opt_cfg = _adamw.from_model_config(model.cfg, **(opt_overrides or {}))

    manager = None
    if ckpt_dir is not None:
        from repro.checkpoint import CheckpointManager

        manager = CheckpointManager(ckpt_dir)

    is_stream = hasattr(batches, "state_dict") and hasattr(batches, "load_state_dict")
    start_step = 0
    data_state = None
    if resume and manager is not None and state is None:
        from repro.checkpoint.store import latest_step

        if latest_step(ckpt_dir) is not None:
            start_step, state = manager.restore_train_state()
            data_state = manager.restore_data_state(start_step)
    loop_start = 0  # index the enumerate starts at
    if is_stream and data_state is not None:
        batches.load_state_dict(data_state)  # O(1) seek past the consumed prefix
        loop_start = start_step

    st_specs = b_specs = None
    if mesh is None:
        if state is None:
            state = init_train_state(model, key, opt_cfg)
    else:
        from jax.sharding import NamedSharding

        from repro.distributed.sharding import (
            batch_specs,
            shard_tree,
            train_state_specs,
        )

        init = lambda k: init_train_state(model, k, opt_cfg)
        st_specs = train_state_specs(
            jax.eval_shape(init, key) if state is None else state, model.cfg, mesh
        )
        if state is None:
            # every leaf is created on its own shards: the whole state of a
            # model that needs the mesh does not fit one device
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), st_specs,
                is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
            )
            state = jax.jit(init, out_shardings=shardings)(key)
        else:
            state = shard_tree(state, st_specs, mesh)

    guarded = guard is not None or (faults is not None and faults.get("nan_grad"))
    tguard = None
    if guarded:
        from repro.robustness.guards import ROLLBACK, GuardConfig, TrainGuard

        tguard = TrainGuard(
            guard if guard is not None else GuardConfig(),
            can_rollback=manager is not None and is_stream and ckpt_every > 0,
        )

    # preemption safety: SIGTERM requests one final synchronous checkpoint.
    # Signal handlers are a main-thread-only facility; elsewhere (e.g. a
    # train_loop driven from a worker thread in tests) the flag stays False.
    import signal as _signal
    import threading as _threading

    sig_flag = {"term": False}
    prev_handler = None
    hook_signal = (
        manager is not None
        and _threading.current_thread() is _threading.main_thread()
    )
    if hook_signal:
        prev_handler = _signal.getsignal(_signal.SIGTERM)
        _signal.signal(_signal.SIGTERM, lambda *_: sig_flag.update(term=True))

    step_fn = None
    log = TrainLog()
    mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
    saved_at = -1

    emitted = {"n": 0}

    def _stream_events() -> None:
        # forward newly appended guard-ladder events to the telemetry sink
        # exactly once each, in order
        if telemetry is None or tguard is None:
            return
        while emitted["n"] < len(tguard.events):
            telemetry.event(dict(tguard.events[emitted["n"]]))
            emitted["n"] += 1

    def _save(block: bool) -> Optional[str]:
        path = manager.save_train_state(
            state,
            data_state=batches.state_dict() if is_stream else None,
            block=block,
        )
        if faults is not None and faults.get("ckpt_corrupt") is not None:
            manager.wait()  # the file must be fully written before corrupting
            if faults.corrupt_after_save(path):
                ev = {"step": i, "kind": "ckpt_corrupted", "path": path}
                log.events.append(ev)
                if telemetry is not None:
                    telemetry.event(ev)
        return path

    try:
        it = iter(batches)
        i = loop_start - 1
        while True:
            # bound infinite streams (epoch-looping corpus loaders) *before*
            # pulling: the stream cursor must stay in sync with the step count,
            # so never consume a batch that won't be trained on
            if total_steps and i + 1 >= total_steps:
                break
            try:
                batch = next(it)
            except StopIteration:
                break
            i += 1
            if i < start_step:
                continue  # resumed plain iterable: replay-skip the consumed prefix
            if mesh is not None:
                if b_specs is None:
                    b_all = batch_specs(
                        model.cfg, mesh, jax.tree.leaves(batch)[0].shape[0]
                    )
                    b_specs = {k: b_all[k] for k in batch}
                batch = shard_tree(batch, b_specs, mesh)
            if step_fn is None:
                step_fn = compile_train_step(
                    model,
                    opt_cfg,
                    linear_warmup_cosine(lr, warmup_steps, total_steps),
                    state,
                    batch,
                    mesh=mesh,
                    microbatches=microbatches,
                    donate=donate,
                    st_specs=st_specs,
                    b_specs=b_specs,
                    rng=jax.random.fold_in(key, 0x5eed),
                    guarded=bool(guarded),
                    telemetry=telemetry,
                )
            if telemetry is not None:
                telemetry.before_step(i)  # profiler window, if configured
            t0 = time.perf_counter()
            step_args = (state, batch)
            if guarded:
                force_skip, lr_scale = tguard.controls(i)
                inject = faults is not None and faults.nan_fires(i)
                controls = jnp.asarray(
                    [float(inject), float(force_skip), lr_scale], jnp.float32
                )
                step_args = step_args + (controls,)
            if telemetry is not None:
                step_args = step_args + (telemetry.buf, jnp.asarray(i, jnp.int32))
                with mesh_ctx:
                    state, mets, tbuf = step_fn(*step_args)
            else:
                with mesh_ctx:
                    state, mets = step_fn(*step_args)
            jax.block_until_ready(mets["loss"])
            dt = time.perf_counter() - t0
            if telemetry is not None:
                telemetry.note_step_time(i, dt)
                # adopt before guard observation so an anomalous step's row
                # is captured even when the guard rolls back past it
                telemetry.after_step(i, tbuf)
            if guarded:
                action = tguard.observe(  # raises TrainingDiverged on RAISE
                    i, float(mets["loss"]), bool(mets["step_ok"])
                )
                log.events = tguard.events
                _stream_events()
                if action == ROLLBACK:
                    r_step, state = manager.restore_train_state()
                    ds = manager.restore_data_state(r_step)
                    if ds is None:
                        from repro.robustness.guards import TrainingDiverged

                        raise TrainingDiverged(
                            f"rollback to step {r_step}: checkpoint has no "
                            f"data cursor to rewind the stream with"
                        )
                    if hasattr(batches, "close"):
                        batches.close()  # a Prefetcher must re-arm post-rewind
                    batches.load_state_dict(ds)
                    it = iter(batches)
                    if mesh is not None:
                        state = shard_tree(state, st_specs, mesh)
                    log.truncate(r_step - loop_start)
                    log.events = tguard.events
                    _stream_events()
                    if telemetry is not None:
                        telemetry.event(
                            {"step": i, "kind": "rollback_replay", "to_step": r_step}
                        )
                    start_step = 0  # a fallback restore may predate `resume`
                    i = r_step - 1
                    if log_every:
                        print(f"rollback -> step {r_step} (replaying)")
                    continue
            log.record(mets, dt)
            if log_every and i % log_every == 0:
                print(
                    f"step {i:5d} loss {log.losses[-1]:.4f} "
                    f"ppl {log.perplexities[-1]:.2f}"
                    + (
                        f" maxvio {log.max_vio_steps[-1].max():.3f}"
                        if log.max_vio_steps
                        else ""
                    )
                )
            if manager is not None and ckpt_every and (i + 1) % ckpt_every == 0:
                _save(block=not async_ckpt)
                saved_at = i
            if sig_flag["term"]:
                # preemption: make the state durable NOW, synchronously
                _save(block=True)
                saved_at = i
                ev = {"step": i, "kind": "sigterm_checkpoint"}
                log.events.append(ev)
                if telemetry is not None:
                    telemetry.event(ev)
                break
        if manager is not None and ckpt_every and saved_at != i:
            _save(block=not async_ckpt)  # final state, off-boundary stop
    finally:
        if telemetry is not None:
            telemetry.finish()  # partial window + outstanding async copies
        if hook_signal:
            _signal.signal(_signal.SIGTERM, prev_handler)
        if manager is not None:
            manager.wait()  # checkpoints durable before the loop returns
        if hasattr(batches, "close"):
            batches.close()  # stop a Prefetcher's producer on early break
    return state, log


def evaluate_ppl(model: Model, state: TrainState, batches) -> float:
    """Test perplexity, routing states frozen (read-only copy per batch).

    Per-batch CE means are weighted by each batch's valid-token count, so
    ragged final batches / masked labels don't skew the corpus perplexity."""
    ces, ns = [], []
    loss_fn = jax.jit(model.loss_fn)
    for batch in batches:
        _, (_, mets) = loss_fn(state.params, batch, state.router_states)
        ces.append(float(mets["ce_loss"]))
        ns.append(int(np.sum(np.asarray(batch["labels"]) >= 0)))
    return float(np.exp(np.average(ces, weights=ns)))
