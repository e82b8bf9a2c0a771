"""Continuous-batching serving engine (DESIGN.md §Serving).

Replaces the token-at-a-time ServeEngine: requests are admitted from a FIFO
queue into a fixed pool of batch slots, every slot advances by up to
`chunk_size` tokens per step through ONE jit'd `serve_step` — prefilling
slots consume their next prompt chunk, decoding slots their last sampled
token, idle slots are masked out. Static shapes (n_slots, chunk_size) mean
the whole engine runs trace-once; per-slot cache positions let sequences at
different offsets coexist; the BIP router's dual vector q threads through
every step, so expert loads stay balanced under mixed prefill/decode
traffic — the paper's systems payoff at inference time.

Two extensions ride on the same slot pool:

* `mesh=` puts the whole engine on a device mesh: params/cache/router
  state are laid out with the training shardings (distributed/sharding.py)
  and both jit'd step programs carry explicit in/out shardings, so MoE
  layers run the expert-parallel dispatch paths (ep/ep2d/ep2ds) with the
  masked global-sync duals — serving and training share one routing
  implementation.
* PACKED prefill decouples batch rows from cache slots: when a prompt is
  longer than one chunk and other rows would idle, its tail chunks spread
  across free rows (all-global stacks: write-then-attend makes this
  exact), and short fresh prompts tuck into other rows' padding columns as
  extra segments to free more rows. The packed step is only dispatched
  when it strictly reduces step count; otherwise the legacy single-layout
  program runs unchanged.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serving.scheduler import DECODE, PREFILL, Request, Scheduler
from repro.telemetry.slo import ServingTelemetry
from repro.telemetry.trace import Profiler, trace_span


class ContinuousBatchingEngine:
    """Slot-pooled serving with chunked prefill fused into the decode step."""

    def __init__(
        self,
        model: Model,
        params: Any,
        *,
        n_slots: int = 8,
        chunk_size: int = 32,
        max_seq_len: int = 2048,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        max_waiting: int = 256,
        use_kernel: Optional[bool] = None,
        seed: int = 0,
        default_deadline: Optional[float] = None,
        queue_timeout: Optional[float] = None,
        shed_on_full: bool = False,
        step_delay: float = 0.0,
        clock=time.perf_counter,
        sink=None,
        profile=None,
        profile_dir: str = "profile",
        mesh=None,
    ):
        cfg = model.cfg
        if (
            use_kernel is not None
            and cfg.is_moe
            and use_kernel != cfg.routing.use_kernel
        ):
            # serving-side override: flip the Pallas kernels (grouped expert
            # FFN + ADMM dual update) on/off without editing the config file.
            # Same parameter shapes, so the caller's params stay valid — the
            # serve path dispatches via moe._expert_ffn on the same masked
            # sort-based dispatch plan either way.
            from repro.models import build_model

            cfg = dataclasses.replace(
                cfg, routing=dataclasses.replace(cfg.routing, use_kernel=use_kernel)
            )
            model = build_model(cfg, model.mesh_ctx)
        if mesh is not None:
            # rebuild on the mesh: moe_ffn dispatches the expert-parallel
            # shard_map paths, attention/MLP get the training constraints
            from repro.distributed.sharding import make_mesh_ctx
            from repro.models import build_model

            model = build_model(cfg, make_mesh_ctx(mesh))
        assert not cfg.n_enc_layers and not cfg.frontend_dim, (
            "continuous batching serves token-only families; use "
            "greedy_generate's legacy path for encdec/vlm"
        )
        if cfg.is_moe:
            from repro.core import get_balancer

            if not get_balancer(cfg.routing.strategy).serving_ok:
                # fail at construction, not deep inside the first jit trace:
                # e.g. expert_choice selects each expert's top-C over the
                # batch, so a token's routing depends on later tokens —
                # incompatible with autoregressive decode
                raise NotImplementedError(
                    f"routing strategy {cfg.routing.strategy!r} is "
                    "training-only (batch-dependent selection breaks decode "
                    "causality); serve with a token-choice strategy instead"
                )
        if cfg.window_size and any(k == "local" for k, _ in cfg.layer_kinds()):
            # a chunk must fit the sliding-window ring buffer, whose capacity
            # is min(window, max_seq_len) (common.init_attention_cache)
            chunk_size = min(chunk_size, cfg.window_size, max_seq_len)
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.chunk_size = chunk_size
        self.max_seq_len = max_seq_len
        self.eos_id = eos_id
        # robustness knobs (DESIGN.md §Robustness): `default_deadline` is a
        # RELATIVE per-request latency budget applied at submit (absolute
        # deadline = clock() + budget); `clock` is injectable so deadline /
        # timeout behavior is testable deterministically with a fake clock;
        # `step_delay` is the slow_step fault-injection hook (seconds slept
        # per step, simulating decode slowdown).
        self.default_deadline = default_deadline
        self.step_delay = step_delay
        self.clock = clock
        self.scheduler = Scheduler(
            n_slots,
            max_waiting=max_waiting,
            queue_timeout=queue_timeout,
            shed_on_full=shed_on_full,
        )

        self.mesh = mesh
        self.cache = model.init_slot_cache(params, n_slots, max_seq_len)
        self.router_states = model.init_router_states()
        self._rng = jax.random.PRNGKey(seed)

        # packed-prefill capability gates: packing needs segment-aware
        # attention (no SSM/conv state — it advances strictly left-to-right
        # per row); spreading one stream across rows additionally needs the
        # write-then-attend cache on EVERY layer (no sliding-window rings)
        kinds = [k.replace("+shared", "") for k, _ in cfg.layer_kinds()]
        self._can_pack = all(k in ("global", "local") for k in kinds)
        self._can_spread = self._can_pack and all(k == "global" for k in kinds)

        def serve_step(params, cache, states, tokens, lengths, rng):
            logits, cache, states, mets = model.prefill_chunk(
                params, tokens, cache, states, lengths
            )
            idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
            if temperature > 0.0:
                nxt = jax.random.categorical(rng, last / temperature, axis=-1)
            else:
                nxt = jnp.argmax(last, axis=-1)
            return nxt.astype(jnp.int32), cache, states, mets

        def serve_step_packed(
            params, cache, states, tokens, positions, segments,
            write_slots, cache_rows, gather_rows, gather_cols, rng,
        ):
            logits, cache, states, mets = model.prefill_chunk(
                params, tokens, cache, states,
                positions=positions, segments=segments,
                write_slots=write_slots, cache_rows=cache_rows,
            )
            # per-SLOT sample: gather_* point at each slot's last real
            # column in the packed grid (garbage rows are never consumed)
            last = logits[gather_rows, gather_cols]  # (n_slots, vocab)
            if temperature > 0.0:
                nxt = jax.random.categorical(rng, last / temperature, axis=-1)
            else:
                nxt = jnp.argmax(last, axis=-1)
            return nxt.astype(jnp.int32), cache, states, mets

        if mesh is None:
            self._reset = jax.jit(model.reset_slot)
            self._serve_step = jax.jit(serve_step)
            self._serve_step_packed = jax.jit(serve_step_packed)
        else:
            # explicit shardings: params/cache/router state keep the
            # training layouts across every step; everything small (tokens,
            # sampled ids, metrics) is replicated
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.distributed.sharding import (
                cache_specs, param_specs, router_state_specs, shard_tree,
            )

            def named(specs):
                return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

            repl = NamedSharding(mesh, P())
            pshard = named(param_specs(params, cfg, mesh))
            cshard = named(cache_specs(self.cache, cfg, mesh, n_slots))
            sshard = named(router_state_specs(self.router_states))
            mshard = {"moe_load": repl, "max_vio": repl}
            self.params = shard_tree(params, param_specs(params, cfg, mesh), mesh)
            self.cache = shard_tree(
                self.cache, cache_specs(self.cache, cfg, mesh, n_slots), mesh
            )
            self.router_states = jax.tree.map(
                lambda x, s: jax.device_put(x, s), self.router_states, sshard
            )
            self._reset = jax.jit(
                model.reset_slot,
                in_shardings=(cshard, repl),
                out_shardings=cshard,
            )
            self._serve_step = jax.jit(
                serve_step,
                in_shardings=(pshard, cshard, sshard, repl, repl, repl),
                out_shardings=(repl, cshard, sshard, mshard),
            )
            self._serve_step_packed = jax.jit(
                serve_step_packed,
                in_shardings=(pshard, cshard, sshard) + (repl,) * 8,
                out_shardings=(repl, cshard, sshard, mshard),
            )

        # telemetry: counters, per-expert load, and SLO histograms live in
        # one reset-able ServingTelemetry; `sink` streams per-request
        # lifecycle records + the final summary (telemetry/slo.py). The
        # legacy counter attributes below are read-only views.
        self.telemetry = ServingTelemetry(
            cfg.routing.n_experts if cfg.is_moe else 1, sink=sink
        )
        # `profile` = (lo, hi) serve-step window captured with jax.profiler
        self._profiler = (
            Profiler(profile, log_dir=profile_dir) if profile is not None else None
        )

    # ------------------------------------------- legacy telemetry views

    @property
    def n_steps(self) -> int:
        return self.telemetry.n_steps

    @property
    def prefill_tokens(self) -> int:
        return self.telemetry.prefill_tokens

    @property
    def decode_tokens(self) -> int:
        return self.telemetry.decode_tokens

    @property
    def expert_load(self) -> np.ndarray:
        return self.telemetry.expert_load

    @property
    def max_vio_per_step(self) -> List[float]:
        return self.telemetry.max_vio_per_step

    @property
    def n_deadline_missed(self) -> int:
        return self.telemetry.n_deadline_missed

    @property
    def n_shed(self) -> int:
        return self.telemetry.n_shed

    def close(self) -> None:
        """Stop an in-flight profiler capture (sink closing is the caller's)."""
        if self._profiler is not None:
            self._profiler.close()

    # -------------------------------------------------------------- intake

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        *,
        eos_id: Optional[int] = None,
        ignore_eos: bool = False,
        arrival_time: float = 0.0,
        deadline: Optional[float] = None,
    ) -> Optional[Request]:
        """Queue one request. Returns it, or None under backpressure
        (bounded waiting queue full — retry after stepping the engine;
        never None when the engine sheds on full). `deadline` is a RELATIVE
        latency budget in seconds (falls back to the engine default);
        overdue requests are dropped/evicted with a deadline outcome
        instead of holding resources."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        assert len(prompt) < self.max_seq_len, "prompt does not fit the cache"
        now = self.clock()
        budget = deadline if deadline is not None else self.default_deadline
        req = Request(
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            ignore_eos=ignore_eos,
            arrival_time=arrival_time,
            deadline=None if budget is None else now + budget,
        )
        return req if self.scheduler.submit(req, now) else None

    # ---------------------------------------------------------------- step

    def _observe(self, req: Request) -> Request:
        """Route every request outcome (finish OR drop) through telemetry
        exactly once: counters, SLO histograms, and the per-request record."""
        self.telemetry.on_finish(req, len(req.output))
        return req

    def _plan_packed(self, active):
        """Packed-layout step plan, or None when the legacy one-row-per-slot
        layout is already step-optimal.

        Packing pays only when some prompt has more than `chunk_size` tokens
        left: its tail chunks can then SPREAD across rows that would
        otherwise idle (exactness argument in
        common._attention_chunk_packed — all-global stacks only), finishing
        a k-chunk prefill in ceil(k / n_free_rows) steps instead of k. Short
        fresh prompts are tucked into used rows' free columns as extra
        segments, vacating their rows for spreading. Returns the operand
        arrays of `serve_step_packed` plus the bookkeeping plan; falls back
        to None whenever the resulting layout would be identical to the
        legacy one (so steady-state decode keeps the legacy program)."""
        b, c = self.n_slots, self.chunk_size
        if not self._can_spread:
            return None
        if not any(
            not slot.prompt_done
            and len(slot.request.prompt) - slot.n_prefilled > c
            for _, slot in active
        ):
            return None

        tokens = np.zeros((b, c), np.int32)
        positions = np.zeros((b, c), np.int32)
        segments = np.full((b, c), -1, np.int32)
        write_slots = np.full((b, c), -1, np.int32)
        cache_rows = np.arange(b, dtype=np.int32)
        gather_rows = np.zeros((b,), np.int32)
        gather_cols = np.zeros((b,), np.int32)
        col_used = np.zeros((b,), np.int32)
        next_seg = np.ones((b,), np.int32)
        row_taken = [False] * b
        plan: List[tuple] = []

        decodes, shorts, streams = [], [], []
        for i, slot in active:
            if slot.prompt_done:
                decodes.append((i, slot))
            elif slot.n_prefilled == 0 and len(slot.request.prompt) < c:
                shorts.append((i, slot))
            else:
                streams.append((i, slot))

        for i, slot in decodes:
            tokens[i, 0] = slot.request.output[-1]
            positions[i, 0] = slot.pos - 1  # == cache pos of slot i
            segments[i, 0] = 0
            write_slots[i, 0] = i
            col_used[i] = 1
            row_taken[i] = True
            gather_rows[i], gather_cols[i] = i, 0
            plan.append((i, slot, DECODE, 1))

        # prefill streams: first chunk in the slot's own row as the resident
        # (segment 0) continuation of its cache
        rem: Dict[int, int] = {}
        last_at: Dict[int, tuple] = {}
        stream_slot = dict(streams)
        for i, slot in streams:
            p0 = slot.n_prefilled
            L = min(len(slot.request.prompt) - p0, c)
            tokens[i, :L] = slot.request.prompt[p0 : p0 + L]
            positions[i, :L] = np.arange(p0, p0 + L)
            segments[i, :L] = 0
            write_slots[i, :L] = i
            col_used[i] = L
            row_taken[i] = True
            rem[i] = len(slot.request.prompt) - p0 - L
            last_at[i] = (i, L - 1, L)  # (row, col, placed-so-far)

        # short fresh prompts: best-fit into a used row's padding columns as
        # a fresh segment (frees their own row for spreading below)
        for i, slot in sorted(
            shorts, key=lambda t: -len(t[1].request.prompt)
        ):
            L = len(slot.request.prompt)
            fit = [
                r for r in range(b) if row_taken[r] and col_used[r] + L <= c
            ]
            r = min(fit, key=lambda r: c - col_used[r] - L) if fit else i
            s = int(next_seg[r])
            row_taken[r] = True
            lo = col_used[r]
            tokens[r, lo : lo + L] = slot.request.prompt
            positions[r, lo : lo + L] = np.arange(L)
            segments[r, lo : lo + L] = s
            write_slots[r, lo : lo + L] = i
            next_seg[r] = s + 1
            col_used[r] = lo + L
            gather_rows[i], gather_cols[i] = r, lo + L - 1
            plan.append((i, slot, PREFILL, L))

        # spread: hand free rows to the streams with the most prompt left
        free = [r for r in range(b) if not row_taken[r]]
        used_extra = False
        for r in free:
            if not rem:
                break
            i = max(rem, key=rem.get)
            if rem[i] <= 0:
                break
            slot = stream_slot[i]
            p0 = slot.n_prefilled + last_at[i][2]
            L = min(rem[i], c)
            tokens[r, :L] = slot.request.prompt[p0 : p0 + L]
            positions[r, :L] = np.arange(p0, p0 + L)
            segments[r, :L] = 0
            cache_rows[r] = i  # this row CONTINUES slot i's stream
            write_slots[r, :L] = i
            col_used[r] = L
            row_taken[r] = True
            rem[i] -= L
            last_at[i] = (r, L - 1, last_at[i][2] + L)
            used_extra = True

        if not used_extra:
            return None  # no spreading happened: legacy layout is identical
        for i, slot in streams:
            r, col, placed = last_at[i]
            gather_rows[i], gather_cols[i] = r, col
            plan.append((i, slot, PREFILL, placed))
        return (
            tokens, positions, segments, write_slots, cache_rows,
            gather_rows, gather_cols, plan,
        )

    def step(self) -> List[Request]:
        """One fused serve step. Returns requests completed this step —
        including any dropped by the deadline/timeout sweep or shed at
        submit, so every request's outcome is reported exactly once."""
        if self.step_delay > 0:
            time.sleep(self.step_delay)  # slow_step fault injection
        if self._profiler is not None:
            self._profiler.step(self.telemetry.n_steps)
        # host phases as spans: admit, plan, dispatch, fetch (the wait on
        # the device), observe; each carries the step index, and those after
        # the plan its prefill and decode token counts
        step = self.telemetry.n_steps
        with trace_span("serve/admit", step=step):
            now = self.clock()
            # sweep BEFORE admission: evicting overdue slots frees them for
            # waiting work within the same step
            dropped = [
                self._observe(r)
                for r in self.scheduler.expire(now) + self.scheduler.take_dropped()
            ]
            for slot_idx, _req in self.scheduler.admit(now):
                self.cache = self._reset(self.cache, jnp.asarray(slot_idx))
            active = list(self.scheduler.active())
        if not active:
            return dropped

        b, c = self.n_slots, self.chunk_size
        with trace_span("serve/plan", step=step):
            packed = self._plan_packed(active) if self._can_pack else None
            self._rng, sub = jax.random.split(self._rng)
            if packed is not None:
                (tokens, positions, segments, write_slots, cache_rows,
                 gather_rows, gather_cols, plan) = packed
                args = (jnp.asarray(tokens), jnp.asarray(positions),
                        jnp.asarray(segments), jnp.asarray(write_slots),
                        jnp.asarray(cache_rows), jnp.asarray(gather_rows),
                        jnp.asarray(gather_cols), sub)
                serve_step = self._serve_step_packed
            else:
                tokens = np.zeros((b, c), np.int32)
                lengths = np.zeros((b,), np.int32)
                plan = []  # (slot_idx, slot, kind, n_tokens)
                for i, slot in active:
                    req = slot.request
                    if not slot.prompt_done:
                        chunk = req.prompt[slot.n_prefilled : slot.n_prefilled + c]
                        tokens[i, : len(chunk)] = chunk
                        lengths[i] = len(chunk)
                        plan.append((i, slot, PREFILL, len(chunk)))
                    else:
                        tokens[i, 0] = req.output[-1]
                        lengths[i] = 1
                        plan.append((i, slot, DECODE, 1))
                args = (jnp.asarray(tokens), jnp.asarray(lengths), sub)
                serve_step = self._serve_step
            counts = {
                "n_prefill": sum(n for _, _, kind, n in plan if kind == PREFILL),
                "n_decode": sum(1 for _, _, kind, _ in plan if kind == DECODE),
            }
        with trace_span("serve/dispatch", step=step, **counts):
            nxt, self.cache, self.router_states, mets = serve_step(
                self.params, self.cache, self.router_states, *args
            )
        with trace_span("serve/fetch", step=step, **counts):
            nxt = np.asarray(nxt)
        with trace_span("serve/observe", step=step, **counts):
            return self._finish_step(dropped, plan, mets, nxt, counts)

    def _finish_step(self, dropped, plan, mets, nxt, counts) -> List[Request]:
        """Record the step and hand each planned slot its sampled token."""
        self.telemetry.on_step(
            mets, queue_depth=len(self.scheduler.waiting), **counts
        )

        done: List[Request] = dropped
        now = self.clock()
        for i, slot, kind, n_tok in plan:
            req = slot.request
            if kind == PREFILL:
                slot.n_prefilled += n_tok
                if not slot.prompt_done:
                    continue  # still mid-prompt: this step's sample is unused
                req.phase = DECODE
                req.t_first_token = now
            # the step that finishes the prompt doubles as the first decode:
            # its last-position logits sample the first generated token
            tok = int(nxt[i])
            req.output.append(tok)
            eos = req.eos_id if req.eos_id is not None else self.eos_id
            if eos is not None and not req.ignore_eos and tok == eos:
                done.append(self._observe(self.scheduler.finish(i, "eos", now)))
            elif len(req.output) >= req.max_new_tokens:
                done.append(
                    self._observe(self.scheduler.finish(i, "max_new_tokens", now))
                )
            elif slot.pos >= self.max_seq_len:
                done.append(self._observe(self.scheduler.finish(i, "length", now)))
        return done

    # ----------------------------------------------------------------- run

    def run(self, requests: Optional[Iterable[Request]] = None) -> List[Request]:
        """Drain: submit any extra `requests` (respecting backpressure by
        interleaving steps), then step until no work remains. Returns all
        requests completed during this call, in completion order."""
        finished: List[Request] = []
        pending = list(requests) if requests is not None else []
        for req in pending:  # same guard submit() applies
            assert len(req.prompt) < self.max_seq_len, "prompt does not fit the cache"
        while pending:
            req = pending[0]
            if self.scheduler.submit(req, self.clock()):
                pending.pop(0)
            else:
                finished.extend(self.step())  # make room
        while self.scheduler.has_work:
            finished.extend(self.step())
        return finished


# ----------------------------------------------------------- compatibility


def greedy_generate(
    model: Model,
    params,
    prompts: jnp.ndarray,
    n_steps: int,
    max_seq_len: int = 2048,
    extra_batch: Optional[Dict[str, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Batched greedy decoding — thin wrapper over the continuous-batching
    engine (encdec/vlm requests carry per-request side inputs the slot pool
    does not model yet, so they fall back to the per-token legacy path)."""
    cfg = model.cfg
    if extra_batch or cfg.n_enc_layers or cfg.frontend_dim:
        return _legacy_generate(model, params, prompts, n_steps, max_seq_len, extra_batch)
    b, s = prompts.shape
    eng = ContinuousBatchingEngine(
        model,
        params,
        n_slots=b,
        chunk_size=min(max(s, 1), 64),
        # honor the (B, n_steps) shape contract: never evict on 'length'
        max_seq_len=max(max_seq_len, s + n_steps + 1),
    )
    reqs = [
        eng.submit(np.asarray(prompts[i]), n_steps, ignore_eos=True) for i in range(b)
    ]
    assert all(r is not None for r in reqs)
    eng.run()
    return jnp.asarray([r.output for r in reqs], jnp.int32)


def _legacy_generate(
    model: Model, params, prompts, n_steps, max_seq_len, extra_batch
) -> jnp.ndarray:
    """Seed-style per-token prefill + greedy decode (encdec/vlm only)."""
    batch = {"tokens": prompts}
    if extra_batch:
        batch.update(extra_batch)
    cache = model.init_cache(params, batch, max_seq_len)
    states = model.init_router_states()
    decode = jax.jit(model.decode_step)
    logits = None
    for t in range(prompts.shape[1]):
        logits, cache, states = decode(params, prompts[:, t : t + 1], cache, states)
    toks = []
    for _ in range(n_steps):
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        toks.append(nxt)
        logits, cache, states = decode(params, nxt, cache, states)
    return jnp.concatenate(toks, axis=1)
