"""Unified top-k router — a thin orchestrator over the balancer registry.

`route()` resolves cfg.strategy through `core.balancers` and drives the hook
protocol in a fixed order (score → guard → score_adjust → select → aux_loss →
update_state → metrics); every balancing method — the paper's four
(topk / aux_loss / lossfree / bip) and the registry additions (phi / lpr /
expert_choice) — plugs in behind the same call. See core/balancers.py for
the protocol and the per-method semantics.

All strategies share RouterState {'q': (m,)}; for 'lossfree' the vector plays
the role of the bias b (added), for 'bip' the dual price q (subtracted), for
'phi' the multiplicative log-correction. Gate *values* are always the raw
scores of the selected experts, so none of these vectors receive gradient —
only 'aux_loss' shapes gradients, via its explicit loss.

The router is functional: `route(logits, state, cfg)` returns RouterOutput with
the new state; the training loop threads state through like any other pytree.

Distribution note (see DESIGN.md §3.3 / §Global-sync): under plain jit/pjit
the math below is written over the *global* token batch, so single-program
callers get paper-global duals for free — XLA inserts the collectives for the
column order statistic when tokens are sharded. Inside a shard_map (the EP
paths in models/moe.py) each device sees only its token shard, and
cfg.sync selects the semantics: 'global' runs the threshold dual update with
psum-reduced counts over cfg.data_axes (`ref_bip.bip_dual_update_global`) so
every device converges on the same q over the global batch; 'local' solves a
per-shard BIP and the caller averages the warm-start duals. sync='local' with
`local_shards > 1` additionally lets a single-program caller emulate the
per-shard semantics by vmapping the dual update over token groups.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import balancers, ref_bip
from repro.core.types import RouterConfig, RouterOutput, init_router_state
from repro.telemetry.trace import named_span


# ------------------------------------------------------- dispatch plan
#
# Sort-based ragged dispatch (megablocks-style, Gale et al.): one stable
# argsort of the (n·k,) expert assignments replaces the (n·k, m) one-hot +
# serial cumsum bookkeeping, and packing/combining become pure gathers —
# no m-wide intermediate, no repeat(x, k) materialization, no scatter-add
# over d-wide activations. Semantics match the historical one-hot plan
# bit-for-bit: capacity queues are token-ordered (earlier tokens win),
# slot-major within a token, and token_mask rows never occupy capacity.


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Ragged routing plan consumed within a single trace (not a pytree).

    order    (n·k,) stable argsort of expert assignments (masked → sentinel m)
    offsets  (m+1,) segment start of each expert's queue in sorted order
    pos      (n, k) position of each (token, slot) in its expert's queue
    keep     (n, k) slot survives capacity (and token_mask)
    """

    expert_index: jnp.ndarray  # (n, k) int32
    order: jnp.ndarray
    offsets: jnp.ndarray
    pos: jnp.ndarray
    keep: jnp.ndarray
    capacity: int
    top_k: int

    @property
    def counts(self) -> jnp.ndarray:
        """Per-expert assigned load (m,), pre-capacity, masked rows excluded."""
        return self.offsets[1:] - self.offsets[:-1]

    def pack(
        self,
        x: jnp.ndarray,  # (n, d)
        *,
        expert_offset=0,  # first expert owned locally (may be traced)
        n_local: Optional[int] = None,  # experts packed (static); default all
    ) -> jnp.ndarray:
        """Gather tokens into the (n_local, capacity, d) expert buffers."""
        nk = self.order.shape[0]
        m_loc = (self.offsets.shape[0] - 1) if n_local is None else n_local
        cap = self.capacity
        slots = jnp.arange(m_loc * cap, dtype=jnp.int32)
        se = expert_offset + slots // cap
        src_sorted = jnp.take(self.offsets, se) + slots % cap
        valid = src_sorted < jnp.take(self.offsets, se + 1)
        src_tok = jnp.take(self.order, jnp.minimum(src_sorted, nk - 1)) // self.top_k
        buf = jnp.take(x, src_tok, axis=0) * valid[:, None].astype(x.dtype)
        return buf.reshape(m_loc, cap, x.shape[-1])

    def combine(
        self,
        y: jnp.ndarray,  # (n_local, capacity, d) expert outputs
        weights: jnp.ndarray,  # (n, k) combine weights
        *,
        expert_offset=0,
    ) -> jnp.ndarray:
        """Gather expert outputs back per (token, slot), weight, and sum."""
        m_loc, cap, d = y.shape
        n, k = self.expert_index.shape
        e_rel = self.expert_index - expert_offset
        ok = (self.keep & (e_rel >= 0) & (e_rel < m_loc)).reshape(-1)
        slot = (e_rel * cap + self.pos).reshape(-1)
        g = jnp.take(y.reshape(m_loc * cap, d), jnp.where(ok, slot, 0), axis=0)
        w = weights.reshape(-1, 1).astype(y.dtype)
        contrib = jnp.where(ok[:, None], g * w, 0.0)
        return contrib.reshape(n, k, d).sum(axis=1)


def make_dispatch_plan(
    expert_index: jnp.ndarray,  # (n, k) int32
    n_experts: int,
    capacity: int,
    token_mask: Optional[jnp.ndarray] = None,  # (n,) bool; False never dispatches
) -> DispatchPlan:
    """Build the sort-based plan for one routed batch.

    Masked tokens are re-keyed to the sentinel expert m, so the stable sort
    pushes them past every real segment: they neither occupy capacity nor
    displace real tokens, and `counts` covers real traffic only.
    """
    n, k = expert_index.shape
    nk = n * k
    flat = expert_index.reshape(-1).astype(jnp.int32)
    if token_mask is not None:
        flat = jnp.where(jnp.repeat(token_mask, k), flat, n_experts)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sorted_e = jnp.take(flat, order)
    offsets = jnp.searchsorted(
        sorted_e, jnp.arange(n_experts + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    # rank within the expert's segment == position in its capacity queue
    pos_sorted = jnp.arange(nk, dtype=jnp.int32) - jnp.take(offsets, sorted_e)
    pos = jnp.zeros((nk,), jnp.int32).at[order].set(pos_sorted).reshape(n, k)
    keep = pos < capacity
    if token_mask is not None:
        keep = keep & token_mask[:, None]
    return DispatchPlan(
        expert_index=expert_index.astype(jnp.int32),
        order=order,
        offsets=offsets,
        pos=pos,
        keep=keep,
        capacity=capacity,
        top_k=k,
    )


def compute_scores(logits: jnp.ndarray, cfg: RouterConfig) -> jnp.ndarray:
    """Gating function G. Paper / minimind: softmax over experts."""
    logits = logits.astype(cfg.router_dtype)
    if cfg.score_fn == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def route(
    logits: jnp.ndarray,
    state: Dict[str, jnp.ndarray],
    cfg: RouterConfig,
    *,
    local_shards: int = 1,
    token_mask=None,
) -> RouterOutput:
    """Route a flattened batch of tokens.

    logits: (n, m) router logits (pre-gating-function).
    state:  {'q': (m,)} carried vector (ADMM warm start / Loss-Free bias /
      φ-correction); methods add their own leaves (bip forecast:
      'q_ema'/'q_err' EMAs; lpr: 'proto' prototype matrix). Unrecognized
      keys pass through untouched.
    token_mask: optional (n,) bool — serving padding rows are False; they
      still get selections (static shapes) but are excluded from every
      state update and loss, so the carried q tracks real traffic only
      even when decode-heavy chunks are mostly padding (DESIGN.md §Serving).
      Strategies whose selection is not per-token causal (expert_choice)
      reject the masked/serving path outright.
    """
    n, m = logits.shape
    assert m == cfg.n_experts, (m, cfg.n_experts)
    bal = balancers.get_balancer(cfg.strategy)
    bal.check_config(cfg)
    if token_mask is not None and not bal.serving_ok:
        raise NotImplementedError(
            f"strategy {cfg.strategy!r} is training-only: its selection for "
            "one token depends on the whole batch (an expert's top-C can "
            "evict a token when later tokens arrive), so the masked "
            "serving/decode path would break causality."
        )
    with named_span("router/scores"):
        s = compute_scores(logits, cfg)
    # carry every state key through unchanged unless a hook updates it, so
    # the router-state pytree structure is stable across scan/loop carries
    new_state = dict(state)

    if cfg.guard_duals:
        with named_span("router/score_adjust"):
            # dual-health watchdog: the balancer's guarded keys (q, plus e.g.
            # the bip forecaster EMAs) are one coupled carry, so any
            # non-finite/runaway entry in any of them resets them all to safe
            # init (zeros — the fresh-layer warm start). jnp.where on the
            # scalar verdict keeps healthy carries bitwise unchanged, so the
            # watchdog is free to leave enabled.
            gkeys = bal.guard_keys(state)
            vecs = [state[k] for k in gkeys]
            stacked = jnp.concatenate(vecs) if len(vecs) > 1 else vecs[0]
            _, dual_healthy = ref_bip.sanitize_duals(stacked, cfg.dual_abs_limit)
            for k in gkeys:
                new_state[k] = jnp.where(
                    dual_healthy, state[k], jnp.zeros_like(state[k])
                )
            # the hooks below must read the sanitized carry (a copy, so later
            # new_state updates cannot leak into the hooks' view of `state`)
            state = dict(new_state)

    # sync='global': state updates run with psum-reduced statistics over the
    # data axes, so the carried state converges identically on every shard
    # (DESIGN.md §Global-sync). Empty data_axes (single device, or a caller
    # outside shard_map) degrades to the plain per-batch update.
    global_axes = tuple(cfg.data_axes) if cfg.sync == "global" else ()

    with named_span("router/score_adjust"):
        adjusted = bal.score_adjust(
            s, state, cfg,
            token_mask=token_mask, axis_names=global_axes,
            local_shards=local_shards,
        )
    # hooks may return (corrected, updates) or (corrected, updates,
    # telemetry): the optional third dict carries method-specific health
    # scalars (e.g. bip forecaster error / window-hit rate) straight into
    # the metrics — already-computed values only, never extra collectives
    if len(adjusted) == 3:
        corrected, pre_updates, hook_telemetry = adjusted
    else:
        corrected, pre_updates = adjusted
        hook_telemetry = {}
    new_state.update(pre_updates)
    with named_span("router/select"):
        w, idx = bal.select(s, corrected, cfg)
    with named_span("router/aux_loss"):
        aux = bal.aux_loss(s, idx, cfg, token_mask)
    with named_span("router/update_state"):
        new_state.update(
            bal.update_state(
                s, idx, state, cfg, token_mask=token_mask, axis_names=global_axes
            )
        )
    with named_span("router/metrics"):
        metrics = dict(balancers.router_metrics(bal, s, w, idx, cfg))
        metrics.update(hook_telemetry)
        # dual-carry magnitude: every strategy carries 'q' (bias / dual
        # price / log-correction), so its sup-norm is a universal health signal
        metrics["q_abs_max"] = jnp.max(jnp.abs(new_state["q"]))
    return RouterOutput(
        combine_weights=w,
        expert_index=idx,
        state={k: lax.stop_gradient(v) for k, v in new_state.items()},
        aux_loss=aux,
        metrics=metrics,
    )


__all__ = [
    "DispatchPlan",
    "compute_scores",
    "init_router_state",
    "make_dispatch_plan",
    "route",
    "RouterConfig",
    "RouterOutput",
]
