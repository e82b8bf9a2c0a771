"""Mixture-of-Experts FFN with BIP-balanced routing and expert parallelism.

Two execution paths, same math:

* `moe_ffn_local` — plain jnp scatter/gather on one logical array. Used on
  single-device (tests, the paper-reproduction training runs) and as the
  semantic reference for the distributed path.

* `moe_ffn_ep` — shard_map over the production mesh. Activations arrive
  sharded over the data axes and replicated over 'model'; experts are sharded
  over 'model' (expert parallelism). Each model-rank routes its replicated
  token block, gathers the tokens bound for ITS experts into a static
  (m_local, C, d) buffer, runs the expert GEMMs, and contributes its experts'
  outputs to a psum over 'model'. There is no explicit all-to-all: dispatch
  is a local gather (tokens are already present via model-axis replication)
  and combine rides the same all-reduce tensor parallelism already pays for
  the FFN block. See DESIGN.md §6.

Capacity: C = ceil(k·n/m · capacity_factor). Because BIP routing bounds
per-expert load at ~(1 + MaxVio)·k·n/m with MaxVio ≲ 0.2 from the first step,
capacity_factor 1.25 loses almost nothing — the paper's systems payoff.
Tokens beyond capacity are dropped (contribute zero), standard MoE practice.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import metrics as core_metrics
from repro.core import get_balancer, make_dispatch_plan, route
from repro.core.types import RouterConfig
from repro.telemetry.trace import named_span

Params = Dict[str, jnp.ndarray]


def router_config(cfg: ModelConfig, data_axes: Tuple[str, ...] = ()) -> RouterConfig:
    """RouterConfig for this model — one conversion point (RoutingSpec shim)."""
    return cfg.routing.to_router_config(data_axes=data_axes)


def _state_specs(router_state):
    """Replicated PartitionSpec pytree matching the router-state dict.

    Every router-state leaf (q and the forecaster EMAs (m,), lpr's (m, m)
    prototype matrix) is replicated across the mesh, so the spec tree is
    P(None) everywhere (trailing dims pad with None) — built from the live
    state so new keys never need a hand-written spec.
    """
    return jax.tree.map(lambda _: P(None), router_state)


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    r = cfg.routing
    return max(
        int(math.ceil(r.top_k * n_tokens / r.n_experts * r.capacity_factor)), 1
    )


# ------------------------------------------------------------------- init


def init_moe(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    m = cfg.routing.n_experts
    keys = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "w_router": jax.random.normal(keys[0], (d, m), jnp.float32) * s_in,
        "w_gate": jax.random.normal(keys[1], (m, d, f), cfg.param_dtype) * s_in,
        "w_up": jax.random.normal(keys[2], (m, d, f), cfg.param_dtype) * s_in,
        "w_down": jax.random.normal(keys[3], (m, f, d), cfg.param_dtype)
        * (s_out / math.sqrt(2 * cfg.n_layers)),
    }
    return p


def _flat_axis_index(mesh, axes: Tuple[str, ...]):
    """Row-major flat index across several mesh axes (inside shard_map)."""
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + lax.axis_index(a)
    return idx


# Above this many tokens per invocation, gathering activations (ep2d) costs
# more than gathering weight shards (ep); below it, ep2d wins outright —
# for decode it removes the per-layer weight gather entirely. Measured via
# the dry-run roofline (EXPERIMENTS.md §Perf).
EP2D_TOKEN_THRESHOLD = 32768


def moe_ffn(params, x, router_state, cfg, mesh_ctx, token_mask=None):
    """Dispatch to the configured implementation ('auto' picks by size).

    token_mask (n,) bool marks real tokens; False rows (serving padding)
    still receive selections (static shapes) but are excluded from
    dispatch, capacity, the router-state update, and the load metrics.
    Every path supports it: the EP impls shard the mask alongside the
    tokens and psum the real-token counts, so EP-sharded serving reports
    the same masked load histograms as the single-device engine
    (DESIGN.md §Serving).
    """
    if mesh_ctx is not None and getattr(mesh_ctx, "use_ep", False):
        impl_name = cfg.routing.moe_impl
        if impl_name == "auto":
            # selective gather wins at every scale measured (§Perf); tiny
            # token counts route through its ep2d fallback automatically
            impl_name = "ep2ds"
        impl = {"ep2d": moe_ffn_ep2d, "ep2ds": moe_ffn_ep2ds, "ep": moe_ffn_ep}[
            impl_name
        ]
        return impl(
            params,
            x,
            router_state,
            cfg,
            mesh_ctx.mesh,
            data_axes=mesh_ctx.data_axes,
            model_axis=mesh_ctx.model_axis,
            token_mask=token_mask,
        )
    return moe_ffn_local(params, x, router_state, cfg, token_mask=token_mask)


# -------------------------------------------------- dispatch bookkeeping
#
# The hot path builds a sort-based ragged plan (core.router.make_dispatch_plan):
# argsort + segment offsets, pack/combine as pure gathers. `_dispatch_plan`
# below is the historical one-hot/cumsum formulation, kept as the semantic
# oracle for the parity suite (tests/test_moe_dispatch.py), the property
# tests, and benchmarks/moe_dispatch.py's old-vs-new comparison.


def _dispatch_plan(
    expert_index: jnp.ndarray,  # (n, k) int32
    n_experts: int,
    capacity: int,
    token_mask: Optional[jnp.ndarray] = None,  # (n,) bool; False never dispatches
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Position of every (token, slot) inside its expert's capacity queue.

    Returns (pos (n, k) int32, keep (n, k) bool). Queue order is token order
    (earlier tokens win capacity), slot-major within a token. Masked tokens
    (serving padding) are excluded from the queues entirely: they neither
    occupy capacity nor displace real tokens, so a padded batch dispatches
    identically to the same real tokens alone.
    """
    n, k = expert_index.shape
    flat = expert_index.reshape(-1)  # (n*k,) — token-major, slot-minor
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # (n*k, m)
    if token_mask is not None:
        onehot = onehot * jnp.repeat(token_mask, k).astype(jnp.int32)[:, None]
    pos_flat = jnp.cumsum(onehot, axis=0) - 1  # position within expert queue
    pos = jnp.take_along_axis(pos_flat, flat[:, None], axis=1)[:, 0]
    pos = pos.reshape(n, k)
    keep = pos < capacity
    if token_mask is not None:
        keep = keep & token_mask[:, None]
    return pos, keep


@named_span("moe/gemm")
def _expert_ffn(
    w_gate: jnp.ndarray,  # (e, d, f)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # (e, f, d)
    xb: jnp.ndarray,  # (e, c, d)
    cfg: ModelConfig,
) -> jnp.ndarray:
    dt = cfg.compute_dtype
    if cfg.routing.use_kernel and cfg.act == "silu":
        from repro.kernels import ops as kernel_ops  # lazy: avoid import cycle

        return kernel_ops.expert_ffn(
            xb.astype(dt),
            w_gate.astype(dt),
            w_up.astype(dt),
            w_down.astype(dt),
        )
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xb, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", act(g) * u, w_down.astype(dt))


# -------------------------------------------------------- single-device


def moe_ffn_local(
    params: Params,
    x: jnp.ndarray,  # (n, d) flattened tokens
    router_state: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    token_mask: Optional[jnp.ndarray] = None,  # (n,) bool
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Reference path. Returns (y, new_router_state, aux_loss, metrics).

    The router sees the whole batch, so the duals are the paper's global
    semantics under either sync mode (data_axes=()); this is the trajectory
    the sync='global' mesh paths are parity-tested against.
    """
    n, d = x.shape
    m = cfg.routing.n_experts
    cap = expert_capacity(n, cfg)
    rcfg = router_config(cfg, data_axes=())

    with named_span("router/scores"):
        logits = jnp.einsum("nd,dm->nm", x.astype(jnp.float32), params["w_router"])
    out = route(logits, router_state, rcfg, token_mask=token_mask)
    with named_span("moe/dispatch"):
        plan = make_dispatch_plan(out.expert_index, m, cap, token_mask)
        buf = plan.pack(x)  # (m, cap, d) by gather — no one-hot, no scatter
    y = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"], buf, cfg)
    with named_span("moe/combine"):
        y_tok = plan.combine(y, out.combine_weights)

    mets = out.metrics
    if token_mask is not None:
        # balance metrics over the real tokens only (padding routes as
        # uniform filler and would flatten the reported load); the plan's
        # segment counts already exclude masked rows. Counts stay int32
        # (telemetry dtype audit — no float round-trip).
        load = plan.counts
        mean_load = jnp.maximum(
            jnp.sum(token_mask) * cfg.routing.top_k / m, 1e-9
        )
        mets = dict(mets)
        mets.update(load=load, max_vio=jnp.max(load) / mean_load - 1.0)
    return y_tok, out.state, out.aux_loss, mets


# ------------------------------------------------------ expert parallel


def moe_ffn_ep2d(
    params: Params,
    x: jnp.ndarray,  # (n_global, d), sharded over data axes
    router_state: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    mesh,
    *,
    data_axes: Tuple[str, ...],
    model_axis: str,
    token_mask: Optional[jnp.ndarray] = None,  # (n_global,) bool
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray, Dict[str, jnp.ndarray]]:
    """2D expert-parallel path: gather ACTIVATIONS, never gather weights.

    Expert weights stay fully sharded at rest AND at use: experts over
    'model', each expert's hidden f over the data axes. Tokens are
    all-gathered over data inside the block (every rank sees the full
    microbatch), each rank computes its (m_loc, f_loc) slice for all tokens,
    and the combine is one reduce-scatter over data + psum over model.

    vs the FSDP path (moe_ffn_ep + data-sharded weights): communication per
    layer drops from O(expert_weight_bytes) to O(token_bytes) — for
    arctic-480b decode that is 1.67 GB -> ~2 MB per layer (§Perf). Expert
    gradients become fully local (each rank owns its weight shard and holds
    all tokens), removing the gradient reduce-scatter for expert params.
    """
    m = cfg.routing.n_experts
    k = cfg.routing.top_k
    n_global, d = x.shape
    n_data_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    token_sharded = (
        n_data_shards > 1
        and n_global % n_data_shards == 0
        and n_global >= n_data_shards
    )
    ep = mesh.shape[model_axis]
    assert m % ep == 0, (m, ep)
    m_loc = m // ep
    f = cfg.moe_d_ff or cfg.d_ff
    f_shards = n_data_shards if (token_sharded and f % n_data_shards == 0) else 1
    cap = expert_capacity(n_global, cfg)
    # data_axes deliberately (): routing below sees the GATHERED token batch,
    # so the duals are paper-global by construction under either sync mode —
    # psum'ing the order statistics on top would double-count every token
    rcfg = router_config(cfg)

    x_spec = P(data_axes if token_sharded else None, None)
    wf_spec = P(model_axis, None, data_axes if f_shards > 1 else None)
    wd_spec = P(model_axis, data_axes if f_shards > 1 else None, None)

    def block(x_loc, w_router, w_gate, w_up, w_down, q_state, *mask_args):
        rank = lax.axis_index(model_axis)
        mask_loc = mask_args[0] if mask_args else None
        if token_sharded:
            x_all = lax.all_gather(x_loc, data_axes, axis=0, tiled=True)
            mask_all = (
                lax.all_gather(mask_loc, data_axes, axis=0, tiled=True)
                if mask_loc is not None
                else None
            )
        else:
            x_all = x_loc  # already replicated
            mask_all = mask_loc
        with named_span("router/scores"):
            logits = jnp.einsum("nd,dm->nm", x_all.astype(jnp.float32), w_router)
        out = route(logits, q_state, rcfg, token_mask=mask_all)
        with named_span("moe/dispatch"):
            plan = make_dispatch_plan(out.expert_index, m, cap, mask_all)
            # gather THIS rank's expert segments straight out of the sort order
            buf = plan.pack(x_all, expert_offset=rank * m_loc, n_local=m_loc)

        # expert FFN on the local (m_loc, f_loc) weight shard; y is partial
        # over f, completed by the psum below
        y = _expert_ffn(w_gate, w_up, w_down, buf, cfg)

        with named_span("moe/combine"):
            y_tok = plan.combine(y, out.combine_weights, expert_offset=rank * m_loc)
            y_tok = lax.psum(y_tok, model_axis)
        if token_sharded:
            if f_shards > 1:
                y_tok = lax.psum_scatter(
                    y_tok, data_axes, scatter_dimension=0, tiled=True
                )
            else:
                idx = _flat_axis_index(mesh, data_axes)
                n_loc = n_global // n_data_shards
                y_tok = lax.dynamic_slice_in_dim(y_tok, idx * n_loc, n_loc, 0)

        # routing ran on the gathered tokens (global duals regardless of
        # cfg.routing.sync): identical on every data rank, but all_gather
        # outputs are typed varying-over-data — the pmeans are semantic
        # no-ops (NOT cross-shard dual averaging, every rank already holds
        # the converged global q / forecaster EMAs) that re-establish
        # replication for check_vma
        new_state = out.state
        # masked: balance over real tokens only — router_metrics counts the
        # padded rows' placeholder selections; the plan's segment counts
        # already exclude them (mirrors moe_ffn_local)
        load = plan.counts if mask_all is not None else out.metrics["load"]
        n_real = (
            jnp.sum(mask_all.astype(jnp.int32)) if mask_all is not None else None
        )
        dropped = out.metrics["dropped_frac_cap1"]
        aux = out.aux_loss
        if token_sharded:
            new_state = jax.tree.map(lambda v: lax.pmean(v, data_axes), new_state)
            # every data rank routed the same gathered batch, so the int32
            # count histograms are replicated: psum // n is the exact
            # integer identity (pmean would round-trip through float)
            load = lax.psum(load, data_axes) // n_data_shards
            dropped = lax.pmean(dropped, data_axes)
            aux = lax.pmean(aux, data_axes)
            if n_real is not None:
                n_real = lax.psum(n_real, data_axes) // n_data_shards
        if n_real is not None:
            mean_load = jnp.maximum(n_real * k / m, 1e-9)
        else:
            mean_load = (n_global * k) / m
        mets = {
            "load": load,
            "max_vio": jnp.max(load) / mean_load - 1.0,
            "dropped_frac_cap1": dropped,
        }
        return y_tok, new_state, aux, mets

    in_specs = [
        x_spec,
        P(None, None),
        wf_spec,
        wf_spec,
        wd_spec,
        _state_specs(router_state),
    ]
    args = [
        x,
        params["w_router"],
        params["w_gate"],
        params["w_up"],
        params["w_down"],
        router_state,
    ]
    if token_mask is not None:
        in_specs.append(P(data_axes if token_sharded else None))
        args.append(token_mask)
    fn = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            x_spec,
            _state_specs(router_state),
            P(),
            {"load": P(), "max_vio": P(), "dropped_frac_cap1": P()},
        ),
        check_vma=True,
    )
    return fn(*args)


def moe_ffn_ep2ds(
    params: Params,
    x: jnp.ndarray,  # (n_global, d), sharded over data axes
    router_state: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    mesh,
    *,
    data_axes: Tuple[str, ...],
    model_axis: str,
    token_mask: Optional[jnp.ndarray] = None,  # (n_global,) bool
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Selective 2D expert parallelism — gather only DISPATCHED tokens.

    Weights stay fully sharded like ep2d (experts→model, f→data), but
    instead of all-gathering the raw activations, each data rank dispatches
    its local tokens into per-expert capacity buffers FIRST and the
    (m_loc, cap_local, d) buffers are what crosses the wire:

        gather bytes / layer = k·n·cf/m · m_loc · d  (≈ x_bytes · k·cf/ep)

    — ~8x less than ep2d's full-token gather at arctic's k=2, ep=16, and it
    replaces moe_ffn_ep's per-layer expert-weight gather entirely. Combine
    is one psum_scatter over data (sums f-partials AND returns each source
    rank its own slice) plus the model-axis psum shared with TP.
    See EXPERIMENTS.md §Perf for the measured before/after.
    """
    m = cfg.routing.n_experts
    k = cfg.routing.top_k
    n_global, d = x.shape
    n_data_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    token_sharded = (
        n_data_shards > 1
        and n_global % n_data_shards == 0
        and n_global >= n_data_shards
    )
    if not token_sharded:
        return moe_ffn_ep2d(
            params, x, router_state, cfg, mesh,
            data_axes=data_axes, model_axis=model_axis, token_mask=token_mask,
        )
    ep = mesh.shape[model_axis]
    assert m % ep == 0, (m, ep)
    m_loc = m // ep
    n_loc = n_global // n_data_shards
    cap = expert_capacity(n_loc, cfg)
    f = cfg.moe_d_ff or cfg.d_ff
    f_sharded = f % n_data_shards == 0
    # sync='global': route() runs the psum'd threshold dual update over the
    # data axes, so each rank routes its local shard against the SAME duals
    # the unsharded reference would compute (DESIGN.md §Global-sync)
    rcfg = router_config(
        cfg, data_axes=data_axes if cfg.routing.sync == "global" else ()
    )

    wf_spec = P(model_axis, None, data_axes if f_sharded else None)
    wd_spec = P(model_axis, data_axes if f_sharded else None, None)

    def block(x_loc, w_router, w_gate, w_up, w_down, q_state, *mask_args):
        rank = lax.axis_index(model_axis)
        mask_loc = mask_args[0] if mask_args else None
        with named_span("router/scores"):
            logits = jnp.einsum("nd,dm->nm", x_loc.astype(jnp.float32), w_router)
        out = route(logits, q_state, rcfg, token_mask=mask_loc)
        with named_span("moe/dispatch"):
            plan = make_dispatch_plan(out.expert_index, m, cap, mask_loc)
            buf = plan.pack(x_loc, expert_offset=rank * m_loc, n_local=m_loc)
            # selective gather: only dispatched tokens cross the data axis
            buf_all = lax.all_gather(buf, data_axes, axis=1, tiled=True)
            # (m_loc, n_data * cap, d)

        y = _expert_ffn(w_gate, w_up, w_down, buf_all, cfg)

        with named_span("moe/combine"):
            if f_sharded:
                # y is partial over f: sum partials and hand every source rank
                # its own slice back in one collective
                y = lax.psum_scatter(y, data_axes, scatter_dimension=1, tiled=True)
            else:
                # weights were replicated over data: y is complete; just take
                # this rank's slice of the gathered axis
                idx = _flat_axis_index(mesh, data_axes)
                y = lax.dynamic_slice_in_dim(y, idx * cap, cap, axis=1)
            # (m_loc, cap, d), complete values for THIS rank's dispatched tokens
            y_tok = plan.combine(y, out.combine_weights, expert_offset=rank * m_loc)
            y_tok = lax.psum(y_tok, model_axis)

        # global sync: the whole state dict (q + forecaster EMAs) converged
        # identically per shard (vma-replicated, no averaging); local sync:
        # pmean each balancer-declared carried leaf (the bip warm-start q,
        # lpr's prototypes) across shards so the replicated-state invariant
        # holds — keys outside local_avg_keys (forecaster EMAs) are
        # untouched by the local path and stay replicated
        if cfg.routing.sync == "global":
            new_state = out.state
        else:
            new_state = dict(out.state)
            for key in get_balancer(cfg.routing.strategy).local_avg_keys:
                new_state[key] = lax.pmean(out.state[key], data_axes)
        if mask_loc is not None:
            # per-expert counts of real tokens only (plan excludes masked
            # rows); normalize by the psum'd real-token count
            load = lax.psum(plan.counts, data_axes)
            n_real = lax.psum(jnp.sum(mask_loc.astype(jnp.int32)), data_axes)
            mean_load = jnp.maximum(n_real * k / m, 1e-9)
        else:
            load = lax.psum(out.metrics["load"], data_axes)
            mean_load = (n_global * k) / m
        mets = {
            "load": load,
            "max_vio": jnp.max(load) / mean_load - 1.0,
            "dropped_frac_cap1": lax.pmean(
                out.metrics["dropped_frac_cap1"], data_axes
            ),
        }
        aux = lax.pmean(out.aux_loss, data_axes)
        return y_tok, new_state, aux, mets

    in_specs = [
        P(data_axes, None),
        P(None, None),
        wf_spec,
        wf_spec,
        wd_spec,
        _state_specs(router_state),
    ]
    args = [
        x,
        params["w_router"],
        params["w_gate"],
        params["w_up"],
        params["w_down"],
        router_state,
    ]
    if token_mask is not None:
        in_specs.append(P(data_axes))
        args.append(token_mask)
    fn = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(data_axes, None),
            _state_specs(router_state),
            P(),
            {"load": P(), "max_vio": P(), "dropped_frac_cap1": P()},
        ),
        check_vma=True,
    )
    return fn(*args)


def moe_ffn_ep(
    params: Params,
    x: jnp.ndarray,  # (n_global, d), sharded over data axes
    router_state: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    mesh,
    *,
    data_axes: Tuple[str, ...],
    model_axis: str,
    token_mask: Optional[jnp.ndarray] = None,  # (n_global,) bool
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel path under shard_map (see module docstring)."""
    m = cfg.routing.n_experts
    k = cfg.routing.top_k
    n_global, d = x.shape
    n_data_shards = int(np.prod([mesh.shape[a] for a in data_axes]))
    if n_global % n_data_shards != 0 or n_global < n_data_shards:
        # tiny token counts (single-request decode): replicate tokens over
        # the data axes instead of sharding them.
        data_axes = ()
        n_data_shards = 1
    ep = mesh.shape[model_axis]
    assert m % ep == 0, (m, ep)
    m_loc = m // ep
    n_loc = n_global // n_data_shards
    cap = expert_capacity(n_loc, cfg)
    rcfg = router_config(cfg, data_axes=data_axes if cfg.routing.sync == "global" else ())

    def block(x_loc, w_router, w_gate, w_up, w_down, q_state, *mask_args):
        # x_loc: (n_loc, d); w_gate: (m_loc, d, f); q_state: {'q': (m,)}
        rank = lax.axis_index(model_axis)
        mask_loc = mask_args[0] if mask_args else None
        with named_span("router/scores"):
            logits = jnp.einsum("nd,dm->nm", x_loc.astype(jnp.float32), w_router)
        out = route(logits, q_state, rcfg, token_mask=mask_loc)
        with named_span("moe/dispatch"):
            plan = make_dispatch_plan(out.expert_index, m, cap, mask_loc)
            # pack only the slots routed to THIS rank's experts (pure gather)
            buf = plan.pack(x_loc, expert_offset=rank * m_loc, n_local=m_loc)

        y = _expert_ffn(w_gate, w_up, w_down, buf, cfg)

        with named_span("moe/combine"):
            y_tok = plan.combine(y, out.combine_weights, expert_offset=rank * m_loc)
            # combine across expert-owners (rides the TP all-reduce)
            y_tok = lax.psum(y_tok, model_axis)

        # router state: sync='global' duals already converged identically on
        # every shard (psum'd order statistics inside route, vma-replicated);
        # sync='local' averages the per-shard carried leaves (q warm start,
        # lpr prototypes) into the replicated state — keys outside
        # local_avg_keys (forecaster EMAs) are untouched by the local path
        if data_axes and cfg.routing.sync != "global":
            new_state = dict(out.state)
            for key in get_balancer(cfg.routing.strategy).local_avg_keys:
                new_state[key] = lax.pmean(out.state[key], data_axes)
        else:
            new_state = out.state
        # global balance metrics: sum local loads over data shards
        load = plan.counts if mask_loc is not None else out.metrics["load"]
        n_real = (
            jnp.sum(mask_loc.astype(jnp.int32)) if mask_loc is not None else None
        )
        dropped = out.metrics["dropped_frac_cap1"]
        aux = out.aux_loss
        if data_axes:
            load = lax.psum(load, data_axes)
            dropped = lax.pmean(dropped, data_axes)
            aux = lax.pmean(aux, data_axes)
            if n_real is not None:
                n_real = lax.psum(n_real, data_axes)
        if n_real is not None:
            mean_load = jnp.maximum(n_real * k / m, 1e-9)
        else:
            mean_load = (n_global * k) / m
        mets = {
            "load": load,
            "max_vio": jnp.max(load) / mean_load - 1.0,
            "dropped_frac_cap1": dropped,
        }
        return y_tok, new_state, aux, mets

    in_specs = [
        P(data_axes if data_axes else None, None),  # x
        P(None, None),  # w_router (replicated)
        P(model_axis, None, None),  # w_gate
        P(model_axis, None, None),  # w_up
        P(model_axis, None, None),  # w_down
        _state_specs(router_state),  # router state replicated
    ]
    args = [
        x,
        params["w_router"],
        params["w_gate"],
        params["w_up"],
        params["w_down"],
        router_state,
    ]
    if token_mask is not None:
        in_specs.append(P(data_axes if data_axes else None))
        args.append(token_mask)
    f = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(data_axes if data_axes else None, None),
            _state_specs(router_state),
            P(),
            {"load": P(), "max_vio": P(), "dropped_frac_cap1": P()},
        ),
        check_vma=True,
    )
    return f(*args)
