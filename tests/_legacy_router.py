"""Frozen pre-registry `route()` — the parity oracle for the balancer API.

This is a verbatim snapshot of `repro.core.router.route` (and its private
helpers) as it stood BEFORE the pluggable-balancer refactor: the four-way
strategy if/elif over topk / aux_loss / lossfree / bip, including the
masked serving path, the sync='global' threshold branch, the forecaster
EMA updates, and the dual-health watchdog. tests/test_balancers.py runs
this next to the registry-backed route() and asserts bitwise-identical
RouterOutput fields and state trajectories. Do not "fix" or modernize this
file — its value is being the old code. The one deliberate change since the
snapshot: BIP's masked (serving) path runs the config's own dual solver with
the mask, as route() does, instead of a masked bisection fallback.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ref_bip
from repro.core.metrics import balance_metrics
from repro.core.types import RouterConfig, RouterOutput


def compute_scores(logits: jnp.ndarray, cfg: RouterConfig) -> jnp.ndarray:
    logits = logits.astype(cfg.router_dtype)
    if cfg.score_fn == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    return jax.nn.sigmoid(logits)


def _topk_select(
    s: jnp.ndarray, corrected: jnp.ndarray, cfg: RouterConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    _, idx = lax.top_k(corrected, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32)


def _aux_loss(
    s: jnp.ndarray, idx: jnp.ndarray, cfg: RouterConfig, token_mask=None
) -> jnp.ndarray:
    n, m = s.shape
    onehot = jax.nn.one_hot(idx, m, dtype=s.dtype)  # (n, k, m)
    if token_mask is not None:
        w = token_mask.astype(s.dtype)
        n_eff = jnp.maximum(jnp.sum(w), 1.0)
        f = lax.stop_gradient((onehot * w[:, None, None]).sum(axis=(0, 1))) * (
            m / (cfg.top_k * n_eff)
        )
        p_mean = jnp.sum(s * w[:, None], axis=0) / n_eff
    else:
        f = lax.stop_gradient(onehot.sum(axis=(0, 1))) * (m / (cfg.top_k * n))
        p_mean = s.mean(axis=0)
    return cfg.aux_loss_alpha * jnp.sum(f * p_mean)


def _bip_q(s: jnp.ndarray, q0: jnp.ndarray, cfg: RouterConfig,
           token_mask=None) -> jnp.ndarray:
    if cfg.use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.bip_dual_update(
            s, q0, top_k=cfg.top_k, n_iters=cfg.bip_iters, token_mask=token_mask
        )
    q, _ = ref_bip.bip_dual_update(
        s, q0, top_k=cfg.top_k, n_iters=cfg.bip_iters, token_mask=token_mask
    )
    return q


def legacy_route(
    logits: jnp.ndarray,
    state: Dict[str, jnp.ndarray],
    cfg: RouterConfig,
    *,
    local_shards: int = 1,
    token_mask=None,
) -> RouterOutput:
    """The pre-refactor route() body, verbatim (warn-once calls dropped)."""
    n, m = logits.shape
    assert m == cfg.n_experts, (m, cfg.n_experts)
    s = compute_scores(logits, cfg)
    q0 = state["q"]
    aux = jnp.zeros((), dtype=cfg.router_dtype)
    new_q = q0
    new_state = dict(state)

    if cfg.guard_duals:
        fkeys = [k for k in ("q_ema", "q_err") if k in state]
        stacked = jnp.concatenate([q0] + [state[k] for k in fkeys]) if fkeys else q0
        _, dual_healthy = ref_bip.sanitize_duals(stacked, cfg.dual_abs_limit)
        q0 = jnp.where(dual_healthy, q0, jnp.zeros_like(q0))
        for k in fkeys:
            new_state[k] = jnp.where(
                dual_healthy, state[k], jnp.zeros_like(state[k])
            )
        state = new_state
        new_q = q0

    global_axes = tuple(cfg.data_axes) if cfg.sync == "global" else ()

    if cfg.strategy == "bip":
        if cfg.sync == "global" and cfg.use_kernel:
            from repro.kernels import ops as kernel_ops

            q = kernel_ops.bip_dual_update(
                lax.stop_gradient(s), q0,
                top_k=cfg.top_k, n_iters=cfg.bip_iters,
                axis_names=global_axes, token_mask=token_mask,
            )
            corrected = s - q[None, :]
            new_q = q
        elif cfg.sync == "global":
            use_forecast = cfg.forecast and "q_ema" in state
            window = None
            if use_forecast:
                half = cfg.forecast_margin * state["q_err"] + cfg.forecast_floor
                window = (state["q_ema"] - half, state["q_ema"] + half)
            q, _, t = ref_bip.bip_dual_update_global(
                lax.stop_gradient(s), q0,
                top_k=cfg.top_k, n_iters=cfg.bip_iters,
                token_mask=token_mask, axis_names=global_axes,
                n_bisect=cfg.n_bisect, fanout=cfg.bisect_fanout,
                score_bounds=(0.0, 1.0), window=window, with_stats=True,
            )
            if use_forecast:
                d = cfg.forecast_decay
                err = jnp.abs(t - state["q_ema"])
                new_state["q_ema"] = d * state["q_ema"] + (1.0 - d) * t
                new_state["q_err"] = d * state["q_err"] + (1.0 - d) * err
            corrected = s - q[None, :]
            new_q = q
        elif local_shards > 1 and cfg.sync == "local":
            s_grp = lax.stop_gradient(s).reshape(local_shards, n // local_shards, m)
            m_grp = None if token_mask is None else token_mask.reshape(local_shards, -1)
            q_grp = jax.vmap(lambda sg, mg: _bip_q(sg, q0, cfg, mg))(
                s_grp, m_grp
            )  # (S, m)
            corrected = (
                s.reshape(local_shards, -1, m) - q_grp[:, None, :]
            ).reshape(n, m)
            new_q = q_grp.mean(axis=0)
        else:
            q = _bip_q(lax.stop_gradient(s), q0, cfg, token_mask)
            corrected = s - q[None, :]
            new_q = q
        w, idx = _topk_select(s, corrected, cfg)
        if not cfg.bip_warm_start:
            new_q = jnp.zeros_like(q0)

    elif cfg.strategy == "lossfree":
        corrected = s + q0[None, :]
        w, idx = _topk_select(s, corrected, cfg)
        onehot = jax.nn.one_hot(idx, m, dtype=cfg.router_dtype)
        if token_mask is not None:
            onehot = onehot * token_mask.astype(cfg.router_dtype)[:, None, None]
        load = lax.stop_gradient(onehot.sum(axis=(0, 1)))
        if global_axes:
            load = lax.psum(load, global_axes)
        err = load.mean() - load
        new_q = q0 + cfg.lossfree_lr * jnp.sign(err)

    elif cfg.strategy == "aux_loss":
        w, idx = _topk_select(s, s, cfg)
        aux = _aux_loss(s, idx, cfg, token_mask)

    else:  # 'topk'
        w, idx = _topk_select(s, s, cfg)

    metrics = balance_metrics(idx, m, cfg.top_k)
    new_state["q"] = new_q
    return RouterOutput(
        combine_weights=w,
        expert_index=idx,
        state={k: lax.stop_gradient(v) for k, v in new_state.items()},
        aux_loss=aux,
        metrics=metrics,
    )
