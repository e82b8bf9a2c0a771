"""Pluggable balancer registry — the routing strategy surface.

Every load-balancing method the repo can sweep is a `Balancer` subclass
registered by name. `route()` (core/router.py) is a thin orchestrator that
resolves `cfg.strategy` here and calls the hook protocol:

    init_state(cfg)                      -> per-layer carried state dict
    score_adjust(s, state, cfg, ...)     -> (corrected scores, state updates)
                                            or (corrected, updates, telemetry)
                                            [pre-selection: dual solves,
                                             bias/multiplier application,
                                             prototype affinities; the
                                             optional telemetry dict of
                                             already-computed health scalars
                                             is folded into the metrics]
    select(s, corrected, cfg)            -> (combine_weights, expert_index)
                                            [token top-k by default;
                                             expert-choice overrides]
    aux_loss(s, idx, cfg, token_mask)    -> scalar loss (0 by default)
    update_state(s, idx, state, cfg,...) -> state updates
                                            [post-selection: sign/EMA/
                                             multiplicative corrections]
    finalize_metrics(base, s, w, idx)    -> metrics dict (coverage columns
                                            for expert-choice)

Each hook receives the full RouterConfig plus `token_mask` (masked serving
rows, DESIGN.md §Serving) and `axis_names` (the mesh data axes when
cfg.sync='global', else ()), so cross-shard dual sync and masked-serving
semantics come for free to every method: reductions over selections go
through `_global_load`-style psums and masked sums exactly once, here.

The four paper strategies (topk / aux_loss / lossfree / bip) are ports of
the historical `route()` if/elif — bit-identical by construction (the same
jnp ops in the same order; tests/test_balancers.py pins this against the
frozen legacy implementation). phi (φ-Balancing, arxiv 2605.15403), lpr
(Latent Prototype Routing, arxiv 2506.21328) and expert_choice
(core/expert_choice.py, training-only) register behind the same surface.

Adding a method = one module with a @register_balancer subclass; the
launchers, sweeps, and validation all resolve through `registered_balancers`.
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ref_bip
from repro.core.metrics import balance_metrics
from repro.core.types import RouterConfig
from repro.telemetry.trace import named_span

Array = jnp.ndarray
State = Dict[str, Array]

_REGISTRY: Dict[str, "Balancer"] = {}

_warned: set = set()


def _warn_once(key: str, msg: str) -> None:
    """Emit a config-degradation warning once per process (trace-time)."""
    if key not in _warned:
        _warned.add(key)
        warnings.warn(msg, stacklevel=4)


def register_balancer(name: str):
    """Class decorator: instantiate and register a Balancer under `name`."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def registered_balancers() -> Tuple[str, ...]:
    """All registered strategy names, sorted (for error messages / sweeps)."""
    return tuple(sorted(_REGISTRY))


def get_balancer(name: str) -> "Balancer":
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown routing strategy {name!r}; registered: "
            f"{', '.join(registered_balancers())}"
        ) from None


# ---------------------------------------------------------------- protocol


def topk_select(
    s: Array, corrected: Array, cfg: RouterConfig
) -> Tuple[Array, Array]:
    """Top-k on `corrected` scores, gate values gathered from raw `s`."""
    _, idx = lax.top_k(corrected, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32)


class Balancer:
    """Base strategy: plain token-choice top-k, no balancing, no state use.

    Subclasses override the hooks they need; the base implementations are
    exactly the 'topk' semantics (corrected = raw scores, zero aux loss,
    state carried through untouched).

    Class attributes (the per-method capability contract):
      STATE_KEYS      ordered state keys this method owns — sets the
                      dual-watchdog concatenation order (bit-compat with
                      the legacy guard) and which leaves reset on poison.
      local_avg_keys  state keys pmean-averaged across data shards by the
                      EP paths under sync='local' (the warm-start average).
      serving_ok      supports masked serving rows (token_mask) — i.e. the
                      method is causally safe for autoregressive decode.
      uses_kernel     consumes cfg.use_kernel (only bip's ADMM kernel).
      uses_sync       cfg.sync='global' changes this method's semantics
                      (for others the matrix records identical cells).
    """

    name: str = ""
    STATE_KEYS: Tuple[str, ...] = ("q",)
    local_avg_keys: Tuple[str, ...] = ("q",)
    serving_ok: bool = True
    uses_kernel: bool = False
    uses_sync: bool = False

    # -- state ------------------------------------------------------------
    def init_state(self, cfg: RouterConfig) -> State:
        """Fresh per-layer carried state ('q' kept for every method so
        checkpoints stay strategy-portable; see types.init_router_state)."""
        return {"q": jnp.zeros((cfg.n_experts,), dtype=cfg.router_dtype)}

    def guard_keys(self, state: State) -> Tuple[str, ...]:
        """State keys the dual-health watchdog covers, in concat order."""
        return tuple(k for k in self.STATE_KEYS if k in state)

    # -- config hygiene ---------------------------------------------------
    def check_config(self, cfg: RouterConfig) -> None:
        """Warn-once on knob combinations this method silently ignores."""
        if cfg.use_kernel and not self.uses_kernel:
            _warn_once(
                f"kernel-unused-{self.name}",
                f"use_kernel=True only accelerates the 'bip' ADMM dual "
                f"update; strategy {self.name!r} runs the reference path "
                f"and the flag is ignored.",
            )
        if cfg.forecast and self.name != "bip":
            _warn_once(
                f"forecast-unused-{self.name}",
                f"RouterConfig.forecast drives the bip dual forecaster; "
                f"strategy {self.name!r} carries no forecaster state and "
                f"the flag is ignored.",
            )

    # -- hooks ------------------------------------------------------------
    def score_adjust(
        self,
        s: Array,
        state: State,
        cfg: RouterConfig,
        *,
        token_mask: Optional[Array] = None,
        axis_names: tuple = (),
        local_shards: int = 1,
    ) -> Tuple[Array, State]:
        return s, {}

    def select(
        self, s: Array, corrected: Array, cfg: RouterConfig
    ) -> Tuple[Array, Array]:
        return topk_select(s, corrected, cfg)

    def aux_loss(
        self,
        s: Array,
        idx: Array,
        cfg: RouterConfig,
        token_mask: Optional[Array] = None,
    ) -> Array:
        return jnp.zeros((), dtype=cfg.router_dtype)

    def update_state(
        self,
        s: Array,
        idx: Array,
        state: State,
        cfg: RouterConfig,
        *,
        token_mask: Optional[Array] = None,
        axis_names: tuple = (),
    ) -> State:
        return {}

    def finalize_metrics(
        self,
        base: Dict[str, Array],
        s: Array,
        w: Array,
        idx: Array,
        cfg: RouterConfig,
    ) -> Dict[str, Array]:
        return base


# ------------------------------------------------------------- strategies


@register_balancer("topk")
class TopKBalancer(Balancer):
    """Vanilla softmax top-k — no balancing; the collapse-prone baseline."""


@register_balancer("aux_loss")
class AuxLossBalancer(Balancer):
    """Loss-Controlled (GShard/Switch): L_balance = α Σ_j f_j P_j.

    f_j = m/(k n) Σ_i δ_ij  (token fraction, non-differentiable -> stopped),
    P_j = 1/n Σ_i s_ij      (mean gate score, carries the gradient).
    With token_mask, both means run over the real rows only.
    """

    def aux_loss(self, s, idx, cfg, token_mask=None):
        n, m = s.shape
        onehot = jax.nn.one_hot(idx, m, dtype=s.dtype)  # (n, k, m)
        if token_mask is not None:
            w = token_mask.astype(s.dtype)
            n_eff = jnp.maximum(jnp.sum(w), 1.0)
            f = lax.stop_gradient(
                (onehot * w[:, None, None]).sum(axis=(0, 1))
            ) * (m / (cfg.top_k * n_eff))
            p_mean = jnp.sum(s * w[:, None], axis=0) / n_eff
        else:
            f = lax.stop_gradient(onehot.sum(axis=(0, 1))) * (m / (cfg.top_k * n))
            p_mean = s.mean(axis=0)
        return cfg.aux_loss_alpha * jnp.sum(f * p_mean)


def selection_load(
    idx: Array,
    m: int,
    dtype,
    token_mask: Optional[Array] = None,
    axis_names: tuple = (),
) -> Array:
    """Per-expert selection histogram (m,), masked rows excluded, psum'd
    over `axis_names` so sync='global' methods see the global batch.

    The one-hot formulation matches the legacy lossfree update bitwise
    (integer-valued float sums are exact in either order).
    """
    onehot = jax.nn.one_hot(idx, m, dtype=dtype)
    if token_mask is not None:
        onehot = onehot * token_mask.astype(dtype)[:, None, None]
    load = lax.stop_gradient(onehot.sum(axis=(0, 1)))
    if axis_names:
        load = lax.psum(load, axis_names)
    return load


@register_balancer("lossfree")
class LossFreeBalancer(Balancer):
    """Loss-Free (Wang et al. 2024): per-batch sign update of bias b.

    The carried 'q' plays the role of the bias b, ADDED to scores for
    selection; gate values stay the raw scores so b gets no gradient.
    Under sync='global' every shard psums the same selection histogram, so
    the carried bias stays bit-identical across devices.
    """

    uses_sync = True

    def score_adjust(self, s, state, cfg, *, token_mask=None, axis_names=(),
                     local_shards=1):
        return s + state["q"][None, :], {}

    def update_state(self, s, idx, state, cfg, *, token_mask=None, axis_names=()):
        m = s.shape[-1]
        load = selection_load(idx, m, cfg.router_dtype, token_mask, axis_names)
        err = load.mean() - load
        return {"q": state["q"] + cfg.lossfree_lr * jnp.sign(err)}


@register_balancer("bip")
class BIPBalancer(Balancer):
    """BIP-Based Balancing (the paper): per-gate ADMM dual update of q.

    The dual price q is SUBTRACTED from scores for selection; the dual
    solve (reference / Pallas kernel / psum-reduced global threshold
    bisection, plus the EMA forecaster window) happens pre-selection in
    score_adjust — the branch structure is the legacy route() body moved
    here verbatim (DESIGN.md §3.3 / §Global-sync).
    """

    STATE_KEYS = ("q", "q_ema", "q_err")
    uses_kernel = True
    uses_sync = True

    def init_state(self, cfg):
        state = {"q": jnp.zeros((cfg.n_experts,), dtype=cfg.router_dtype)}
        if cfg.forecast:
            state["q_ema"] = jnp.zeros((cfg.n_experts,), dtype=cfg.router_dtype)
            state["q_err"] = jnp.zeros((cfg.n_experts,), dtype=cfg.router_dtype)
        return state

    def check_config(self, cfg):
        if cfg.forecast and (cfg.sync != "global" or cfg.use_kernel):
            _warn_once(
                "forecast-inactive",
                "RouterConfig.forecast only drives the reference sync='global' "
                "bisection path; with sync='local' or use_kernel=True the "
                "forecaster state is carried but never consulted.",
            )

    def guard_keys(self, state):
        # legacy watchdog order: q first, then whichever forecaster EMAs
        # are present (they are guarded whenever carried, cfg.forecast or not)
        return ("q",) + tuple(k for k in ("q_ema", "q_err") if k in state)

    def _solve(self, s, q0, cfg, token_mask=None):
        """Single-program dual update: the Pallas histogram kernel under
        use_kernel, else the exact sort form. Both take the serving token
        mask natively, so a masked call runs the same solver as an
        unmasked one."""
        if cfg.use_kernel:
            from repro.kernels import ops as kernel_ops  # lazy: import cycle

            with named_span("bip_kernel"):
                return kernel_ops.bip_dual_update(
                    s, q0, top_k=cfg.top_k, n_iters=cfg.bip_iters,
                    token_mask=token_mask,
                )
        q, _ = ref_bip.bip_dual_update(
            s, q0, top_k=cfg.top_k, n_iters=cfg.bip_iters, token_mask=token_mask
        )
        return q

    def score_adjust(self, s, state, cfg, *, token_mask=None, axis_names=(),
                     local_shards=1):
        n, m = s.shape
        q0 = state["q"]
        updates: State = {}
        # telemetry: dual-health scalars route() folds into the metrics —
        # strictly values the solve already produced (no extra collectives)
        tel: State = {}
        if cfg.sync == "global" and cfg.use_kernel:
            # collective Pallas path: the kernel's (m, n_bins) histogram
            # counts are psum'd across the data axes between the count pass
            # and the rank location (kernels/ops.py). Empty axis_names
            # degrades to the plain single-device kernel.
            from repro.kernels import ops as kernel_ops  # lazy: import cycle

            with named_span("bip_kernel"):
                q = kernel_ops.bip_dual_update(
                    lax.stop_gradient(s), q0,
                    top_k=cfg.top_k, n_iters=cfg.bip_iters,
                    axis_names=axis_names, token_mask=token_mask,
                )
            corrected = s - q[None, :]
            updates["q"] = q
        elif cfg.sync == "global":
            # one implementation serves the mesh path (axis_names) AND the
            # unsharded sync='global' reference (axes=()): both share the
            # bisection numerics, so a sharded global-sync run reproduces
            # the single-device trajectory bit-for-bit at the dual level —
            # the sort-based update would instead park q exactly ON the
            # capacity-marginal token's score and make the comparison
            # tie-degenerate.
            # load forecaster: predict the pre-clamp order statistic t from
            # its EMA, bracket it by the EMA'd error, and let the bisection
            # validate the bracket in-band (free when stale, rounds saved
            # when right)
            use_forecast = cfg.forecast and "q_ema" in state
            window = None
            if use_forecast:
                half = cfg.forecast_margin * state["q_err"] + cfg.forecast_floor
                window = (state["q_ema"] - half, state["q_ema"] + half)
            # scores are softmax/sigmoid outputs, so [0, 1] is a static
            # bracket: no data-dependent (pmin/pmax) bound collectives
            q, _, t = ref_bip.bip_dual_update_global(
                lax.stop_gradient(s), q0,
                top_k=cfg.top_k, n_iters=cfg.bip_iters,
                token_mask=token_mask, axis_names=axis_names,
                n_bisect=cfg.n_bisect, fanout=cfg.bisect_fanout,
                score_bounds=(0.0, 1.0), window=window, with_stats=True,
            )
            if use_forecast:
                d = cfg.forecast_decay
                err = jnp.abs(t - state["q_ema"])
                updates["q_ema"] = d * state["q_ema"] + (1.0 - d) * t
                updates["q_err"] = d * state["q_err"] + (1.0 - d) * err
                # instantaneous forecast quality: mean |t - prediction| and
                # the fraction of experts whose pre-clamp statistic landed
                # inside the warm-start bracket (window-hit rate)
                lo, hi = window
                tel["forecast_err"] = jnp.mean(err)
                tel["forecast_hit"] = jnp.mean(
                    ((t >= lo) & (t <= hi)).astype(jnp.float32)
                )
            corrected = s - q[None, :]
            updates["q"] = q
        elif local_shards > 1 and cfg.sync == "local":
            s_grp = lax.stop_gradient(s).reshape(local_shards, n // local_shards, m)
            m_grp = None if token_mask is None else token_mask.reshape(local_shards, -1)
            q_grp = jax.vmap(lambda sg, mg: self._solve(sg, q0, cfg, mg))(
                s_grp, m_grp
            )  # (S, m)
            corrected = (
                s.reshape(local_shards, -1, m) - q_grp[:, None, :]
            ).reshape(n, m)
            updates["q"] = q_grp.mean(axis=0)  # replicated warm start
        else:
            q = self._solve(lax.stop_gradient(s), q0, cfg, token_mask)
            corrected = s - q[None, :]
            updates["q"] = q
        if not cfg.bip_warm_start:
            updates["q"] = jnp.zeros_like(q0)
        return corrected, updates, tel


@register_balancer("expert_choice")
class ExpertChoiceBalancer(Balancer):
    """Expert-Choice (Zhou et al. 2022): each EXPERT takes its top-C tokens.

    Balance is perfect by construction (C = floor(k·n/m) per expert), but
    tokens may receive fewer than k experts — slots beyond a token's
    assignments carry the sentinel index m with zero combine weight, so
    they occupy no dispatch capacity and no load. TRAINING ONLY: the
    per-expert top-C over the batch lets earlier tokens see selection
    outcomes that depend on later tokens, so autoregressive decode /
    masked serving raises (route() checks `serving_ok`; the standard
    causality caveat — see core/expert_choice.py).
    """

    serving_ok = False
    uses_sync = False

    def check_config(self, cfg):
        super().check_config(cfg)
        if cfg.sync == "global":
            _warn_once(
                "expert-choice-sync",
                "expert_choice selects each expert's top-C over the "
                "device-local token shard; sync='global' does not globalize "
                "the selection (no cross-shard top-C).",
            )

    def select(self, s, corrected, cfg):
        from repro.core.expert_choice import expert_choice_select

        return expert_choice_select(
            s, cfg.top_k, norm_topk_prob=cfg.norm_topk_prob
        )

    def finalize_metrics(self, base, s, w, idx, cfg):
        # coverage columns (benchmarks/expert_choice_compare heritage):
        # how many tokens got all k experts / no expert at all
        per_token = (idx < s.shape[-1]).sum(axis=-1)
        base = dict(base)
        base["coverage_full"] = jnp.mean(
            (per_token >= cfg.top_k).astype(jnp.float32)
        )
        base["coverage_zero"] = jnp.mean((per_token == 0).astype(jnp.float32))
        return base


def router_metrics(
    bal: Balancer,
    s: Array,
    w: Array,
    idx: Array,
    cfg: RouterConfig,
) -> Dict[str, Array]:
    """Balance metrics + the balancer's method-specific columns."""
    base = balance_metrics(idx, cfg.n_experts, cfg.top_k)
    return bal.finalize_metrics(base, s, w, idx, cfg)


# the φ-Balancing and Latent-Prototype-Routing modules self-register on
# import; importing them here makes `import repro.core.balancers` (or any
# RouterConfig construction) populate the full registry
from repro.core import lpr as _lpr  # noqa: E402,F401  (self-registering)
from repro.core import phi as _phi  # noqa: E402,F401  (self-registering)

__all__ = [
    "Balancer",
    "get_balancer",
    "register_balancer",
    "registered_balancers",
    "router_metrics",
    "selection_load",
    "topk_select",
]
