"""Decoder stack assembly: per-layer blocks, scan-over-layers, KV caches.

Layers are grouped by the config's layer-kind cycle (period P from
`cfg.scan_period()`): parameters for each position j < P are stacked with a
leading group axis and the stack is applied with lax.scan over groups — one
traced copy of the period body regardless of depth. A non-dividing remainder
(e.g. zamba2's 81 = 13·6 + 3) is applied once more outside the scan with the
leftover prefix of the period.

Block structure (pre-norm residual):
    attn blocks:   x += [post_norm](mixer(pre_norm(x)))
                   x += [post_norm](ffn(pre_norm2(x)))          ffn ∈ {dense, moe}
    mamba blocks:  x += mamba(pre_norm(x))
    'mamba+shared' additionally applies a weight-SHARED (attn + mlp) block
    (zamba2); shared weights live outside the scan stacks.
    encdec decoder blocks insert cross-attention between mixer and ffn.

MoE layers thread a router state {'q': (m,)} and emit (aux_loss, max_vio)
per layer; the stack returns them stacked per MoE layer so the training loop
can log per-layer AvgMaxVio exactly like the paper's Appendix A tables.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common, mamba2, moe
from repro.core.types import init_router_state
from repro.telemetry.trace import named_span

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """How the model is laid out on a device mesh (None => single device)."""

    mesh: Any = None
    data_axes: Tuple[str, ...] = ()
    model_axis: str = ""

    @property
    def use_ep(self) -> bool:
        return self.mesh is not None and bool(self.model_axis)

    @property
    def batch_spec(self):
        if not self.data_axes:
            return None
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]

    def constrain(self, x, *spec):
        """Pin an activation's sharding (no-op off-mesh). Prevents GSPMD from
        drifting to batch-replicated layouts (e.g. vocab-sharded logits with
        gathered tokens), which blows past HBM."""
        if self.mesh is None or x is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec(*spec))
        )


# ----------------------------------------------------------------- layers


def init_layer(key, cfg: ModelConfig, mixer_kind: str, ffn_kind: str) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {}
    if mixer_kind in ("global", "local"):
        p["pre_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["attn"] = common.init_attention(keys[0], cfg)
        if cfg.post_block_norms:
            p["post_attn_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
        if cfg.n_enc_layers:  # decoder of an encdec model: cross attention
            p["cross_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
            p["cross"] = common.init_attention(keys[1], cfg)
    else:  # mamba
        p["pre_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["mamba"] = mamba2.init_mamba(keys[0], cfg)

    if ffn_kind == "dense":
        p["ffn_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["mlp"] = common.init_mlp(keys[2], cfg)
        if cfg.post_block_norms:
            p["post_ffn_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
    elif ffn_kind == "moe":
        p["ffn_norm"] = common.init_rmsnorm(cfg.d_model, cfg.param_dtype)
        p["moe"] = moe.init_moe(keys[2], cfg)
        if cfg.dense_residual:
            p["mlp"] = common.init_mlp(keys[3], cfg)
        if cfg.n_shared_experts:
            p["shared_mlp"] = common.init_mlp(
                keys[4], cfg, d_ff=(cfg.moe_d_ff or cfg.d_ff) * cfg.n_shared_experts
            )
    return p


def init_shared_block(key, cfg: ModelConfig) -> Params:
    """zamba2: one (attn + mlp) block whose weights are shared across uses."""
    k1, k2 = jax.random.split(key)
    return {
        "pre_norm": common.init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "attn": common.init_attention(k1, cfg),
        "ffn_norm": common.init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "mlp": common.init_mlp(k2, cfg),
    }


def _maybe_post(p: Params, name: str, y: jnp.ndarray, cfg: ModelConfig):
    if cfg.post_block_norms and name in p:
        return common.rmsnorm(p[name], y, cfg.rms_norm_eps)
    return y


def apply_layer(
    p: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ModelConfig,
    mixer_kind: str,
    ffn_kind: str,
    router_state: Optional[Dict[str, jnp.ndarray]],
    *,
    positions: Optional[jnp.ndarray] = None,
    segments: Optional[jnp.ndarray] = None,
    enc_out: Optional[jnp.ndarray] = None,
    shared_params: Optional[Params] = None,
    mesh_ctx: MeshCtx = MeshCtx(),
    rng: Optional[jnp.ndarray] = None,  # per-layer key for dropout-style regularizers
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]], jnp.ndarray, Dict]:
    """Returns (x, new_router_state, aux_loss, metrics)."""
    del rng  # no stochastic regularizer uses it yet; plumbed for them
    aux = jnp.zeros((), jnp.float32)
    mets: Dict[str, jnp.ndarray] = {}
    b, s, d = x.shape

    base_kind = mixer_kind.replace("+shared", "")
    if base_kind in ("global", "local"):
        h = common.attention(
            p["attn"],
            common.rmsnorm(p["pre_norm"], x, cfg.rms_norm_eps),
            cfg,
            layer_kind=base_kind,
            positions=positions,
            segments=segments,
            mesh_ctx=mesh_ctx,
        )
        x = x + _maybe_post(p, "post_attn_norm", h, cfg)
        if enc_out is not None and "cross" in p:
            hc = _cross_attention(
                p["cross"],
                common.rmsnorm(p["cross_norm"], x, cfg.rms_norm_eps),
                enc_out,
                cfg,
                mesh_ctx=mesh_ctx,
            )
            x = x + hc
    else:  # mamba
        h = mamba2.mamba_block(
            p["mamba"], common.rmsnorm(p["pre_norm"], x, cfg.rms_norm_eps), cfg
        )
        x = x + h

    if ffn_kind == "dense":
        h = common.mlp(
            p["mlp"], common.rmsnorm(p["ffn_norm"], x, cfg.rms_norm_eps), cfg
        )
        x = x + _maybe_post(p, "post_ffn_norm", h, cfg)
    elif ffn_kind == "moe":
        xin = common.rmsnorm(p["ffn_norm"], x, cfg.rms_norm_eps)
        flat = xin.reshape(b * s, d)
        y, new_state, aux_moe, moe_mets = moe.moe_ffn(
            p["moe"], flat, router_state, cfg, mesh_ctx
        )
        h = y.reshape(b, s, d)
        if cfg.dense_residual and "mlp" in p:
            h = h + common.mlp(p["mlp"], xin, cfg)
        if cfg.n_shared_experts and "shared_mlp" in p:
            with named_span("moe/shared"):
                h = h + common.mlp(p["shared_mlp"], xin, cfg)
        x = x + h
        router_state = new_state
        aux = aux + aux_moe
        mets = {"max_vio": moe_mets["max_vio"], "load": moe_mets["load"]}
        # optional telemetry scalars (dispatch drops, dual health, bip
        # forecaster quality) ride along when the MoE path computed them;
        # the EP shard_map paths surface only the fixed 3-key dict, so
        # these are local-path-only (DESIGN.md §Observability)
        for k in (
            "dropped_frac_cap1",
            "q_abs_max",
            "forecast_err",
            "forecast_hit",
        ):
            if k in moe_mets:
                mets[k] = moe_mets[k]

    if mixer_kind.endswith("+shared") and shared_params is not None:
        h = common.attention(
            shared_params["attn"],
            common.rmsnorm(shared_params["pre_norm"], x, cfg.rms_norm_eps),
            cfg,
            layer_kind="global",
            positions=positions,
            segments=segments,
            mesh_ctx=mesh_ctx,
        )
        x = x + h
        h = common.mlp(
            shared_params["mlp"],
            common.rmsnorm(shared_params["ffn_norm"], x, cfg.rms_norm_eps),
            cfg,
        )
        x = x + h

    return x, router_state, aux, mets


@named_span("attn")
def _cross_attention(p, x, enc_out, cfg: ModelConfig, *, mesh_ctx: MeshCtx = MeshCtx()):
    """Cross attention, decoder-query-chunked (same memory discipline as
    self-attention: one (chunk, S_enc) score block at a time, or the whole
    sharded block under sequence parallelism)."""
    dt = cfg.compute_dtype
    b, s, _ = x.shape
    se = enc_out.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))

    msize = 0
    if mesh_ctx.mesh is not None and mesh_ctx.model_axis:
        msize = mesh_ctx.mesh.shape[mesh_ctx.model_axis]
    if msize > 1 and cfg.n_heads % msize != 0:
        q = mesh_ctx.constrain(q, mesh_ctx.batch_spec, "model", None, None)
        mask = jnp.ones((1, 1, s, se), bool)
        y = common._attend(q, k, v, mask, 0.0, dt)
        return jnp.einsum("bshk,hkd->bsd", y, p["wo"].astype(dt))

    chunk = min(cfg.attn_chunk, s)
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(b, -1, chunk, cfg.n_heads, q.shape[-1])

    def body(carry, qi):
        mask = jnp.ones((1, 1, chunk, se), bool)
        return carry, common._attend(qi, k, v, mask, 0.0, dt)

    _, ys = lax.scan(body, None, qc.swapaxes(0, 1))
    y = ys.swapaxes(0, 1).reshape(b, -1, cfg.n_heads, q.shape[-1])[:, :s]
    return jnp.einsum("bshk,hkd->bsd", y, p["wo"].astype(dt))


# ------------------------------------------------------------------ stack


def _group_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    period = cfg.scan_period()
    n_groups = cfg.n_layers // period
    remainder = cfg.n_layers % period
    return period, n_groups, remainder


def init_stack(key, cfg: ModelConfig) -> Params:
    """Stacked per-position layer params: params['blocks'][j] has leading
    axis n_groups (+1 when j < remainder)."""
    period, n_groups, remainder = _group_layout(cfg)
    kinds = cfg.layer_kinds()
    blocks = []
    for j in range(period):
        reps = n_groups + (1 if j < remainder else 0)
        keys = jax.random.split(jax.random.fold_in(key, j), reps)
        stacked = jax.vmap(
            lambda k: init_layer(k, cfg, kinds[j][0], kinds[j][1])
        )(keys)
        blocks.append(stacked)
    p: Params = {"blocks": blocks}
    if any(mk.endswith("+shared") for mk, _ in kinds):
        p["shared"] = init_shared_block(jax.random.fold_in(key, 10_001), cfg)
    return p


def init_stack_router_states(cfg: ModelConfig) -> list:
    """Router state stacks mirroring params['blocks'] layout (None for
    non-MoE positions)."""
    period, n_groups, remainder = _group_layout(cfg)
    kinds = cfg.layer_kinds()
    rcfg = moe.router_config(cfg) if cfg.is_moe else None
    states = []
    for j in range(period):
        reps = n_groups + (1 if j < remainder else 0)
        if cfg.is_moe and kinds[j][1] == "moe":
            st = init_router_state(rcfg)
            # prepend the layer axis whatever the leaf rank: (m,) duals tile
            # to (reps, m), lpr's (m, m) prototypes to (reps, m, m)
            states.append(
                jax.tree.map(
                    lambda a: jnp.tile(a, (reps,) + (1,) * a.ndim), st
                )
            )
        else:
            states.append(None)
    return states


def apply_stack(
    params: Params,
    x: jnp.ndarray,
    router_states: list,
    cfg: ModelConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    segments: Optional[jnp.ndarray] = None,
    enc_out: Optional[jnp.ndarray] = None,
    mesh_ctx: MeshCtx = MeshCtx(),
    rng: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, list, jnp.ndarray, Dict]:
    """Run all layers. Returns (x, new_router_states, aux_total, metrics).

    metrics['max_vio_per_layer']: (n_moe_layers,) in layer order; every
    other column the MoE layers emit follows the same convention —
    'load_per_layer' (n_moe_layers, m) int32 dispatch counts,
    'dropped_frac_cap1_per_layer', 'q_abs_max_per_layer', and (bip
    forecaster) 'forecast_err_per_layer' / 'forecast_hit_per_layer'.

    `rng` (optional) is the caller's per-step PRNG key; each layer receives
    a fold of it (group index threaded through the scan, position folded
    inside), so dropout-style regularizers get resume-stable randomness.
    `segments` masks attention to within-document (packed real-text data).
    """
    period, n_groups, remainder = _group_layout(cfg)
    kinds = cfg.layer_kinds()
    shared = params.get("shared")

    def period_body(x, layer_params, layer_states, group_rng=None):
        """Apply positions j = 0..period-1 once; returns per-j aux/mets.

        Per-MoE-layer metrics come back as a dict of stacked arrays
        ({'max_vio': (n_moe,), 'load': (n_moe, m) int32, ...}) so every
        telemetry column the layers emit is threaded through the scan —
        the key set is identical across layers (same MoE path per model),
        which is what lax.scan's fixed carry/output structure needs.
        """
        x = mesh_ctx.constrain(x, mesh_ctx.batch_spec, None, None)
        new_states, auxes = [], []
        per_layer: Dict[str, list] = {}
        for j in range(period):
            x, st, aux, mets = apply_layer(
                layer_params[j],
                x,
                cfg,
                kinds[j][0],
                kinds[j][1],
                layer_states[j],
                positions=positions,
                segments=segments,
                enc_out=enc_out,
                shared_params=shared,
                mesh_ctx=mesh_ctx,
                rng=None if group_rng is None else jax.random.fold_in(group_rng, j),
            )
            new_states.append(st)
            auxes.append(aux)
            if "max_vio" in mets:
                for k, v in mets.items():
                    per_layer.setdefault(k, []).append(v)
        aux_total = sum(auxes) if auxes else jnp.zeros((), jnp.float32)
        stacked = (
            {k: jnp.stack(v) for k, v in per_layer.items()}
            if per_layer
            else {"max_vio": jnp.zeros((0,), jnp.float32)}
        )
        return x, new_states, aux_total, stacked

    # full groups via scan
    if n_groups > 0:
        full_params = [jax.tree.map(lambda a: a[:n_groups], params["blocks"][j]) for j in range(period)]
        full_states = [
            None
            if router_states[j] is None
            else jax.tree.map(lambda a: a[:n_groups], router_states[j])
            for j in range(period)
        ]

        body_fn = period_body
        if cfg.remat == "block":
            # recompute activations in backward: memory per device drops from
            # O(n_layers · tokens · d) to O(period · tokens · d) + residuals
            body_fn = jax.checkpoint(period_body)

        group_keys = (
            None if rng is None else jax.random.split(jax.random.fold_in(rng, 0), n_groups)
        )

        def scan_body(x, per_group):
            lp, ls = per_group[0], per_group[1]
            gk = per_group[2] if group_keys is not None else None
            x, new_states, aux, lmets = body_fn(x, lp, ls, gk)
            return x, (new_states, aux, lmets)

        xs = (full_params, full_states)
        if group_keys is not None:
            xs = xs + (group_keys,)
        x, (scanned_states, auxes, met_groups) = lax.scan(scan_body, x, xs)
        aux_total = jnp.sum(auxes)
        # met_groups[k]: (n_groups, n_moe_in_period, ...) stacked by the scan
    else:
        scanned_states = [None] * period
        aux_total = jnp.zeros((), jnp.float32)
        met_groups = {"max_vio": jnp.zeros((0, 0), jnp.float32)}

    # remainder layers (tail prefix of the period), applied once
    rem_states = []
    rem_mets: list = []
    if remainder:
        lp = [
            jax.tree.map(lambda a: a[n_groups], params["blocks"][j])
            for j in range(remainder)
        ]
        ls = [
            None
            if router_states[j] is None
            else jax.tree.map(lambda a: a[n_groups], router_states[j])
            for j in range(remainder)
        ]
        rem_rng = None if rng is None else jax.random.fold_in(rng, 1)
        for j in range(remainder):
            x, st, aux, mets = apply_layer(
                lp[j],
                x,
                cfg,
                kinds[j][0],
                kinds[j][1],
                ls[j],
                positions=positions,
                segments=segments,
                enc_out=enc_out,
                shared_params=shared,
                mesh_ctx=mesh_ctx,
                rng=None if rem_rng is None else jax.random.fold_in(rem_rng, j),
            )
            rem_states.append(st)
            aux_total = aux_total + aux
            if "max_vio" in mets:
                rem_mets.append(mets)

    # reassemble router-state stacks
    new_router_states = []
    for j in range(period):
        if router_states[j] is None:
            new_router_states.append(None)
            continue
        base = scanned_states[j]
        if remainder and j < remainder and rem_states[j] is not None:
            tail = jax.tree.map(lambda a: a[None], rem_states[j])
            base = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), base, tail
            )
        new_router_states.append(base)

    # per-layer metric columns in true layer order (group-major reassembly,
    # matching how the scan visits layers); every key the layers emitted
    # becomes '<key>_per_layer' with a leading (n_moe_layers,) axis
    moe_positions = [j for j in range(period) if kinds[j][1] == "moe"]
    keys = list(rem_mets[0]) if rem_mets else list(met_groups)
    metrics: Dict[str, jnp.ndarray] = {}
    for k in keys:
        vals = []
        if n_groups > 0 and len(moe_positions) and k in met_groups:
            for g in range(n_groups):
                for i, _ in enumerate(moe_positions):
                    vals.append(met_groups[k][g, i])
        vals.extend(m[k] for m in rem_mets)
        metrics[f"{k}_per_layer"] = (
            jnp.stack(vals) if vals else jnp.zeros((0,), jnp.float32)
        )
    if "max_vio_per_layer" not in metrics:
        metrics["max_vio_per_layer"] = jnp.zeros((0,), jnp.float32)
    return x, new_router_states, aux_total, metrics
