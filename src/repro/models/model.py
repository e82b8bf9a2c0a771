"""Top-level model: embeddings + stack + head, for every assigned family.

`build_model(cfg, mesh_ctx)` returns a `Model` of pure functions:

    init(key)                      -> params
    init_router_states()           -> router state stacks (MoE only)
    forward(params, batch, states) -> (logits, new_states, aux, metrics)
    loss_fn(params, batch, states) -> (loss, (new_states, metrics))
    init_cache(batch, seq_len)     -> decode caches (+ cross-attn KV)
    prefill(params, batch, cache, states)      -> (logits_last, cache, states)
    decode_step(params, tokens, cache, states) -> (logits, cache, states)

Batch dict keys by family:
    all:    'tokens' (B, S) int32; training also 'labels' (B, S)
    vlm:    'patches' (B, frontend_tokens, frontend_dim) — SigLIP stub output
    encdec: 'frames' (B, enc_seq_len, frontend_dim)     — codec stub output
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common, mamba2, moe, stack
from repro.models.stack import MeshCtx
from repro.telemetry.trace import named_span

Params = Dict[str, Any]


# ------------------------------------------------------------- encoder


def _init_encoder(key, cfg: ModelConfig) -> Params:
    """Bidirectional transformer encoder (audio/encdec family)."""
    enc_cfg = dataclasses.replace(
        cfg, n_layers=cfg.n_enc_layers, attn_pattern=("global",)
    )
    keys = jax.random.split(key, cfg.n_enc_layers + 1)
    layers = jax.vmap(lambda k: stack.init_layer(k, enc_cfg, "global", "dense"))(
        keys[: cfg.n_enc_layers]
    )
    return {
        "layers": layers,
        "final_norm": common.init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }


def _apply_encoder(
    params: Params, x: jnp.ndarray, cfg: ModelConfig, mesh_ctx=None
) -> jnp.ndarray:
    """Non-causal self-attention encoder over frame embeddings.

    Uses the shared query-chunked attention (causal=False) so the (S, S)
    score matrix is never materialized, and remats each scanned layer under
    cfg.remat like the decoder stack."""
    enc_cfg = dataclasses.replace(
        cfg, n_layers=cfg.n_enc_layers, attn_pattern=("global",)
    )

    def body_fn(x, lp):
        h = common.attention(
            lp["attn"],
            common.rmsnorm(lp["pre_norm"], x, cfg.rms_norm_eps),
            enc_cfg,
            positions=jnp.arange(x.shape[1])[None, :],
            mesh_ctx=mesh_ctx,
            causal=False,
        )
        x = x + h
        h = common.mlp(
            lp["mlp"], common.rmsnorm(lp["ffn_norm"], x, cfg.rms_norm_eps), enc_cfg
        )
        return x + h

    if cfg.remat == "block":
        body_fn = jax.checkpoint(body_fn)

    def body(x, lp):
        return body_fn(x, lp), None

    x, _ = lax.scan(body, x, params["layers"])
    return common.rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)


def _merge_load(load_total, vio_max, ld, m_load):
    """Fold one MoE layer's per-expert dispatch counts into the running
    (total load, worst per-layer MaxVio) pair. MaxVio = max/mean - 1, the
    paper's metric (same convention as core.metrics.balance_metrics).
    Counts accumulate in int32 (telemetry dtype audit); only the MaxVio
    ratio is float."""
    if ld is None:
        return load_total, vio_max
    mean = jnp.maximum(jnp.sum(ld) / m_load, 1e-9)
    return load_total + ld, jnp.maximum(vio_max, jnp.max(ld) / mean - 1.0)


# --------------------------------------------------------------- model


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    mesh_ctx: MeshCtx

    # ------------------------------------------------------------- init

    def init(self, key) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 6)
        p: Params = {
            "embed": common.init_embedding(keys[0], cfg),
            "stack": stack.init_stack(keys[1], cfg),
            "final_norm": common.init_rmsnorm(cfg.d_model, cfg.param_dtype),
        }
        if cfg.n_enc_layers:
            p["encoder"] = _init_encoder(keys[2], cfg)
        if cfg.frontend_dim:
            p["frontend_proj"] = (
                jax.random.normal(
                    keys[3], (cfg.frontend_dim, cfg.d_model), cfg.param_dtype
                )
                / math.sqrt(cfg.frontend_dim)
            )
        return p

    def init_router_states(self) -> list:
        return stack.init_stack_router_states(self.cfg)

    # -------------------------------------------------------- embedding

    def _embed_inputs(self, params: Params, batch: Dict[str, jnp.ndarray]):
        """Token embeddings with optional modality prefix. Returns (x, n_prefix)."""
        cfg = self.cfg
        x = common.embed(params["embed"], batch["tokens"], cfg)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(cfg.compute_dtype)
            proj = jnp.einsum(
                "bsf,fd->bsd", patches, params["frontend_proj"].astype(cfg.compute_dtype)
            )
            x = jnp.concatenate([proj, x], axis=1)
            return x, cfg.frontend_tokens
        return x, 0

    def _encode(self, params: Params, batch) -> Optional[jnp.ndarray]:
        cfg = self.cfg
        if not cfg.n_enc_layers:
            return None
        frames = batch["frames"].astype(cfg.compute_dtype)
        proj = jnp.einsum(
            "bsf,fd->bsd", frames, params["frontend_proj"].astype(cfg.compute_dtype)
        )
        proj = self.mesh_ctx.constrain(proj, self.mesh_ctx.batch_spec, None, None)
        return _apply_encoder(params["encoder"], proj, cfg, self.mesh_ctx)

    # ---------------------------------------------------------- forward

    def forward(
        self,
        params: Params,
        batch: Dict[str, jnp.ndarray],
        router_states: list,
        rng: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, list, jnp.ndarray, Dict]:
        cfg = self.cfg
        mc = self.mesh_ctx
        x, n_prefix = self._embed_inputs(params, batch)
        x = mc.constrain(x, mc.batch_spec, None, None)
        enc_out = self._encode(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        # packed real-text batches carry per-position document ids; the
        # attention mask then stays within-document (modality-prefix models
        # never pack, so the prefix offset never meets segments)
        segments = batch.get("segments") if n_prefix == 0 else None
        if segments is not None and cfg.family in ("ssm", "hybrid"):
            # the SSM recurrence carries state across the packed boundary —
            # the mask can't cut it, so refuse rather than silently leak
            raise ValueError(
                "segment-masked packing (pack_nocross) is attention-only; "
                f"{cfg.family} architectures leak document state through the "
                "mamba recurrence — use pack_mode='pack' or 'pad'"
            )
        x, new_states, aux, mets = stack.apply_stack(
            params["stack"],
            x,
            router_states,
            cfg,
            positions=positions,
            segments=segments,
            enc_out=enc_out,
            mesh_ctx=self.mesh_ctx,
            rng=rng,
        )
        x = common.rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
        if n_prefix:
            x = x[:, n_prefix:]
        x = mc.constrain(x, mc.batch_spec, None, None)
        logits = common.unembed(params["embed"], x, cfg)
        # tokens stay batch-sharded; the vocab axis carries the model shards
        logits = mc.constrain(logits, mc.batch_spec, None, mc.model_axis or None)
        return logits, new_states, aux, mets

    def loss_fn(
        self,
        params: Params,
        batch: Dict[str, jnp.ndarray],
        router_states: list,
        rng: Optional[jnp.ndarray] = None,
    ):
        logits, new_states, aux, mets = self.forward(params, batch, router_states, rng=rng)
        labels = batch["labels"]
        with named_span("lm_head"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            mask = (labels >= 0).astype(jnp.float32)
            nll = jnp.where(labels >= 0, nll, 0.0)
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        loss = ce + aux
        mets = dict(mets)
        mets.update(ce_loss=ce, aux_loss=aux, perplexity=jnp.exp(ce))
        return loss, (new_states, mets)

    # ---------------------------------------------------------- serving

    def init_cache(
        self, params: Params, batch: Dict[str, jnp.ndarray], seq_len: int
    ) -> Params:
        """Decode caches mirroring the stack layout; cross-attn K/V are
        precomputed from the encoder output here (static per request)."""
        return self._build_cache(
            params, batch["tokens"].shape[0], seq_len, self._encode(params, batch)
        )

    def init_slot_cache(self, params: Params, n_slots: int, max_seq_len: int) -> Params:
        """Slot-pool cache for the continuous-batching engine (DESIGN.md
        §Serving): one cache row per batch slot, no request batch needed.
        Slots are recycled across requests via `reset_slot`; per-slot 'pos'
        indices let slots at different sequence offsets share one traced
        step. Token-only families; encdec needs per-request encoder K/V."""
        assert not self.cfg.n_enc_layers, "slot cache: encdec not supported"
        return self._build_cache(params, n_slots, max_seq_len, None)

    @staticmethod
    def reset_slot(cache: Params, slot: jnp.ndarray) -> Params:
        """Zero one slot's rows across every cache leaf (K/V, positions,
        SSM/conv state) without retracing — `slot` is a traced index, so a
        single jitted reset serves the whole pool."""
        return jax.tree.map(lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)), cache)

    def _build_cache(
        self, params: Params, bsz: int, seq_len: int, enc_out
    ) -> Params:
        cfg = self.cfg
        period, n_groups, remainder = stack._group_layout(cfg)
        kinds = cfg.layer_kinds()
        kv_dtype = cfg.compute_dtype

        def one_cache(mixer_kind, layer_params=None):
            c: Dict[str, jnp.ndarray] = {}
            base = mixer_kind.replace("+shared", "")
            if base in ("global", "local"):
                c.update(common.init_attention_cache(cfg, bsz, seq_len, base, kv_dtype))
                if enc_out is not None and layer_params is not None:
                    dt = cfg.compute_dtype
                    c["ck"] = jnp.einsum(
                        "bsd,dhk->bshk", enc_out, layer_params["cross"]["wk"].astype(dt)
                    )
                    c["cv"] = jnp.einsum(
                        "bsd,dhk->bshk", enc_out, layer_params["cross"]["wv"].astype(dt)
                    )
            else:
                c.update(mamba2.init_mamba_cache(cfg, bsz, kv_dtype))
                if mixer_kind.endswith("+shared"):
                    sc = common.init_attention_cache(cfg, bsz, seq_len, "global", kv_dtype)
                    c.update({"sk": sc["k"], "sv": sc["v"], "spos": sc["pos"]})
            return c

        caches = []
        for j in range(period):
            reps = n_groups + (1 if j < remainder else 0)
            lp0 = jax.tree.map(lambda a: a[0], params["stack"]["blocks"][j])
            proto = one_cache(kinds[j][0], lp0)
            if "ck" in proto:
                # per-rep cross KV differ (different layer weights): build each
                per = [
                    one_cache(
                        kinds[j][0],
                        jax.tree.map(lambda a: a[r], params["stack"]["blocks"][j]),
                    )
                    for r in range(reps)
                ]
                caches.append(
                    jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per)
                )
            else:
                caches.append(
                    jax.tree.map(
                        lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), proto
                    )
                )
        return {"blocks": caches}

    def _apply_layer_chunk(
        self, p, x, cfg, mixer_kind, ffn_kind, cache, router_state, lengths,
        packed=None,
    ):
        """One layer over a (B, C) token chunk against the slot cache.

        `lengths` is (B,) valid-token counts, or None meaning every column is
        real (the decode_step / dryrun path — keeps the MoE dispatch
        unmasked and therefore expert-parallel safe). `packed` (a dict of
        positions/segments/write_slots/cache_rows) switches attention into
        the packed multi-request layout; column validity then comes from
        segments >= 0. Returns
        (x, new_cache, new_router_state, aux, load) with load the per-expert
        dispatch counts of this layer's real tokens ((m,) or None).
        """
        base = mixer_kind.replace("+shared", "")
        new_cache = dict(cache)
        valid = None
        if packed is not None:
            valid = packed["segments"] >= 0  # (B, C)
        elif lengths is not None:
            valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]  # (B, C)
        if base in ("global", "local"):
            h, attn_cache = common.attention_chunk(
                p["attn"],
                common.rmsnorm(p["pre_norm"], x, cfg.rms_norm_eps),
                {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]},
                cfg,
                layer_kind=base,
                lengths=lengths,
                **(packed or {}),
            )
            new_cache.update(attn_cache)
            x = x + stack._maybe_post(p, "post_attn_norm", h, cfg)
            if "ck" in cache:
                xq = common.rmsnorm(p["cross_norm"], x, cfg.rms_norm_eps)
                dt = cfg.compute_dtype
                se = cache["ck"].shape[1]
                if valid is None:
                    mask = jnp.ones((1, 1, x.shape[1], se), bool)
                else:
                    mask = jnp.broadcast_to(
                        valid[:, None, :, None], (x.shape[0], 1, x.shape[1], se)
                    )
                with named_span("attn"):
                    q = jnp.einsum("bsd,dhk->bshk", xq, p["cross"]["wq"].astype(dt))
                    y = common._attend(q, cache["ck"], cache["cv"], mask, 0.0, dt)
                    x = x + jnp.einsum(
                        "bshk,hkd->bsd", y, p["cross"]["wo"].astype(dt)
                    )
        else:
            h, mcache = mamba2.mamba_chunk(
                p["mamba"],
                common.rmsnorm(p["pre_norm"], x, cfg.rms_norm_eps),
                {"ssm": cache["ssm"], "conv": cache["conv"]},
                cfg,
                lengths=lengths,
            )
            new_cache.update(mcache)
            x = x + h

        aux = jnp.zeros((), jnp.float32)
        load = None
        if ffn_kind == "dense":
            h = common.mlp(
                p["mlp"], common.rmsnorm(p["ffn_norm"], x, cfg.rms_norm_eps), cfg
            )
            x = x + stack._maybe_post(p, "post_ffn_norm", h, cfg)
        elif ffn_kind == "moe":
            xin = common.rmsnorm(p["ffn_norm"], x, cfg.rms_norm_eps)
            b, s, d = xin.shape
            if valid is None:
                flat = xin.reshape(b * s, d)
                token_mask = None
            else:
                # zero padded rows so they router-score as neutral uniform
                flat = (xin * valid[..., None].astype(xin.dtype)).reshape(b * s, d)
                token_mask = valid.reshape(b * s)
            y, router_state, aux, moe_mets = moe.moe_ffn(
                p["moe"], flat, router_state, cfg, self.mesh_ctx, token_mask=token_mask
            )
            load = moe_mets["load"]
            h = y.reshape(b, s, d)
            if cfg.dense_residual and "mlp" in p:
                h = h + common.mlp(p["mlp"], xin, cfg)
            if cfg.n_shared_experts and "shared_mlp" in p:
                with named_span("moe/shared"):
                    h = h + common.mlp(p["shared_mlp"], xin, cfg)
            x = x + h

        if mixer_kind.endswith("+shared"):
            sp = self._shared_params
            h, sc = common.attention_chunk(
                sp["attn"],
                common.rmsnorm(sp["pre_norm"], x, cfg.rms_norm_eps),
                {"k": cache["sk"], "v": cache["sv"], "pos": cache["spos"]},
                cfg,
                layer_kind="global",
                lengths=lengths,
                **(packed or {}),
            )
            new_cache.update({"sk": sc["k"], "sv": sc["v"], "spos": sc["pos"]})
            x = x + h
            h = common.mlp(
                sp["mlp"], common.rmsnorm(sp["ffn_norm"], x, cfg.rms_norm_eps), cfg
            )
            x = x + h
        return x, new_cache, router_state, aux, load

    def prefill_chunk(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, C) int32
        cache: Params,
        router_states: list,
        lengths: Optional[jnp.ndarray] = None,  # (B,) valid counts; None = all C
        *,
        positions: Optional[jnp.ndarray] = None,  # (B, C) packed-mode layout
        segments: Optional[jnp.ndarray] = None,  # (B, C); -1 = padding
        write_slots: Optional[jnp.ndarray] = None,  # (B, C) cache row per column
        cache_rows: Optional[jnp.ndarray] = None,  # (B,) cache row each row reads
    ) -> Tuple[jnp.ndarray, Params, list, Dict[str, jnp.ndarray]]:
        """Advance every slot by up to C tokens in ONE fused, trace-once step.

        The continuous-batching core (DESIGN.md §Serving): prefilling slots
        carry their next <=C prompt tokens, decoding slots carry 1 sampled
        token, idle slots carry 0 — all through the same program, so mixed
        prefill/decode traffic shares each MoE layer's router invocation and
        the BIP dual vector q keeps balancing across the whole batch.
        Returns (logits (B, C, vocab), cache, router_states, metrics) where
        metrics['moe_load'] is the per-expert dispatch count of real tokens
        summed over MoE layers and metrics['max_vio'] the worst per-layer
        violation. Padded logit columns are garbage; callers index
        lengths-1.

        Passing `segments` switches attention into the PACKED layout
        (common._attention_chunk_packed): rows and cache slots decouple, and
        every column carries (position, segment, write slot). Attention-only
        stacks only — SSM/conv state advances strictly left-to-right per row
        and cannot host interleaved streams.
        """
        cfg = self.cfg
        period, n_groups, remainder = stack._group_layout(cfg)
        kinds = cfg.layer_kinds()
        packed = None
        if segments is not None:
            bad = {k for k, _ in kinds if k.replace("+shared", "") not in ("global", "local")}
            if bad:
                raise ValueError(
                    f"packed prefill: attention-only stacks required, got {sorted(bad)}"
                )
            packed = {
                "positions": positions,
                "segments": segments,
                "write_slots": write_slots,
                "cache_rows": cache_rows,
            }
        self._shared_params = params["stack"].get("shared")
        x = common.embed(params["embed"], tokens, cfg)
        m_load = cfg.routing.n_experts if cfg.is_moe else 1

        def apply_period(x, lp, lc, ls):
            new_caches, new_states = [], []
            load = jnp.zeros((m_load,), jnp.int32)
            vio = jnp.zeros((), jnp.float32)
            for j in range(period):
                x, nc, st, _, ld = self._apply_layer_chunk(
                    lp[j], x, cfg, kinds[j][0], kinds[j][1], lc[j], ls[j], lengths,
                    packed,
                )
                new_caches.append(nc)
                new_states.append(st)
                load, vio = _merge_load(load, vio, ld, m_load)
            return x, new_caches, new_states, load, vio

        def scan_body(x, per_group):
            lp, lc, ls = per_group
            x, new_caches, new_states, load, vio = apply_period(x, lp, lc, ls)
            return x, (new_caches, new_states, load, vio)

        if n_groups > 0:
            lp = [
                jax.tree.map(lambda a: a[:n_groups], params["stack"]["blocks"][j])
                for j in range(period)
            ]
            lc = [
                jax.tree.map(lambda a: a[:n_groups], cache["blocks"][j])
                for j in range(period)
            ]
            ls = [
                None
                if router_states[j] is None
                else jax.tree.map(lambda a: a[:n_groups], router_states[j])
                for j in range(period)
            ]
            x, (new_caches, new_states, loads, vios) = lax.scan(
                scan_body, x, (lp, lc, ls)
            )
            load_total = jnp.sum(loads, axis=0)
            vio_max = jnp.max(vios) if n_groups else jnp.zeros((), jnp.float32)
        else:
            new_caches = [None] * period
            new_states = [None] * period
            load_total = jnp.zeros((m_load,), jnp.int32)
            vio_max = jnp.zeros((), jnp.float32)

        # remainder layers (tail prefix of the period), applied once
        rem_caches, rem_states = [], []
        for j in range(remainder):
            lp_j = jax.tree.map(lambda a: a[n_groups], params["stack"]["blocks"][j])
            lc_j = jax.tree.map(lambda a: a[n_groups], cache["blocks"][j])
            ls_j = (
                None
                if router_states[j] is None
                else jax.tree.map(lambda a: a[n_groups], router_states[j])
            )
            x, nc, st, _, ld = self._apply_layer_chunk(
                lp_j, x, cfg, kinds[j][0], kinds[j][1], lc_j, ls_j, lengths,
                packed,
            )
            rem_caches.append(nc)
            rem_states.append(st)
            load_total, vio_max = _merge_load(load_total, vio_max, ld, m_load)

        out_caches, out_states = [], []
        for j in range(period):
            c = new_caches[j]
            s = new_states[j]
            if remainder and j < remainder:
                c = jax.tree.map(
                    lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                    c,
                    rem_caches[j],
                )
                if s is not None:
                    s = jax.tree.map(
                        lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                        s,
                        rem_states[j],
                    )
            out_caches.append(c)
            out_states.append(s)

        x = common.rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
        logits = common.unembed(params["embed"], x, cfg)
        mets = {"moe_load": load_total, "max_vio": vio_max}
        return logits, {"blocks": out_caches}, out_states, mets

    def decode_step(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, 1) int32
        cache: Params,
        router_states: list,
    ) -> Tuple[jnp.ndarray, Params, list]:
        """One token for every sequence in the batch (prefill_chunk, C=1)."""
        logits, cache, states, _ = self.prefill_chunk(
            params, tokens, cache, router_states
        )
        return logits, cache, states

    def prefill(
        self,
        params: Params,
        batch: Dict[str, jnp.ndarray],
        router_states: list,
        seq_len: int,
    ):
        """Prefill = forward pass + cache fill. For simplicity the cache is
        filled by scanning decode steps for short prompts; production prefill
        uses the chunked forward and writes K/V in bulk — here we only need
        the compiled-graph shape for the dry-run, so prefill == forward and
        returns last-position logits."""
        logits, new_states, aux, mets = self.forward(params, batch, router_states)
        return logits[:, -1:], new_states, mets


def build_model(cfg: ModelConfig, mesh_ctx: MeshCtx = MeshCtx()) -> Model:
    cfg.validate()
    return Model(cfg=cfg, mesh_ctx=mesh_ctx)
