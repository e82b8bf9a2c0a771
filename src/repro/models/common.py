"""Shared model primitives: norms, RoPE, GQA attention (global / sliding
window / logit softcap), gated MLPs, embeddings.

All modules are functional: `init_*(key, cfg, ...) -> params pytree` and
`apply(params, x, ...) -> y`. Parameters are plain dicts of jnp arrays so
they stack cleanly under vmap for lax.scan-over-layers.

Attention is memory-tiled: queries are processed in chunks of cfg.attn_chunk
via lax.scan so the (S, S) score matrix is never materialized — per chunk the
footprint is (B, H, chunk, S), which keeps 32k-token prefill inside HBM on the
production mesh (see DESIGN.md §3).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.telemetry.trace import named_span

Params = Dict[str, jnp.ndarray]

NEG_INF = -2.0e38  # large-negative fill that survives bf16 casts


# ------------------------------------------------------------------ norms


def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


@named_span("norm")
def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return _rmsnorm(params, x, eps)


def _rmsnorm(params: Params, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """rmsnorm without its layer scope, for the q/k norms inside `attn`."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return out.astype(dt)


# ------------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]  # (.., S, 1, D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention


def init_attention(key, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (d, h, hd), cfg.param_dtype) * scale,
        "wk": jax.random.normal(k2, (d, kv, hd), cfg.param_dtype) * scale,
        "wv": jax.random.normal(k3, (d, kv, hd), cfg.param_dtype) * scale,
        "wo": jax.random.normal(k4, (h, hd, d), cfg.param_dtype)
        * (scale / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, cfg.param_dtype)
        p["k_norm"] = init_rmsnorm(hd, cfg.param_dtype)
    return p


def _attn_weights(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, KV, D)
    mask: jnp.ndarray,  # (B, 1|H, Sq, Sk) bool
    softcap: float,
) -> jnp.ndarray:
    groups = q.shape[2] // k.shape[2]
    kq = jnp.repeat(k, groups, axis=2)  # (B, Sk, H, D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq).astype(jnp.float32)
    logits = logits / math.sqrt(q.shape[-1])
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (can happen for padded chunks): zero them out
    w = jnp.where(mask.any(axis=-1, keepdims=True), w, 0.0)
    return w


def _attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    softcap: float,
    compute_dtype,
) -> jnp.ndarray:
    w = _attn_weights(q, k, mask, softcap)
    groups = q.shape[2] // v.shape[2]
    vq = jnp.repeat(v, groups, axis=2)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(compute_dtype), vq)


def causal_window_mask(
    q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: int
) -> jnp.ndarray:
    """(…, Sq, Sk) bool. window=0 -> plain causal; else sliding window."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    mask = diff >= 0
    if window > 0:
        mask &= diff < window
    return mask


@named_span("attn")
def attention(
    params: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ModelConfig,
    *,
    layer_kind: str = "global",  # 'global' | 'local'
    positions: Optional[jnp.ndarray] = None,
    segments: Optional[jnp.ndarray] = None,  # (B, S) document ids
    mesh_ctx=None,
    causal: bool = True,
) -> jnp.ndarray:
    """Training / prefill attention with two memory-bounded layouts.

    `segments` (when given) restricts attention to seg_q == seg_k: packed
    multi-document sequences (data/packing.py 'pack_nocross') attend only
    within their own document, at zero cost when absent.

    * heads % model_axis == 0 (or no mesh): Megatron layout — heads shard
      over 'model'; queries are processed in chunks via lax.scan so only one
      (chunk, S) score block lives at a time.
    * otherwise: SEQUENCE-parallel layout — the query axis shards over
      'model' (K/V replicated; exact since each query row is independent).
      No scan: the sharded score block (B, H, S/model, S) is the working set.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]
    theta = cfg.rope_theta
    window = 0
    if layer_kind == "local":
        window = cfg.window_size
        if cfg.rope_local_theta:
            theta = cfg.rope_local_theta

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cfg.compute_dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cfg.compute_dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cfg.compute_dtype))
    if cfg.qk_norm:
        q = _rmsnorm(params["q_norm"], q, cfg.rms_norm_eps)
        k = _rmsnorm(params["k_norm"], k, cfg.rms_norm_eps)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)

    msize = 0
    if mesh_ctx is not None and getattr(mesh_ctx, "mesh", None) is not None:
        msize = mesh_ctx.mesh.shape[mesh_ctx.model_axis] if mesh_ctx.model_axis else 0
    # Megatron layout when heads divide the model axis; otherwise SEQUENCE
    # parallelism: the positions *within each query chunk* shard over
    # 'model' (K/V replicated — exact, since query rows are independent).
    seq_parallel = msize > 1 and cfg.n_heads % msize != 0
    bspec = mesh_ctx.batch_spec if msize else None

    chunk = min(cfg.attn_chunk, s)
    if s % chunk != 0:  # pad the query axis up to a chunk multiple
        pad = chunk - s % chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(positions, ((0, 0), (0, pad)), constant_values=-1)
    else:
        pad = 0
        qpos = positions
    n_chunks = q.shape[1] // chunk
    qc = q.reshape(b, n_chunks, chunk, cfg.n_heads, hd)
    pc = jnp.broadcast_to(qpos, (b, qpos.shape[-1])).reshape(b, n_chunks, chunk)
    sc = None
    if segments is not None:
        segq = jnp.broadcast_to(segments, (b, s))
        if pad:  # padded query rows get a segment no key carries
            segq = jnp.pad(segq, ((0, 0), (0, pad)), constant_values=-2)
        sc = segq.reshape(b, n_chunks, chunk)
    if msize:
        if seq_parallel:
            qc = mesh_ctx.constrain(qc, bspec, None, "model", None, None)
        else:
            qc = mesh_ctx.constrain(qc, bspec, None, None, "model", None)

    def body(carry, inp):
        qi, pi = inp[0], inp[1]  # (B, chunk, H, D), (B, chunk)
        if causal:
            mask = causal_window_mask(pi, positions, window)[:, None]  # (B,1,c,S)
        else:
            mask = (pi >= 0)[:, None, :, None] & jnp.ones((1, 1, 1, s), bool)
        if segments is not None:
            si = inp[2]  # (B, chunk)
            mask = mask & (si[:, :, None] == segments[:, None, :])[:, None]
        yi = _attend(qi, k, v, mask, cfg.attn_logit_softcap, cfg.compute_dtype)
        return carry, yi

    xs = (qc.swapaxes(0, 1), pc.swapaxes(0, 1))
    if sc is not None:
        xs = xs + (sc.swapaxes(0, 1),)
    _, ys = lax.scan(body, None, xs)
    y = ys.swapaxes(0, 1).reshape(b, n_chunks * chunk, cfg.n_heads, hd)
    if pad:
        y = y[:, :s]
    return jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(cfg.compute_dtype))


@named_span("attn")
def attention_chunk(
    params: Params,
    x: jnp.ndarray,  # (B, C, d)
    cache: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    layer_kind: str = "global",
    lengths: jnp.ndarray = None,  # (B,) int32, tokens valid per row (0..C)
    positions: Optional[jnp.ndarray] = None,  # (B, C) packed-mode positions
    segments: Optional[jnp.ndarray] = None,  # (B, C) ids; -1 = padding
    write_slots: Optional[jnp.ndarray] = None,  # (B, C) target cache row; -1 drops
    cache_rows: Optional[jnp.ndarray] = None,  # (B,) cache row each row reads
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Cached attention advancing each row by `lengths[i]` tokens at once.

    The chunked-prefill core (DESIGN.md §Serving): row i's first lengths[i]
    columns are real tokens starting at absolute position cache['pos'][i];
    the rest is padding. Valid K/V are written into the cache in bulk
    (out-of-bounds scatter indices drop the padded columns) and the chunk
    attends with a per-query causal mask, so rows at different sequence
    offsets — including pure decode rows with lengths[i] == 1 — share one
    traced program. Global layers attend against the updated cache; ring
    (sliding-window) layers attend against the pre-update ring concatenated
    with the in-chunk keys, because the bulk write clobbers keys still
    inside earlier in-chunk queries' windows. Padded output columns are
    garbage and must be masked by the caller.

    For local layers C <= window_size is required (asserted; the engine
    clamps chunk_size), so in-chunk writes never collide in the ring.

    Passing `segments` switches to the PACKED layout (see
    `_attention_chunk_packed`); `lengths` is ignored there and the other
    three packed operands describe per-column placement. The segments=None
    path is bit-identical to the pre-packing implementation.
    """
    if segments is not None:
        return _attention_chunk_packed(
            params,
            x,
            cache,
            cfg,
            layer_kind=layer_kind,
            positions=positions,
            segments=segments,
            write_slots=write_slots,
            cache_rows=cache_rows,
        )
    b, c, _ = x.shape
    theta = cfg.rope_theta
    window = 0
    if layer_kind == "local":
        window = cfg.window_size
        if cfg.rope_local_theta:
            theta = cfg.rope_local_theta
    if lengths is None:
        lengths = jnp.full((b,), c, jnp.int32)

    pos0 = cache["pos"]  # (B,)
    q_pos = pos0[:, None] + jnp.arange(c)[None, :]  # (B, C)
    valid = jnp.arange(c)[None, :] < lengths[:, None]  # (B, C)

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cfg.compute_dtype))
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cfg.compute_dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cfg.compute_dtype))
    if cfg.qk_norm:
        q = _rmsnorm(params["q_norm"], q, cfg.rms_norm_eps)
        k_new = _rmsnorm(params["k_norm"], k_new, cfg.rms_norm_eps)
    q = apply_rope(q, q_pos, theta)
    k_new = apply_rope(k_new, q_pos, theta)

    cap = cache["k"].shape[1]
    if window > 0:
        assert c <= cap, f"chunk {c} must fit the ring buffer (window {cap})"
        write_idx = q_pos % cap
    else:
        write_idx = q_pos
    # padded columns scatter out of bounds -> dropped
    write_idx = jnp.where(valid, write_idx, cap)
    k = jax.vmap(lambda cch, n, i: cch.at[i].set(n, mode="drop"))(
        cache["k"], k_new.astype(cache["k"].dtype), write_idx
    )
    v = jax.vmap(lambda cch, n, i: cch.at[i].set(n, mode="drop"))(
        cache["v"], v_new.astype(cache["v"].dtype), write_idx
    )

    idx = jnp.arange(cap)[None, :]  # (1, cap)
    if window > 0:
        # Ring layers must attend against the PRE-update ring plus the
        # in-chunk keys: writing position p' overwrites the key at p'-cap,
        # which is still inside the window of every earlier in-chunk query
        # p in [p'-cap+1, p'-1] — a bulk write-then-attend would clobber it.
        prev = pos0 - 1  # (B,) latest position already in the ring
        k_pos = prev[:, None] - ((prev[:, None] - idx) % cap)  # (B, cap)
        ring_ok = (
            (k_pos >= 0)[:, None, :]
            & (k_pos[:, None, :] <= q_pos[..., None])
            & (k_pos[:, None, :] > q_pos[..., None] - window)
        )  # (B, C, cap)
        chunk_ok = (
            (q_pos[:, None, :] <= q_pos[..., None])
            & (q_pos[:, None, :] > q_pos[..., None] - window)
            & valid[:, None, :]
        )  # (B, C, C)
        mask = jnp.concatenate([ring_ok, chunk_ok], axis=-1) & valid[..., None]
        k_att = jnp.concatenate(
            [cache["k"].astype(cfg.compute_dtype), k_new], axis=1
        )
        v_att = jnp.concatenate(
            [cache["v"].astype(cfg.compute_dtype), v_new], axis=1
        )
    else:
        k_pos = jnp.broadcast_to(idx, (b, cap))
        mask = (k_pos[:, None, :] <= q_pos[..., None]) & valid[..., None]
        k_att, v_att = k, v.astype(cfg.compute_dtype)
    mask = mask[:, None]  # (B, 1, C, cap[+C])

    y = _attend(q, k_att, v_att, mask, cfg.attn_logit_softcap, cfg.compute_dtype)
    out = jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(cfg.compute_dtype))
    return out, {"k": k, "v": v, "pos": pos0 + lengths}


def _attention_chunk_packed(
    params: Params,
    x: jnp.ndarray,  # (B, C, d)
    cache: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    *,
    layer_kind: str,
    positions: jnp.ndarray,  # (B, C) absolute position of every column
    segments: jnp.ndarray,  # (B, C) int32; -1 = padding
    write_slots: jnp.ndarray,  # (B, C) cache row each column writes; -1 drops
    cache_rows: Optional[jnp.ndarray],  # (B,) cache row each ROW reads
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Packed multi-request chunk: row != slot, column placement is explicit.

    Each column carries (position, segment, target cache row). Segment 0 is
    the row's RESIDENT stream — the continuation of cache row
    `cache_rows[b]` — and attends through the cache exactly like the dense
    path. Segments >= 1 are FRESH packed prompts: whole short prompts
    sharing a row, attending only their own in-chunk keys (same row, same
    segment, causal by position) — their K/V still scatter into their own
    slot's cache row via `write_slots` so the next step continues them as
    residents. Segment -1 columns are padding: never written, never
    attended, outputs garbage (same caller-masks contract as the dense
    path).

    Cross-row placement of ONE stream (a long prompt spread over several
    rows as segment 0 with a shared cache row) is sound only on GLOBAL
    layers, where write-then-attend routes every in-flight key through the
    cache; ring layers see in-chunk keys per-row only, so the engine gates
    spreading on all-global stacks.
    """
    b, c, _ = x.shape
    n_rows, cap = cache["k"].shape[0], cache["k"].shape[1]
    theta = cfg.rope_theta
    window = 0
    if layer_kind == "local":
        window = cfg.window_size
        if cfg.rope_local_theta:
            theta = cfg.rope_local_theta
    if cache_rows is None:
        cache_rows = jnp.arange(b, dtype=jnp.int32)
    valid = segments >= 0  # (B, C)
    q_pos = positions

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cfg.compute_dtype))
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cfg.compute_dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cfg.compute_dtype))
    if cfg.qk_norm:
        q = _rmsnorm(params["q_norm"], q, cfg.rms_norm_eps)
        k_new = _rmsnorm(params["k_norm"], k_new, cfg.rms_norm_eps)
    q = apply_rope(q, q_pos, theta)
    k_new = apply_rope(k_new, q_pos, theta)

    if window > 0:
        assert c <= cap, f"chunk {c} must fit the ring buffer (window {cap})"
        write_pos = q_pos % cap
    else:
        write_pos = q_pos
    # dropped columns (padding, or write_slots < 0) scatter out of bounds
    drop = ~valid | (write_slots < 0)
    ws = jnp.where(drop, n_rows, write_slots)
    wp = jnp.where(drop, cap, write_pos)
    k = cache["k"].at[ws, wp].set(k_new.astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[ws, wp].set(v_new.astype(cache["v"].dtype), mode="drop")

    resident = segments == 0  # cache-attached columns
    fresh = segments >= 1  # in-chunk packed prompts
    same_seg = segments[:, None, :] == segments[:, :, None]  # (B, C, C)
    idx = jnp.arange(cap)[None, :]  # (1, cap)
    pos0 = cache["pos"]
    if window > 0:
        # pre-update ring of the row's resident stream (same rationale as
        # the dense path); fresh segments never touch it
        prev = pos0[cache_rows] - 1  # (B,)
        k_pos = prev[:, None] - ((prev[:, None] - idx) % cap)  # (B, cap)
        ring_ok = (
            (k_pos >= 0)[:, None, :]
            & (k_pos[:, None, :] <= q_pos[..., None])
            & (k_pos[:, None, :] > q_pos[..., None] - window)
            & resident[..., None]
        )  # (B, C, cap)
        chunk_ok = (
            same_seg
            & (q_pos[:, None, :] <= q_pos[..., None])
            & (q_pos[:, None, :] > q_pos[..., None] - window)
            & valid[:, None, :]
        )  # (B, C, C)
        mask = jnp.concatenate([ring_ok, chunk_ok], axis=-1) & valid[..., None]
        k_att = jnp.concatenate(
            [cache["k"][cache_rows].astype(cfg.compute_dtype), k_new], axis=1
        )
        v_att = jnp.concatenate(
            [cache["v"][cache_rows].astype(cfg.compute_dtype), v_new], axis=1
        )
    else:
        # write-then-attend through the POST-update cache row: residents see
        # every key of their stream regardless of which row wrote it this
        # chunk (that is what makes cross-row spreading exact); fresh
        # segments attend their in-chunk keys only — their cache writes
        # land in a row this row does not read
        k_pos = jnp.broadcast_to(idx, (b, cap))
        cache_ok = (k_pos[:, None, :] <= q_pos[..., None]) & resident[..., None]
        chunk_ok = (
            same_seg
            & (q_pos[:, None, :] <= q_pos[..., None])
            & valid[:, None, :]
            & fresh[..., None]
        )
        mask = jnp.concatenate([cache_ok, chunk_ok], axis=-1) & valid[..., None]
        k_att = jnp.concatenate([k[cache_rows], k_new], axis=1)
        v_att = jnp.concatenate(
            [v[cache_rows].astype(cfg.compute_dtype), v_new], axis=1
        )
    mask = mask[:, None]  # (B, 1, C, cap+C)

    y = _attend(q, k_att, v_att, mask, cfg.attn_logit_softcap, cfg.compute_dtype)
    out = jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(cfg.compute_dtype))
    # each cache row advances by the number of valid columns written into it
    counts = jnp.zeros((n_rows,), jnp.int32).at[ws.reshape(-1)].add(
        valid.reshape(-1).astype(jnp.int32), mode="drop"
    )
    return out, {"k": k, "v": v, "pos": pos0 + counts}


def init_attention_cache(
    cfg: ModelConfig, batch: int, seq_len: int, layer_kind: str, dtype
) -> Dict[str, jnp.ndarray]:
    cap = min(cfg.window_size, seq_len) if layer_kind == "local" else seq_len
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cap, kv, hd), dtype),
        "v": jnp.zeros((batch, cap, kv, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# -------------------------------------------------------------------- mlp


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "w_gate": jax.random.normal(k1, (d, f), cfg.param_dtype) * s_in,
        "w_up": jax.random.normal(k2, (d, f), cfg.param_dtype) * s_in,
        "w_down": jax.random.normal(k3, (f, d), cfg.param_dtype)
        * (s_out / math.sqrt(2 * cfg.n_layers)),
    }


def mlp(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(cfg.compute_dtype))
    u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(cfg.compute_dtype))
    return jnp.einsum(
        "...f,fd->...d", act(g) * u, params["w_down"].astype(cfg.compute_dtype)
    )


# ------------------------------------------------------------- embeddings


def init_embedding(key, cfg: ModelConfig) -> Params:
    p = {
        "tok": jax.random.normal(
            key, (cfg.vocab_size, cfg.d_model), cfg.param_dtype
        )
        * (1.0 / math.sqrt(cfg.d_model))
    }
    if not cfg.tie_embeddings:
        p["unembed"] = (
            jax.random.normal(
                jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab_size), cfg.param_dtype
            )
            / math.sqrt(cfg.d_model)
        )
    return p


@named_span("embed")
def embed(params: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return params["tok"].astype(cfg.compute_dtype)[tokens]


@named_span("lm_head")
def unembed(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "...d,vd->...v", x, params["tok"].astype(cfg.compute_dtype)
        )
    else:
        logits = jnp.einsum(
            "...d,dv->...v", x, params["unembed"].astype(cfg.compute_dtype)
        )
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap > 0:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits
