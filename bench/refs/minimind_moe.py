"""Plain reference of the minimind-moe family: weights, forward, loss, AdamW.

Written from the architecture's description (minimind MoE, paper
arXiv:2502.15451 Algorithm 1) in straightforward jax.numpy, float32 at
`Precision.HIGHEST`, with no kernels, no cache and no batching tricks. It
imports nothing of the program under test. It does share one thing with the
program on purpose: the layout of the parameter tree, so that the weights the
benchmark makes from the seed can be handed to both (`param_shapes`).

Each decoder layer (pre-norm residual):
    x += Attn(RMSNorm(x))                  causal, grouped KV heads, RoPE (rotate halves)
    x += MoE(RMSNorm(x)) + SharedFFN(RMSNorm(x))
MoE routing is the paper's BIP gate: scores s = softmax(x W_r); T iterations
of the dual update (sort form, exact order statistics) warm-started from the
layer's carried dual q; experts = top-k of s - q; gate values = raw s.
Dispatch keeps the first ceil(k·n/m · capacity_factor) (token, slot) pairs of
each expert in token-major, slot-minor order and drops the rest. Logits use
the tied embedding. Training is mean next-token cross-entropy over
microbatches that run one after another (the dual carried between them),
then AdamW with global-norm clipping; matrices (every leaf of rank >= 2)
decay.

`precision="fp8"` is the control: every matmul operand, in the forward
pass and in the backward pass (the incoming cotangent too), is rounded to
float8 e4m3 with a per-tensor scale before an exact product, the next
precision below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


# ----------------------------------------------------------------- weights


def param_shapes(cfg: dict) -> dict:
    """The parameter tree: layer leaves stacked on a leading layer axis."""
    L, d, V = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    m, f = cfg["n_experts"], cfg["moe_d_ff"]
    fs = f * cfg["n_shared_experts"]
    block = {
        "pre_norm": {"scale": (L, d)},
        "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
                 "wo": (L, h, hd, d)},
        "ffn_norm": {"scale": (L, d)},
        "moe": {"w_router": (L, d, m), "w_gate": (L, m, d, f), "w_up": (L, m, d, f),
                "w_down": (L, m, f, d)},
        "shared_mlp": {"w_gate": (L, d, fs), "w_up": (L, d, fs), "w_down": (L, fs, d)},
    }
    return {"embed": {"tok": (V, d)}, "stack": {"blocks": [block]},
            "final_norm": {"scale": (d,)}}


def _init_std(path: str, cfg: dict) -> float:
    """Standard deviation of a leaf: 1/sqrt(fan-in); output projections also
    by 1/sqrt(2·layers); norm scales are ones (std 0)."""
    d, f, L = cfg["d_model"], cfg["moe_d_ff"], cfg["n_layers"]
    if path.endswith("scale"):
        return 0.0
    if path.endswith("wo"):
        return 1.0 / math.sqrt(d) / math.sqrt(2 * L)
    if path.endswith("w_down"):
        return 1.0 / math.sqrt(f * (cfg["n_shared_experts"] if "shared" in path else 1)) \
            / math.sqrt(2 * L)
    return 1.0 / math.sqrt(d)


def init_params(cfg: dict, key) -> dict:
    """Weights from `key`, float32, leaf i drawn from fold_in(key, i).
    Call under jax.jit so that they are made on the device in one program."""
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        std = _init_std(name, cfg)
        if std == 0.0:
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                                  jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------- counting
# What bench/counts.py reads of this architecture: every layer is MoE, with
# grouped-query attention and shared experts at the routed width.


def matmul_params_per_token(cfg: dict) -> int:
    """Weights one token is multiplied by: attention projections, router,
    its top-k routed experts, shared experts, and the (tied) unembedding.
    The embedding lookup is a gather, not a matmul."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f, m, k = cfg["moe_d_ff"], cfg["n_experts"], cfg["top_k"]
    attn = d * hd * (2 * h + 2 * kv)
    ffn = 3 * d * f * (k + cfg["n_shared_experts"]) + d * m
    return cfg["n_layers"] * (attn + ffn) + cfg["vocab_size"] * d


def attention_flops_per_token(cfg: dict, context: float) -> float:
    """Forward FLOPs of scores and weighted values for one query that attends
    `context` keys: 2 matmuls x 2 FLOPs per multiply-add x heads x head_dim."""
    return cfg["n_layers"] * 4.0 * cfg["n_heads"] * cfg["head_dim"] * context


def moe_layers(cfg: dict) -> int:
    """Layers that run the grouped expert FFN: all of them."""
    return cfg["n_layers"]


# ------------------------------------------------------------------ matmul


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (straight-through grad)."""
    s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity forward; rounds the incoming cotangent to float8 e4m3, so the
    backward matmuls take float8 operands as the forward ones do."""
    return y


def _fp8_cotangent_fwd(y):
    return y, None


def _fp8_cotangent_bwd(_, g):
    return (_fp8(g),)


_fp8_cotangent.defvjp(_fp8_cotangent_fwd, _fp8_cotangent_bwd)


def make_mm(precision: str):
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: _fp8_cotangent(
            jnp.einsum(eq, _fp8(a), _fp8(b), precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ layers


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (B, S, H, D); rotate the two halves of D by position · frequency."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, :, None].astype(jnp.float32) * freqs  # (B, S, D/2)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, mm, chunk):
    """softmax(q k^T / sqrt(D)) v over keys at positions <= the query's,
    one block of `chunk` queries at a time (memory, not arithmetic)."""
    b, s, h, dh = q.shape
    chunk = min(chunk, s)
    n = s // chunk
    kq = jnp.repeat(k, h // k.shape[2], axis=2)
    vq = jnp.repeat(v, h // v.shape[2], axis=2)

    @jax.checkpoint
    def block(args):
        i, qi = args
        sc = mm("bqhd,bkhd->bhqk", qi, kq) / math.sqrt(dh)
        qpos = i * chunk + jnp.arange(chunk)
        sc = jnp.where(qpos[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), vq)

    qc = q.reshape(b, n, chunk, h, dh).swapaxes(0, 1)
    ys = lax.map(block, (jnp.arange(n), qc))
    return ys.swapaxes(0, 1).reshape(b, s, h, dh)


def swiglu(x, wg, wu, wd, mm, eq_in, eq_out):
    return mm(eq_out, jax.nn.silu(mm(eq_in, x, wg)) * mm(eq_in, x, wu), wd)


def bip_duals(s, q, top_k: int, n_iters: int):
    """Algorithm 1's dual update with exact order statistics:
        p_i = max(0, (k+1)-th largest of s_i - q)
        q_j = max(0, (floor(n·k/m)+1)-th largest of s_:j - p)."""
    n, m = s.shape
    cap_idx = (n * top_k) // m
    for _ in range(n_iters):
        if top_k < m:
            p = jnp.maximum(0.0, jnp.sort(s - q[None, :], axis=1)[:, m - 1 - top_k])
        else:
            p = jnp.zeros((n,), s.dtype)
        if cap_idx >= n:
            q = jnp.zeros_like(q)
        else:
            q = jnp.maximum(0.0, jnp.sort(s - p[:, None], axis=0)[n - 1 - cap_idx, :])
    return q


def moe(p, x, q_prev, cfg, mm):
    """x (n, d) -> (routed experts' output (n, d), new dual q, load (m,))."""
    n, d = x.shape
    m, k = cfg["n_experts"], cfg["top_k"]
    cap = max(int(math.ceil(k * n / m * cfg["capacity_factor"])), 1)
    s = jax.nn.softmax(mm("nd,dm->nm", x, p["w_router"]), axis=-1)
    q = bip_duals(lax.stop_gradient(s), q_prev, k, cfg["bip_iters"])
    _, idx = lax.top_k(s - q[None, :], k)
    gate = jnp.take_along_axis(s, idx, axis=1)  # (n, k) raw scores
    flat = idx.reshape(-1)  # token-major, slot-minor
    onehot = jax.nn.one_hot(flat, m, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat[:, None], axis=1)[:, 0]
    keep = pos < cap
    dest = jnp.where(keep, flat * cap + pos, m * cap)  # m*cap: dropped
    buf = jnp.zeros((m * cap + 1, d), x.dtype).at[dest].set(jnp.repeat(x, k, axis=0))
    buf = buf[: m * cap].reshape(m, cap, d)
    y = swiglu(buf, p["w_gate"], p["w_up"], p["w_down"], mm, "ecd,edf->ecf", "ecf,efd->ecd")
    y = jnp.concatenate([y.reshape(m * cap, d), jnp.zeros((1, d), y.dtype)])[dest]
    w = (gate * keep.reshape(n, k)).reshape(n * k, 1)
    return (y * w).reshape(n, k, d).sum(axis=1), q, jnp.sum(onehot, axis=0)


def layer(p, x, q_prev, cfg, mm):
    b, s, d = x.shape
    eps = cfg["rms_norm_eps"]
    h = rmsnorm(x, p["pre_norm"]["scale"], eps)
    a = p["attn"]
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    qh = rope(mm("bsd,dhk->bshk", h, a["wq"]), pos, cfg["rope_theta"])
    kh = rope(mm("bsd,dhk->bshk", h, a["wk"]), pos, cfg["rope_theta"])
    vh = mm("bsd,dhk->bshk", h, a["wv"])
    att = causal_attention(qh, kh, vh, mm, cfg["attn_chunk"])
    x = x + mm("bshk,hkd->bsd", att, a["wo"])
    xin = rmsnorm(x, p["ffn_norm"]["scale"], eps).reshape(b * s, d)
    y, q, load = moe(p["moe"], xin, q_prev, cfg, mm)
    sm = p["shared_mlp"]
    y = y + swiglu(xin, sm["w_gate"], sm["w_up"], sm["w_down"], mm, "nd,df->nf", "nf,fd->nd")
    return x + y.reshape(b, s, d), q, load


# ------------------------------------------------------------------- model


def loss_fn(params, tokens, labels, duals, cfg, precision="f32"):
    """Mean next-token cross-entropy over (B, S); duals (L, m) are the
    layers' carried q. Returns (loss, new duals)."""
    mm = make_mm(precision)
    x = params["embed"]["tok"][tokens]
    blocks = params["stack"]["blocks"][0]

    @jax.checkpoint
    def body(x, per_layer):
        p, q = per_layer
        x, q, _ = layer(p, x, q, cfg, mm)
        return x, q

    x, new_duals = lax.scan(body, x, (blocks, duals))
    x = rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = mm("bsd,vd->bsv", x, params["embed"]["tok"])
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), new_duals


def init_duals(cfg: dict):
    return jnp.zeros((cfg["n_layers"], cfg["n_experts"]), jnp.float32)


# --------------------------------------------------------------- training


def make_grad_fn(cfg: dict, precision: str = "f32"):
    """jit'd (params, tokens, labels, duals) -> (loss, duals, grads)."""

    def f(params, tokens, labels, duals):
        (loss, duals), grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, labels, duals, cfg, precision), has_aux=True)(params)
        return loss, duals, grads

    return jax.jit(f)


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up from 0 to the peak, then cosine to 10% of the peak."""
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * t)))


def leaf_norms(tree) -> jnp.ndarray:
    """Frobenius norm of every leaf, in tree order, as one (n_leaves,) array."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def make_adamw(opt: dict):
    """jit'd AdamW step (b1, b2, eps, decoupled weight decay on rank >= 2
    leaves, global-norm clipping). Returns (params, mu, nu, clipped grads)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    def f(params, mu, nu, grads, step, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step

        def upd(p, m, v):
            delta = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if p.ndim >= 2:
                delta = delta + wd * p
            return p - lr * delta

        return jax.tree.map(upd, params, mu, nu), mu, nu, grads

    return jax.jit(f)


class TrainReference:
    """The reference's training step for one configuration, optimizer and
    precision; its jitted pieces are built once and reused across seeds."""

    def __init__(self, cfg: dict, opt: dict, precision: str = "f32"):
        self.cfg, self.opt = cfg, opt
        self.grad_fn = make_grad_fn(cfg, precision)
        self.adamw = make_adamw(opt)
        self.norms = jax.jit(leaf_norms)

    def readings(self, params0, batches: List[Dict], microbatches: int,
                 drop_half: bool = False) -> dict:
        """Follow the program's first len(batches) steps from `params0`.

        Returns per-step losses, the per-leaf norms of step 1's clipped
        gradient (what the optimizer takes in) and of its raw gradient, and
        the per-leaf norms of the parameters' change after the last step.
        `drop_half` is a planted fault: each step keeps only the first half
        of its microbatches and takes the mean over them."""
        params = params0
        mu = jax.tree.map(jnp.zeros_like, params0)
        nu = jax.tree.map(jnp.zeros_like, params0)
        duals = init_duals(self.cfg)
        losses, g1_clip, g1_raw = [], None, None
        for step, batch in enumerate(batches):
            size = batch["tokens"].shape[0] // microbatches
            used = microbatches // 2 if drop_half else microbatches
            grads, loss = None, 0.0
            for i in range(used):
                sl = slice(i * size, (i + 1) * size)
                l, duals, g = self.grad_fn(params, batch["tokens"][sl], batch["labels"][sl],
                                           duals)
                loss = loss + l
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            grads = jax.tree.map(lambda g: g / used, grads)
            losses.append(float(loss) / used)
            lr = lr_at(step, self.opt)  # the program's counter before this step
            params, mu, nu, clipped = self.adamw(params, mu, nu, grads,
                                                 jnp.float32(step + 1), jnp.float32(lr))
            if step == 0:
                g1_raw = [float(v) for v in self.norms(grads)]
                g1_clip = [float(v) for v in self.norms(clipped)]
            del grads, clipped
        change = self.norms(jax.tree.map(jnp.subtract, params, params0))
        return {"losses": losses, "grad1": g1_clip, "grad1_raw": g1_raw,
                "change": [float(v) for v in change]}
