"""Pure-jnp reference implementation of BIP-Based Balancing (Algorithm 1 / 2).

This is the oracle. The Pallas kernel (`repro.kernels.bip_admm`) and the
distributed variants are tested against these functions.

Algorithm 1 (inner loop, per gate invocation), for score matrix s in R^{n x m}:

    for t = 1..T:
        P   = s - 1_n^T q                      # (n, m)
        p_i = max(0, (k+1)-th largest of P_i)  # row-wise selection
        Q   = s^T - 1_m^T p                    # (m, n);  Q_ji = s_ij - p_i
        q_j = max(0, (nk/m+1)-th largest of Q_j)

    g_ij = s_ij  if  s_ij - q_j in TopK({s_it - q_t}, k)  else 0

Interpretation: (p, q) are the dual prices of the relaxed assignment LP; ADMM
coordinate steps on the dual are closed-form order statistics. Gate *values*
stay the raw scores, so q carries no gradient (like Loss-Free's bias).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def expert_kth_index(n: int, k: int, m: int) -> int:
    """0-based order-statistic index for the (nk/m + 1)-th largest of n values.

    Returns floor(n*k/m); values at that index or beyond are "over capacity".
    If the index falls past the end (m >= n*k, more capacity than tokens) the
    constraint is slack and q_j must be 0 — signalled by returning -1.
    """
    idx = (n * k) // m
    return -1 if idx >= n else idx


def kth_largest(x: jnp.ndarray, kth: int, axis: int = -1) -> jnp.ndarray:
    """Value of the (kth+1)-th largest element along `axis` (0-based kth)."""
    # lax.top_k operates on the last axis.
    moved = jnp.moveaxis(x, axis, -1)
    vals = lax.top_k(moved, kth + 1)[0][..., kth]
    return vals


def bip_dual_update(
    s: jnp.ndarray,
    q0: jnp.ndarray,
    *,
    top_k: int,
    n_iters: int,
    token_mask: Optional[jnp.ndarray] = None,  # (n,) bool; False rows invisible
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """T iterations of the ADMM dual update. Returns (q, p).

    s:  (n, m) routing scores for the current batch (float).
    q0: (m,) warm-start expert prices (zeros on the first batch).

    `token_mask` marks real rows (serving padding is False). Masked rows sink
    to -1e30 out of every order statistic and the capacity index is
    floor(n_real·k/m) over the real rows; a call with no real row leaves q0
    unchanged. With an all-True mask the result is bitwise the unmasked one:
    both take the same element of the same column as the order statistic.
    """
    n, m = s.shape
    if token_mask is None:
        s_m = s
        cap_idx = expert_kth_index(n, top_k, m)
    else:
        s_m = jnp.where(token_mask[:, None], s, jnp.asarray(-1e30, s.dtype))
        n_real = jnp.sum(token_mask.astype(jnp.int32))
        cap_idx = (n_real * top_k) // m  # traced counterpart of expert_kth_index

    def q_step(q, p):
        x = s_m - p[:, None]
        if token_mask is None:
            if cap_idx < 0:
                return jnp.zeros_like(q)
            return jnp.maximum(0.0, kth_largest(x, cap_idx, axis=0))
        # traced rank: take it from the descending column sort (masked rows
        # sort last); slack capacity (rank past the real rows) -> price 0
        xs = -jnp.sort(-x, axis=0)
        t = jnp.take(xs, jnp.minimum(cap_idx, n - 1), axis=0)
        return jnp.where(cap_idx >= n_real, 0.0, jnp.maximum(0.0, t))

    def body(_, pq):
        q, _p = pq
        # p_i = max(0, (k+1)-th largest of s_i - q); k == m -> no (k+1)-th
        # largest exists (all experts selected), token constraint is slack.
        if top_k >= m:
            p = jnp.zeros((n,), s.dtype)
        else:
            p = jnp.maximum(0.0, kth_largest(s_m - q[None, :], top_k, axis=-1))
        # q_j = max(0, (nk/m + 1)-th largest of s_:j - p)
        return (q_step(q, p), p)

    # inherit s's varying-manual-axes type (shard_map vma): inside a
    # shard_map over data axes the loop carry must be typed 'varying' from
    # iteration 0, and adding 0·s does exactly that with no semantic change
    p0 = 0.0 * s[:, 0]
    q_init = q0.astype(s.dtype) + 0.0 * s[0]
    q, p = lax.fori_loop(0, n_iters, body, (q_init, p0))
    if token_mask is not None:
        q = jnp.where(n_real > 0, q, q_init)  # idle engine step: q stays
    return q, p


def bip_topk(
    s: jnp.ndarray, q: jnp.ndarray, top_k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Select top-k experts by corrected scores s - q; gate values are raw s.

    Returns (combine_weights (n,k), expert_index (n,k) int32).
    """
    corrected = s - q[None, :]
    _, idx = lax.top_k(corrected, top_k)
    weights = jnp.take_along_axis(s, idx, axis=-1)
    return weights, idx.astype(jnp.int32)


def bip_route_reference(
    s: jnp.ndarray, q0: jnp.ndarray, *, top_k: int, n_iters: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full Algorithm 1 gate: dual update then biased top-k.

    Returns (combine_weights, expert_index, q_new).
    """
    q, _ = bip_dual_update(s, q0, top_k=top_k, n_iters=n_iters)
    w, idx = bip_topk(s, q, top_k)
    return w, idx, q


# ---------------------------------------------------------------------------
# Sort-free variant: order statistics via threshold binary search.
#
# This mirrors what the Pallas kernel does on TPU (compare + reduce only, no
# sort network), and is also the building block for sync='global' routing:
# the count reduction can be extended with lax.psum over data axes so the
# order statistic is computed over the *global* token set while each device
# only holds its local shard.
# ---------------------------------------------------------------------------


def bisect_ladder_depth(fanout: int) -> int:
    """Midpoint-ladder depth r for a requested per-round probe budget.

    The fused round probes a depth-r midpoint ladder of the bracket —
    2^r - 1 interior points, every one a chain of exact (a+b)*0.5
    midpoints — so `fanout` rounds UP to the next 2^r - 1. The ladder
    construction (rather than equally spaced convex combinations) is what
    keeps the thresholds bit-deterministic across compilation contexts:
    (a+b)*0.5 has no mul+add to contract into an fma, so eager reference
    runs, jitted mesh programs, and every device of a shard_map agree
    bitwise — which the cross-shard parity suite checks down to exact
    load histograms.
    """
    if fanout < 1:
        raise ValueError(f"fanout must be >= 1, got {fanout}")
    return max(1, math.ceil(math.log2(fanout + 1.0)))


def bisect_rounds(n_bisect: int, fanout: int) -> int:
    """Worst-case fused-bisection rounds for `n_bisect` bits of resolution.

    Each round shrinks the bracket 2^r x (r = bisect_ladder_depth(fanout)),
    so fanout=1 is classic bisection (n_bisect rounds) and fanout=F needs
    ceil(n_bisect / r) rounds for the same final width — 5 rounds at the
    production defaults (n_bisect=26, fanout=32 -> r=6).
    """
    if n_bisect < 1:
        raise ValueError(f"n_bisect must be >= 1, got {n_bisect}")
    return max(1, math.ceil(n_bisect / bisect_ladder_depth(fanout)))


def kth_largest_threshold(
    x: jnp.ndarray,
    kth: int,
    *,
    axis: int = -1,
    n_bisect: int = 26,
    axis_names: tuple = (),
    lo: Optional[jnp.ndarray] = None,
    hi: Optional[jnp.ndarray] = None,
    fanout: int = 1,
    window: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """(kth+1)-th largest along `axis` via fused multi-threshold bisection.

    Finds the largest threshold t such that #{x > t} <= kth; the order
    statistic lies in a bracket (t_lo, t_hi] that each round shrinks 2^r x
    (r = bisect_ladder_depth(fanout)): the round probes the bracket's
    depth-r midpoint ladder — 2^r - 1 interior thresholds — with ONE fused
    exceedance count (with `axis_names`, one (probes * batch)-sized psum
    across those mesh axes instead of 2^r - 1 sequential round-trips),
    then GATHERS the sub-interval whose edge counts bracket `kth` out of
    the ladder. fanout=1 is classic midpoint bisection. Every ladder point
    is a chain of (a+b)*0.5 midpoints (exact multiply, no fma-contractible
    mul+add) and the new bounds are selected, never recomputed, so the
    thresholds are bit-identical across eager/jit/shard_map programs —
    the parity suite's exact load-histogram checks depend on this.

    Rounds run under a static `bisect_rounds(n_bisect, fanout)` trip
    count, but each round branches on convergence (every bracket narrower
    than the target resolution, initial width * 2^-n_bisect) and skips its
    count — and its collective — once converged. The convergence predicate
    only reads collectively-reduced bounds, so it is replicated and every
    device in the mesh takes the identical branch (a lax.cond, not a
    lax.while_loop, because shard_map's replication checker has rules for
    scan/cond but not while on this jax version).

    `window` is an optional (w_lo, w_hi) predicted bracket per batch element
    (see the router's load forecaster). Its validity check — the statistic
    lies in (w_lo, w_hi] iff count(w_lo) > kth >= count(w_hi) — rides in
    round 0's fused count at zero extra collectives; where valid it is
    intersected with round 0's sub-interval, where stale the full-range
    sub-interval is used, so a wrong forecast costs nothing but the saved
    rounds.

    Exactness: for routing we only need the *set* {x > t} to have kth
    elements; 26 bits over a [-2, 2] range give ~6e-8 resolution, far below
    any meaningful score gap in fp32 softmax outputs. Counts are small exact
    integers in f32, so given identical (replicated) brackets every device
    converges on bit-identical thresholds.
    """
    axis_names = tuple(axis_names)
    if lo is None:
        lo = jnp.min(x, axis=axis)
        if axis_names:
            lo = lax.pmin(lo, axis_names)
    if hi is None:
        hi = jnp.max(x, axis=axis)
        if axis_names:
            hi = lax.pmax(hi, axis_names)

    xm = jnp.moveaxis(x, axis, 0)  # (n, *rest)
    rest = xm.shape[1:]
    dt = xm.dtype
    # ensure the answer is strictly inside (lo, hi]
    lo = jnp.broadcast_to(jnp.asarray(lo, dt), rest) - jnp.asarray(1e-6, dt)
    hi = jnp.broadcast_to(jnp.asarray(hi, dt), rest)

    depth = bisect_ladder_depth(fanout)
    n_probes = 2 ** depth - 1
    max_rounds = bisect_rounds(n_bisect, fanout)
    target = jnp.max(hi - lo) * jnp.asarray(2.0 ** (-n_bisect), dt)

    def fused_counts(pts, extra=()):
        # exceedance counts for the interior ladder points pts[1:-1], via
        # bucketize (searchsorted + scatter histogram + reverse cumsum):
        # O(n log P) comparisons instead of the O(n*P) broadcast compare,
        # and still exact small-integer counts. `extra` thresholds (the
        # window validation probes) are counted by direct compare and ride
        # the SAME psum — one collective either way.
        n_pts = pts.shape[0]
        ptsf = pts.reshape(n_pts, -1)
        xf = xm.reshape(xm.shape[0], -1)
        # b = #{ladder points < x}: x > pts[i] iff b > i
        b = jax.vmap(
            lambda a, v: jnp.searchsorted(a, v, side="left"),
            in_axes=(1, 1), out_axes=1,
        )(ptsf, xf)
        hist = jax.vmap(
            lambda col: jnp.zeros((n_pts + 1,), jnp.float32).at[col].add(1.0),
            in_axes=1, out_axes=1,
        )(b)
        rc = jnp.cumsum(hist[::-1], axis=0)[::-1]  # rc[i] = #{b >= i}
        cnt = rc[2:n_pts].reshape((n_pts - 2,) + rest)  # #{x > pts[i]}, i=1..P-2
        if extra:
            ex = jnp.stack(
                [jnp.sum((xm > e[None]).astype(jnp.float32), axis=0) for e in extra]
            )
            cnt = jnp.concatenate([cnt, ex], axis=0)
        if axis_names:
            cnt = lax.psum(cnt, axis_names)
        return cnt

    def ladder(lo_, hi_):
        # depth-r midpoint ladder: (2^r + 1, *rest) sorted boundary points
        # including lo_/hi_; each refinement interleaves adjacent midpoints
        pts = jnp.stack([lo_, hi_])
        for _ in range(depth):
            mids = (pts[:-1] + pts[1:]) * 0.5
            body = jnp.stack([pts[:-1], mids], axis=1).reshape((-1,) + rest)
            pts = jnp.concatenate([body, pts[-1:]], axis=0)
        return pts

    def subinterval(pts, cnt):
        # counts are non-increasing in the threshold, so the number of
        # probes with count > kth indexes the ladder cell holding the stat;
        # the new bounds are GATHERED ladder points (no recomputation)
        j = jnp.sum((cnt > kth).astype(jnp.int32), axis=0)[None]  # (1, *rest)
        new_lo = jnp.take_along_axis(pts, j, axis=0)[0]
        new_hi = jnp.take_along_axis(pts, j + 1, axis=0)[0]
        return new_lo, new_hi

    # round 0, peeled: carries the two window-edge validation probes (if any)
    # inside the same fused count
    pts = ladder(lo, hi)
    if window is not None:
        w_lo = jnp.broadcast_to(jnp.asarray(window[0], dt), rest)
        w_hi = jnp.broadcast_to(jnp.asarray(window[1], dt), rest)
        cnt = fused_counts(pts, extra=(w_lo, w_hi))
        new_lo, new_hi = subinterval(pts, cnt[:n_probes])
        ok = (cnt[n_probes] > kth) & (cnt[n_probes + 1] <= kth) & (w_lo < w_hi)
        lo = jnp.where(ok, jnp.maximum(w_lo, new_lo), new_lo)
        hi = jnp.where(ok, jnp.minimum(w_hi, new_hi), new_hi)
    else:
        lo, hi = subinterval(pts, fused_counts(pts))

    def round_body(_, bounds):
        lo_, hi_ = bounds
        converged = jnp.max(hi_ - lo_) <= target

        def narrow(b):
            p = ladder(b[0], b[1])
            return subinterval(p, fused_counts(p))

        return lax.cond(converged, lambda b: b, narrow, (lo_, hi_))

    lo, hi = lax.fori_loop(0, max_rounds - 1, round_body, (lo, hi))
    return hi  # upper end: guarantees #{x > hi} <= kth (capacity respected)


def bip_dual_update_threshold(
    s: jnp.ndarray,
    q0: jnp.ndarray,
    *,
    top_k: int,
    n_iters: int,
    axis_names: tuple = (),
    n_bisect: int = 26,
    fanout: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-free ADMM dual update; optionally global over sharded tokens.

    Thin alias of `bip_dual_update_global` without a token mask, kept as
    the historically-named entry point for the kernel/property parity
    tests. With axis_names=() this matches `bip_dual_update` up to
    bisection resolution; with axis_names set, `s` is the device-local
    (n_local, m) shard and the expert-price step uses psum'd global
    counts, reproducing the paper's single-device semantics under data
    parallelism.
    """
    return bip_dual_update_global(
        s, q0, top_k=top_k, n_iters=n_iters,
        axis_names=axis_names, n_bisect=n_bisect, fanout=fanout,
    )


def bip_dual_update_global(
    s: jnp.ndarray,
    q0: jnp.ndarray,
    *,
    top_k: int,
    n_iters: int,
    token_mask: Optional[jnp.ndarray] = None,  # (n,) bool; False rows invisible
    axis_names: tuple = (),
    n_bisect: int = 26,
    fanout: int = 1,
    score_bounds: Optional[Tuple[float, float]] = None,
    window: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    with_stats: bool = False,
):
    """ADMM dual update over the union of real tokens across `axis_names`.

    This is the sync='global' building block (DESIGN.md §Global-sync): `s`
    is the device-local (n_local, m) score shard inside a shard_map over
    the data axes, and every collective quantity — the real-token count,
    the bisection bounds, and the per-threshold exceedance counts — is
    reduced across `axis_names`, so every device converges on the SAME
    dual vector q over the GLOBAL token batch while only ever holding its
    shard. The token-price step p is row-wise over experts and stays fully
    local. Collective cost per dual iteration: `bisect_rounds(n_bisect,
    fanout)` fused (m*fanout,)-psums, plus a pmin/pmax bound pair ONLY when
    `score_bounds` is not given (so fanout=32 + static bounds turns PR 5's
    ~n_iters*(n_bisect+2) round-trips into ~n_iters*6).

    `score_bounds` is an optional static (lo, hi) on the entries of `s`
    (softmax/sigmoid scores live in [0, 1]): since q >= 0 implies the token
    price p stays within [0, max(hi, 0)], x = s - p is bracketed by
    [lo - max(hi, 0), hi] with no data-dependent (and hence no collective)
    bound computation at all.

    `window` is an optional (w_lo, w_hi) forecast bracket per expert for
    the pre-clamp order statistic t (see the router's load forecaster); it
    is validated inside round 0 of every dual iteration's fused count and
    ignored where stale, so warm-starts are free when wrong and save
    bisection rounds when right.

    `with_stats=True` additionally returns the final iteration's pre-clamp
    order statistic t (q = max(0, t)) so callers can update forecaster
    state; the (q, p) return signature is unchanged otherwise.

    `token_mask` marks real rows (serving padding is False): masked rows
    are pushed to -1e30 so they sink out of every order statistic, and the
    capacity index floor(n_real·k/m) is computed from the global real-row
    count (traced — hence the threshold/bisection order statistic, whose
    count comparison accepts a traced kth).

    vma typing (shard_map check_vma): q0 enters replicated and the q carry
    STAYS replicated — every q_new is assembled from psum/pmin/pmax
    outputs (or static bounds) — so callers can return it under an
    out_spec of P(None) with no re-replicating pmean. The p carry inherits
    s's varying type.

    With axis_names=() and an all-True (or absent) mask this matches
    `bip_dual_update` up to bisection resolution (~6e-8).
    """
    n, m = s.shape
    axis_names = tuple(axis_names)
    if token_mask is None:
        s_m = s
        n_real = jnp.asarray(n, jnp.int32)
    else:
        # masked rows give max(0, -1e30) = 0: no token price, no count
        s_m = jnp.where(token_mask[:, None], s, jnp.asarray(-1e30, s.dtype))
        n_real = jnp.sum(token_mask).astype(jnp.int32)
    n_glob = lax.psum(n_real, axis_names) if axis_names else n_real
    cap_idx = (n_glob * top_k) // m  # traced counterpart of expert_kth_index
    slack = cap_idx >= jnp.maximum(n_glob, 1)

    if score_bounds is not None:
        s_lo, s_hi = float(score_bounds[0]), float(score_bounds[1])
        lo_b = jnp.full((m,), s_lo - max(s_hi, 0.0), s.dtype)
        hi_b = jnp.full((m,), s_hi, s.dtype)

    def body(_, carry):
        q, _p, _t = carry
        if top_k >= m:
            p = jnp.zeros((n,), s.dtype)
        else:
            p = jnp.maximum(0.0, kth_largest(s_m - q[None, :], top_k, axis=-1))
        x = s_m - p[:, None]
        if score_bounds is not None:
            lo, hi = lo_b, hi_b
        else:
            # bisection bounds from real entries only, else resolution dies
            if token_mask is None:
                lo = jnp.min(x, axis=0)
                hi = jnp.max(x, axis=0)
            else:
                lo = jnp.min(jnp.where(token_mask[:, None], x, jnp.inf), axis=0)
                hi = jnp.max(jnp.where(token_mask[:, None], x, -jnp.inf), axis=0)
            if axis_names:
                lo = lax.pmin(lo, axis_names)
                hi = lax.pmax(hi, axis_names)
        t = kth_largest_threshold(
            x, cap_idx, axis=0,
            axis_names=axis_names, n_bisect=n_bisect, lo=lo, hi=hi,
            fanout=fanout, window=window,
        )
        # slack capacity (cap index past the global real rows) -> price 0
        t = jnp.where(slack, 0.0, t)
        q_new = jnp.maximum(0.0, t)
        return (q_new, p, t)

    p0 = 0.0 * s[:, 0]  # inherit s's vma type (see bip_dual_update)
    t0 = 0.0 * q0.astype(s.dtype)  # inherit q0's replicated type likewise
    q, p, t = lax.fori_loop(0, n_iters, body, (q0.astype(s.dtype), p0, t0))
    # an all-padding invocation (idle engine step) must not move the dual
    q = jnp.where(n_glob > 0, q, q0.astype(s.dtype))
    if with_stats:
        return q, p, t
    return q, p


def sanitize_duals(q: jnp.ndarray, abs_limit: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dual-health check: (q_safe, healthy) for a carried dual vector.

    `healthy` is a scalar bool — True iff every entry of q is finite and
    |q| stays under `abs_limit`. When unhealthy, q_safe is the zeros safe
    init (the warm start any fresh layer would use); when healthy, q_safe
    IS q (jnp.where on the scalar keeps healthy values bitwise unchanged).
    Used by the router watchdog (RouterConfig.guard_duals) so one poisoned
    batch cannot permanently corrupt a layer's carried prices.
    """
    healthy = jnp.all(jnp.isfinite(q) & (jnp.abs(q) <= abs_limit))
    return jnp.where(healthy, q, jnp.zeros_like(q)), healthy
