"""Pallas TPU kernel for the BIP-ADMM dual iteration (the paper's hot loop).

TPU adaptation (DESIGN.md §3): the reference implementation sorts score
columns per ADMM iteration (torch.topk on GPU). Column-wise sort over n up
to 10^6 maps badly onto the VPU, so selection is replaced by *histogram
counting* — which is exactly the paper's own Algorithm 4 approximation, made
hardware-native:

One kernel invocation = one ADMM iteration over the score matrix, streamed
through VMEM once in token blocks. The kernel sees the scores TRANSPOSED,
(m, n): tokens on the 128 lanes, experts on sublanes, so every per-token
quantity is a lane-dense (1, block_n) row. Per block it
  1. computes p_i = max(0, (k+1)-th largest of s_i - q) for its tokens by
     iterative max-extraction over the m sublanes (k+1 unrolled VPU passes,
     tie-broken by index), and
  2. accumulates per-expert counts of (s_ij - p_i) > edge_b against n_bins
     fixed edges spanning [lo_j, hi_j): one (m, block_n) compare and lane
     reduction per edge, in a loop over the edges, so the live working set
     is O(m·block_n) whatever n_bins is (VMEM-bounded at m=64 too).

Each grid step writes its own (m, n_bins) count block and the wrapper sums
them; ops.py turns the counts into q_j = max(0, (kn/m+1)-th largest) by
locating the rank's bin and
interpolating — resolution (hi-lo)/n_bins ≈ 0.004 at 512 bins, far below
any meaningful routing-score gap (validated against the exact oracle in
tests/test_kernels.py).

Work per iteration: n·m·(k+1 + n_bins) compares, no sort and no scatter.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_default, varying_operands

LO, HI = -1.0, 1.0  # score domain: softmax/sigmoid scores in [0,1], minus p in [0,1]
PAD_VALUE = -2.0    # below LO: padded rows never enter any histogram bin
BLOCK_N = 1024      # tokens (lanes) per grid step


def _iteration_kernel(
    st_ref,     # (m, blk) VMEM block of transposed scores
    q_ref,      # (m, 1) current expert prices (same block every step)
    lo_ref,     # (m, 1) per-expert histogram lower bound
    hi_ref,     # (m, 1) per-expert histogram upper bound
    p_ref,      # (1, blk) out: token prices for this block
    cnt_ref,    # (1, m, n_bins) out: this block's histogram counts
    *,
    top_k: int,
    n_bins: int,
):
    m, blk = st_ref.shape
    st = st_ref[...]
    x = st - q_ref[...]

    # --- p_i = max(0, (k+1)-th largest of x_i) : k+1 max-extraction passes
    row = lax.broadcasted_iota(jnp.int32, (m, blk), 0)
    active = jnp.ones((m, blk), jnp.bool_)
    cur = jnp.full((1, blk), PAD_VALUE, jnp.float32)
    for _ in range(top_k + 1):
        masked = jnp.where(active, x, PAD_VALUE)
        cur = jnp.max(masked, axis=0, keepdims=True)  # (1, blk)
        hit = active & (masked == cur)
        first = jnp.min(jnp.where(hit, row, m), axis=0, keepdims=True)
        active = active & (row != first)  # tie-break by expert index
    p = jnp.maximum(cur, 0.0)
    p_ref[...] = p

    # --- histogram of (s - p) per expert over per-expert edge ranges:
    # edge b of expert j is lo_j + (hi_j - lo_j)·b/n_bins. Bins are filled
    # a lane group (128 edges) at a time; each edge is one compare + lane
    # sum over the block, dropped into its lane of the group's (m, 128) tile.
    shifted = st - p  # (m, blk)
    lo = lo_ref[...]
    width = (hi_ref[...] - lo) / n_bins  # (m, 1)
    group = min(n_bins, 128)
    lane = lax.broadcasted_iota(jnp.int32, (m, group), 1)

    for g in range(n_bins // group):

        def edge_count(b, acc, g=g):
            edge = lo + width * (g * group + b).astype(jnp.float32)  # (m, 1)
            c = jnp.sum((shifted > edge).astype(jnp.float32), axis=1, keepdims=True)
            return jnp.where(lane == b, c, acc)

        # zeros typed like the scores, so the carry also type-checks when the
        # body runs as plain JAX inside a shard_map (see iteration_on_transposed)
        acc0 = jnp.zeros_like(shifted[:, :group])
        acc = lax.fori_loop(0, group, edge_count, acc0)
        cnt_ref[0, :, g * group:(g + 1) * group] = acc


class _Block:
    """A kernel ref held as a plain array, for running the body outside Pallas."""

    def __init__(self, value):
        self.value = value

    @property
    def shape(self):
        return self.value.shape

    def __getitem__(self, idx):
        return self.value[idx]

    def __setitem__(self, idx, x):
        self.value = self.value.at[idx].set(x)


def transpose_scores(
    s: jnp.ndarray, token_mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """(n, m) scores -> the kernel's (m, n_padded) f32 operand.

    Tokens are padded to a multiple of BLOCK_N with PAD_VALUE, and masked
    tokens (serving padding) take PAD_VALUE too, so neither ever enters a
    histogram bin: their price p is 0 and s - p = PAD_VALUE < every edge.
    """
    s = s.astype(jnp.float32)
    if token_mask is not None:
        s = jnp.where(token_mask[:, None], s, PAD_VALUE)
    pad = (-s.shape[0]) % BLOCK_N
    if pad:
        s = jnp.pad(s, ((0, pad), (0, 0)), constant_values=PAD_VALUE)
    return s.T


def iteration_on_transposed(
    st: jnp.ndarray,  # (m, n_padded) from transpose_scores
    q: jnp.ndarray,   # (m,)
    lo: jnp.ndarray,  # (m,)
    hi: jnp.ndarray,  # (m,)
    *,
    top_k: int,
    n_bins: int,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused ADMM iteration on prepared scores: (p (n_padded,), counts)."""
    m, n_pad = st.shape
    group = min(n_bins, 128)
    if n_bins % group:
        raise ValueError(f"n_bins must be a multiple of 128 or below it, got {n_bins}")
    # inside shard_map: a per-shard local computation, no collective inside
    # the kernel; its outputs vary over the mesh axes the scores vary over
    col = lambda v: v.astype(jnp.float32).reshape(m, 1)
    vma, (st, q, lo, hi) = varying_operands(st, col(q), col(lo), col(hi))

    if interpret and vma:
        # jax 0.9.0's Pallas interpreter rebuilds the kernel's types without
        # shard_map's varying axes, so an interpreted pallas_call fails under
        # check_vma. On the CPU inside a shard_map the same kernel body runs
        # over the whole shard as one block, as plain JAX ops.
        p, cnt = _Block(jnp.zeros((1, n_pad))), _Block(jnp.zeros((1, m, n_bins)))
        _iteration_kernel(
            _Block(st), _Block(q), _Block(lo), _Block(hi), p, cnt,
            top_k=top_k, n_bins=n_bins,
        )
        return p.value[0], cnt.value[0]

    n_blocks = n_pad // BLOCK_N
    p, cnt = pl.pallas_call(
        functools.partial(_iteration_kernel, top_k=top_k, n_bins=n_bins),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((m, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((1, m, n_bins), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n_blocks, m, n_bins), jnp.float32, vma=vma),
        ],
        # every step writes only its own blocks: steps are independent
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bip_admm",
    )(st, q, lo, hi)
    # per-block counts are small exact integers in f32: the sum is exact
    return p[0], jnp.sum(cnt, axis=0)


def bip_admm_iteration(
    s: jnp.ndarray,  # (n, m) scores in [0, 1]
    q: jnp.ndarray,  # (m,)
    *,
    top_k: int,
    n_bins: int = 512,
    lo=None,          # (m,) per-expert histogram bounds (default [LO, HI))
    hi=None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused ADMM iteration. Returns (p (n,), counts (m, n_bins)).

    interpret=None resolves from the platform: Mosaic on a TPU, the Pallas
    interpreter on the CPU.
    """
    n, m = s.shape
    if lo is None:
        lo = jnp.full((m,), LO, jnp.float32)
    if hi is None:
        hi = jnp.full((m,), HI, jnp.float32)
    p, cnt = iteration_on_transposed(
        transpose_scores(s), q, lo, hi, top_k=top_k, n_bins=n_bins,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return p[:n], cnt


def locate_bin(
    cnt: jnp.ndarray,  # (m, n_bins) counts of (x > edge_b), non-increasing in b
    rank: int,         # cap index: want the (rank+1)-th largest value
    n_bins: int,
    lo: jnp.ndarray,   # (m,) bounds the histogram was built over
    hi: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bin containing the order statistic. Returns (bin_lo, bin_hi, found).

    The (rank+1)-th largest value v satisfies cnt[b] > rank for edges below v
    and cnt[b] <= rank at/above it, so v lies in (edge_{b*}, edge_{b*}+Δ]
    with b* the last edge whose count exceeds rank.
    """
    width = (hi - lo) / n_bins  # (m,)
    above = cnt > rank
    b_star = jnp.sum(above.astype(jnp.int32), axis=1) - 1  # last True edge
    b_clip = jnp.clip(b_star, 0, n_bins - 1).astype(jnp.float32)
    bin_lo = lo + b_clip * width
    bin_hi = bin_lo + width
    return bin_lo, bin_hi, b_star >= 0


def q_from_histogram(
    cnt: jnp.ndarray,
    rank: int,
    n_bins: int,
    lo=None,
    hi=None,
) -> jnp.ndarray:
    """q_j = max(0, order statistic) with linear interpolation in its bin."""
    m = cnt.shape[0]
    if lo is None:
        lo = jnp.full((m,), LO, jnp.float32)
    if hi is None:
        hi = jnp.full((m,), HI, jnp.float32)
    width = (hi - lo) / n_bins
    bin_lo, _, found = locate_bin(cnt, rank, n_bins, lo, hi)
    b_clip = jnp.clip(
        jnp.sum((cnt > rank).astype(jnp.int32), axis=1) - 1, 0, n_bins - 1
    )
    c_lo = jnp.take_along_axis(cnt, b_clip[:, None], axis=1)[:, 0]
    c_hi = jnp.where(
        b_clip + 1 < n_bins,
        jnp.take_along_axis(
            cnt, jnp.clip(b_clip + 1, 0, n_bins - 1)[:, None], axis=1
        )[:, 0],
        0.0,
    )
    frac = (c_lo - rank) / jnp.maximum(c_lo - c_hi, 1.0)
    v = bin_lo + jnp.clip(frac, 0.0, 1.0) * width
    return jnp.where(found, jnp.maximum(v, 0.0), 0.0)
