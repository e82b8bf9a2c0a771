"""jit'd public wrappers around the Pallas kernels.

`bip_dual_update(s, q0, top_k, n_iters)` is a drop-in for the exact oracle in
repro.core.ref_bip (the router dispatches here when RouterConfig.use_kernel).

interpret=None resolves from the platform (kernels/platform.py): the kernel
bodies run in the Pallas interpreter on the CPU and lower to Mosaic on a TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import bip_admm as _bip
from repro.kernels import moe_gemm as _gemm
from repro.kernels.platform import interpret_default, varying_operands
from repro.telemetry.trace import named_span


@functools.partial(
    jax.jit,
    static_argnames=("top_k", "n_iters", "n_bins", "refine", "interpret", "axis_names"),
)
def bip_dual_update(
    s: jnp.ndarray,
    q0: jnp.ndarray,
    *,
    top_k: int,
    n_iters: int,
    n_bins: int = 512,
    refine: int = 1,
    interpret: Optional[bool] = None,
    axis_names: tuple = (),
    token_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """T fused ADMM iterations on the (n, m) score matrix. Returns q (m,).

    Each iteration runs 1 coarse histogram pass over [-1, 1] plus `refine`
    passes over the located bin (per-expert bounds), so the order-statistic
    resolution is (2/n_bins)^(refine+1)·… ≈ 8e-6 at the defaults — tighter
    than fp32 softmax score gaps (validated in tests/test_kernels.py).

    `token_mask` (n,) bool marks real tokens (serving padding is False):
    masked rows take the kernel's pad value, so they never enter a
    histogram, and the capacity rank is floor(n_real·k/m) over the real
    rows. A call with no real token leaves q0 unchanged (idle engine step).

    With `axis_names` (the collective form, sync='global' under shard_map):
    `s` is the device-local (n_local, m) token shard, the counting pass
    stays fully local, and the (m, n_bins) histogram counts are psum'd
    across the mesh axes between the count pass and the rank location —
    one fused collective per pass, refine+1 per dual iteration — so every
    device locates the SAME global order statistic. The real-token count is
    psum'd the same way, and the q carry starts from the replicated q0 so
    the result can leave the shard_map under an out_spec of P(None).
    """
    interpret = interpret_default() if interpret is None else interpret
    n, m = s.shape
    axis_names = tuple(axis_names)
    if token_mask is None:
        n_real = jnp.asarray(n, jnp.int32)
    else:
        n_real = jnp.sum(token_mask.astype(jnp.int32))
    if axis_names:
        n_real = lax.psum(n_real, axis_names)
    rank = (n_real * top_k) // m  # cap index: want the (rank+1)-th largest
    slack = rank >= n_real  # more capacity than tokens: the constraint never binds
    st = _bip.transpose_scores(s, token_mask)  # loop-invariant, built once

    def body(_, q):
        lo = jnp.full((m,), _bip.LO, jnp.float32)
        hi = jnp.full((m,), _bip.HI, jnp.float32)
        for _pass in range(refine + 1):
            _p, cnt = _bip.iteration_on_transposed(
                st, q, lo, hi, top_k=top_k, n_bins=n_bins, interpret=interpret,
            )
            if axis_names:
                cnt = lax.psum(cnt, axis_names)
            cur_lo, cur_hi = lo, hi  # bounds this cnt was computed over
            bin_lo, bin_hi, found = _bip.locate_bin(cnt, rank, n_bins, lo, hi)
            lo = jnp.where(found, bin_lo, lo)
            hi = jnp.where(found, bin_hi, hi)
        q_new = _bip.q_from_histogram(cnt, rank, n_bins, lo=cur_lo, hi=cur_hi)
        return jnp.where(slack, jnp.zeros_like(q_new), q_new)

    if axis_names:
        # the carry must stay REPLICATED: q_new is assembled from psum'd
        # counts, so starting from the replicated q0 keeps the types aligned
        q_init = q0.astype(jnp.float32)
    else:
        # inherit s's varying-manual-axes type for the loop carry (shard_map)
        q_init = q0.astype(jnp.float32) + 0.0 * s[0].astype(jnp.float32)
    q = lax.fori_loop(0, n_iters, body, q_init)
    return jnp.where(n_real > 0, q, q_init)


# ----------------------------------------------- grouped expert FFN (model path)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


@functools.lru_cache(maxsize=None)
def _expert_ffn_vjp(interpret: bool):
    """custom_vjp'd grouped FFN over MXU-aligned (padded) shapes.

    Forward is the fused Pallas pair (grouped_gated_ffn_in + grouped_matmul).
    Backward rematerializes the gate/up pre-activations and expresses every
    dgrad/wgrad as a grouped_matmul (the wgrads read the stored activations
    transposed), so training never falls back to differentiating through
    pallas_call. Each product picks its own tiles from its shape
    (moe_gemm.pick_tiles). Each phase has its own scope under the caller's
    (`moe/gemm`): `fwd`, `bwd/remat`, `bwd/dgrad` (dh, dx and the SwiGLU
    derivative between them) and `bwd/wgrad` (dwd, dwg, dwu).
    """
    mm = functools.partial(_gemm.grouped_matmul, interpret=interpret)

    @jax.custom_vjp
    def f(x, wg, wu, wd):
        with named_span("fwd"):
            h = _gemm.grouped_gated_ffn_in(x, wg, wu, interpret=interpret)
            return mm(h, wd)

    def fwd(x, wg, wu, wd):
        return f(x, wg, wu, wd), (x, wg, wu, wd)

    def bwd(res, dy):
        x, wg, wu, wd = res
        t = lambda a: jnp.swapaxes(a, -1, -2)
        with named_span("bwd/remat"):
            # rematerialize pre-activations: residuals are just the inputs
            g = mm(x, wg)
            u = mm(x, wu)
            gf = g.astype(jnp.float32)
            uf = u.astype(jnp.float32)
            sg = jax.nn.sigmoid(gf)
            silu = gf * sg
            h = (silu * uf).astype(x.dtype)
        with named_span("bwd/dgrad"):
            dh = mm(dy, t(wd))
            dhf = dh.astype(jnp.float32)
            dg = (dhf * uf * (sg * (1.0 + gf * (1.0 - sg)))).astype(x.dtype)
            du = (dhf * silu).astype(x.dtype)
            dx = mm(dg, t(wg)) + mm(du, t(wu))
        with named_span("bwd/wgrad"):
            dwd = mm(h, dy, trans_a=True)
            dwg = mm(x, dg, trans_a=True)
            dwu = mm(x, du, trans_a=True)
        return dx, dwg, dwu, dwd

    f.defvjp(fwd, bwd)
    return f


def expert_ffn(
    x: jnp.ndarray,       # (E, C, D)
    w_gate: jnp.ndarray,  # (E, D, F)
    w_up: jnp.ndarray,    # (E, D, F)
    w_down: jnp.ndarray,  # (E, F, D)
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Differentiable grouped expert FFN with automatic MXU alignment.

    Pads capacity/d/f up to multiples of 128 (zero rows/columns are exact:
    they contribute nothing through the GEMMs and the SwiGLU of zeros is
    zero), runs the Pallas kernel pair under a custom_vjp whose backward is
    itself grouped GEMMs, and slices the padding back off. This is the
    entry point the model path (models/moe._expert_ffn) uses when
    cfg.routing.use_kernel is set.
    """
    interpret = interpret_default() if interpret is None else interpret
    # inside a shard_map: cast outside the custom_vjp, so autodiff turns the
    # cast of an operand that varies over fewer mesh axes into the psum its
    # cotangent needs
    _, (x, w_gate, w_up, w_down) = varying_operands(x, w_gate, w_up, w_down)
    e, c, d = x.shape
    f = w_gate.shape[-1]
    cp, dp, fp = _round_up(c, 128), _round_up(d, 128), _round_up(f, 128)

    def pad(a, rows, cols):
        return jnp.pad(a, ((0, 0), (0, rows - a.shape[1]), (0, cols - a.shape[2])))

    y = _expert_ffn_vjp(bool(interpret))(
        pad(x, cp, dp), pad(w_gate, dp, fp), pad(w_up, dp, fp), pad(w_down, fp, dp)
    )
    return y[:, :c, :d]
