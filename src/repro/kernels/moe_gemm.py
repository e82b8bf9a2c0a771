"""Grouped expert-FFN Pallas kernels (capacity-packed MoE compute).

After BIP-balanced dispatch, expert inputs sit in a dense (E, C, D) buffer
(C = capacity). The FFN is two grouped GEMMs with a gated activation between;
kernel 1 fuses the gate/up pair and the SwiGLU product so the (E, C, F)
hidden tensor is produced in one pass over x:

    h = silu(x @ w_gate) * (x @ w_up)        kernel: grouped_gated_ffn_in
    y = h @ w_down                           kernel: grouped_matmul

Each kernel is one grouped (rows, contraction) @ (contraction, cols)
product per expert on the grid (E, cols/bn, rows/bm, contraction/bk). The
rows axis runs inside the cols axis, so the weight block (the right
operand) keeps its index across consecutive steps and is fetched once per
(expert, col-block). Operands go into the MXU in their own dtype
(bfloat16 on the model path: its products are exact in float32) and are
summed in float32; a contraction split over several steps accumulates in
float32 VMEM scratch, one taken whole needs none.

`pick_tiles` sizes the blocks from the shape: each block is a multiple of
128 that divides its dimension, or the whole dimension, and the step's
double-buffered blocks, float32 products and accumulators fit VMEM_BUDGET.
The kernels raise Mosaic's scoped-VMEM limit for a tile that needs more
than its default.

Balance synergy (the paper's point): with MaxVio ≲ 0.2 the capacity C can be
~1.25·k·n/m, so the (E, C) grid is nearly padding-free; under aux-loss
routing early in training C must be ~2·k·n/m and half the MXU issue slots
compute zeros.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_default, varying_operands

MXU = 128
# what the picker lets one grid step hold (vmem_bytes); a v5e core has 128 MiB
VMEM_BUDGET = 32 * 2**20
# Mosaic's scoped-VMEM limit on v5e when a kernel names none
SCOPED_VMEM_DEFAULT = 16 * 2**20


class Tiles(NamedTuple):
    """Block sizes of one grouped product: rows, contraction, cols."""

    bm: int
    bk: int
    bn: int


def vmem_bytes(t: Tiles, k: int, itemsize: int, n_rhs: int = 1,
               trans_a: bool = False) -> int:
    """Scoped VMEM one grid step holds, by count: the double-buffered blocks
    of the left operand, the `n_rhs` right operands and the output (all of
    `itemsize`); the float32 products, and as many float32 accumulators
    again when the contraction `k` takes more than one step; the output
    block cast from float32; with `trans_a` the left block transposed."""
    blocks = t.bm * t.bk + n_rhs * t.bk * t.bn + t.bm * t.bn
    f32 = n_rhs * t.bm * t.bn * 4 * (2 if t.bk < k else 1)
    temps = (t.bm * t.bn + (t.bm * t.bk if trans_a else 0)) * itemsize
    return 2 * blocks * itemsize + f32 + temps


def vmem_limit(need: int) -> Optional[int]:
    """The scoped-VMEM limit a kernel of `need` counted bytes asks Mosaic
    for: half as much again, for what Mosaic lays out besides (measured
    against its compiler for v5e at the model's widths); None where
    Mosaic's default covers that."""
    limit = need + need // 2
    return limit if limit > SCOPED_VMEM_DEFAULT else None


def _blocks(dim: int) -> list:
    """Legal blocks of a dimension: the whole of it, and the multiples of
    128 that divide it."""
    return sorted({dim} | {b for b in range(MXU, dim, MXU) if dim % b == 0})


def pick_tiles(m: int, k: int, n: int, dtype, *, n_rhs: int = 1,
               trans_a: bool = False) -> Tiles:
    """Blocks for a grouped (m, k) @ (k, n) product with `n_rhs` right
    operands: of the legal blocks whose step fits VMEM_BUDGET, the most work
    per step (fewest grid steps, fewest re-reads of either operand); among
    equals, the longer contraction, then the wider output."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = [
        Tiles(bm, bk, bn)
        for bm in _blocks(m) for bk in _blocks(k) for bn in _blocks(n)
        if vmem_bytes(Tiles(bm, bk, bn), k, itemsize, n_rhs, trans_a) <= VMEM_BUDGET
    ]
    if not fits:
        raise ValueError(f"no tile of ({m}, {k}) @ ({k}, {n}) fits {VMEM_BUDGET} bytes")
    return max(fits, key=lambda t: (t.bm * t.bk * t.bn, t.bk, t.bn))


def _operands(interpret: bool, *xs):
    """(vma, xs) for a grouped GEMM; see platform.varying_operands."""
    vma, xs = varying_operands(*xs)
    if interpret and vma:
        # jax 0.9.0's Pallas interpreter rebuilds the kernel's types without
        # shard_map's varying axes and fails inside the body; say so here
        raise NotImplementedError(
            "the grouped-FFN kernels run inside a shard_map only under Mosaic "
            "(a TPU): the Pallas interpreter of this JAX drops varying mesh "
            "axes. Use use_kernel=False for expert-parallel runs on the CPU."
        )
    return vma, xs


def _call(kernel, args, specs, out_spec, out_shape, *, t: Tiles, k: int, n_rhs: int,
          trans_a: bool, name: str, interpret: bool):
    """The pallas_call of a grouped product on the (E, cols, rows, contraction)
    grid, with float32 accumulators when the contraction is split."""
    e = args[0].shape[0]
    m, n = out_shape.shape[1:]
    nk = k // t.bk
    need = vmem_bytes(t, k, jnp.dtype(args[0].dtype).itemsize, n_rhs, trans_a)
    return pl.pallas_call(
        kernel,
        grid=(e, n // t.bn, m // t.bm, nk),
        in_specs=specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((t.bm, t.bn), jnp.float32)] * (n_rhs if nk > 1 else 0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
        name=name,
    )(*args)


def _accumulate(accs, prods, finish):
    """Sum the step's float32 products over the contraction axis (grid axis
    3) and hand the totals to `finish` at its last step; a contraction taken
    whole hands them over at once."""
    if not accs:
        finish(*prods)
        return
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    for acc, p in zip(accs, prods):
        acc[...] += p

    @pl.when(k == pl.num_programs(3) - 1)
    def _done():
        finish(*(acc[...] for acc in accs))


def _gated_in_kernel(x_ref, wg_ref, wu_ref, h_ref, *accs):
    """One (expert, f-block, c-block, d-block) step of h = silu(x wg) * (x wu)."""
    x = x_ref[0]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)

    def finish(g, u):
        h_ref[0] = (jax.nn.silu(g) * u).astype(h_ref.dtype)

    _accumulate(accs, (g, u), finish)


def grouped_gated_ffn_in(
    x: jnp.ndarray,   # (E, C, D)
    w_gate: jnp.ndarray,  # (E, D, F)
    w_up: jnp.ndarray,    # (E, D, F)
    *,
    tiles: Optional[Tiles] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """h = silu(x w_gate) * (x w_up) per expert; `tiles` (rows C,
    contraction D, cols F) default to pick_tiles'."""
    interpret = interpret_default() if interpret is None else interpret
    vma, (x, w_gate, w_up) = _operands(interpret, x, w_gate, w_up)
    e, c, d = x.shape
    f = w_gate.shape[-1]
    t = tiles or pick_tiles(c, d, f, x.dtype, n_rhs=2)
    assert c % t.bm == 0 and d % t.bk == 0 and f % t.bn == 0, (c, d, f, t)
    w_spec = pl.BlockSpec((1, t.bk, t.bn), lambda e_, j, i, k: (e_, k, j))
    return _call(
        _gated_in_kernel,
        (x, w_gate, w_up),
        [pl.BlockSpec((1, t.bm, t.bk), lambda e_, j, i, k: (e_, i, k)), w_spec, w_spec],
        pl.BlockSpec((1, t.bm, t.bn), lambda e_, j, i, k: (e_, i, j)),
        jax.ShapeDtypeStruct((e, c, f), x.dtype, vma=vma),
        t=t, k=d, n_rhs=2, trans_a=False, name="moe_gated_in", interpret=interpret,
    )


def _matmul_kernel(a_ref, b_ref, y_ref, *accs, trans_a: bool):
    # trans_a: the left block is stored (contraction, rows)
    dims = (((0,), (0,)), ((), ())) if trans_a else (((1,), (0,)), ((), ()))
    p = lax.dot_general(a_ref[0], b_ref[0], dims, preferred_element_type=jnp.float32)

    def finish(y):
        y_ref[0] = y.astype(y_ref.dtype)

    _accumulate(accs, (p,), finish)


def grouped_matmul(
    a: jnp.ndarray,   # (E, M, K), or (E, K, M) with trans_a
    b: jnp.ndarray,   # (E, K, N)
    *,
    trans_a: bool = False,
    tiles: Optional[Tiles] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y = a @ b per expert (aᵀ @ b with `trans_a`, reading a as stored);
    `tiles` (rows M, contraction K, cols N) default to pick_tiles'."""
    interpret = interpret_default() if interpret is None else interpret
    vma, (a, b) = _operands(interpret, a, b)
    e, k, n = b.shape
    m = a.shape[2] if trans_a else a.shape[1]
    t = tiles or pick_tiles(m, k, n, a.dtype, trans_a=trans_a)
    assert m % t.bm == 0 and k % t.bk == 0 and n % t.bn == 0, (m, k, n, t)
    if trans_a:
        a_spec = pl.BlockSpec((1, t.bk, t.bm), lambda e_, j, i, k_: (e_, k_, i))
    else:
        a_spec = pl.BlockSpec((1, t.bm, t.bk), lambda e_, j, i, k_: (e_, i, k_))
    return _call(
        lambda *refs: _matmul_kernel(*refs, trans_a=trans_a),
        (a, b),
        [a_spec, pl.BlockSpec((1, t.bk, t.bn), lambda e_, j, i, k_: (e_, k_, j))],
        pl.BlockSpec((1, t.bm, t.bn), lambda e_, j, i, k_: (e_, i, j)),
        jax.ShapeDtypeStruct((e, m, n), a.dtype, vma=vma),
        t=t, k=k, n_rhs=1, trans_a=trans_a, name="moe_matmul", interpret=interpret,
    )
