"""Grouped expert-FFN Pallas kernels (capacity-packed MoE compute).

After BIP-balanced dispatch, expert inputs sit in a dense (E, C, D) buffer
(C = capacity). The FFN is two grouped GEMMs with a gated activation between;
kernel 1 fuses the gate/up pair and the SwiGLU product so the (E, C, F)
hidden tensor is produced in one pass over x:

    h = silu(x @ w_gate) * (x @ w_up)        kernel: grouped_gated_ffn_in
    y = h @ w_down                           kernel: grouped_matmul

Tiling: grid (E, C/bc, F/bf) with an inner fori_loop over D/bd accumulating
in VMEM scratch — MXU-aligned block shapes (multiples of 128 on the minor
two dims). BlockSpec streams one expert's tiles at a time, so VMEM holds
bc·bd + 2·bd·bf + 2·bc·bf floats (~2 MB at the default 256/512/256).

Balance synergy (the paper's point): with MaxVio ≲ 0.2 the capacity C can be
~1.25·k·n/m, so the (E, C) grid is nearly padding-free; under aux-loss
routing early in training C must be ~2·k·n/m and half the MXU issue slots
compute zeros.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_default, varying_operands


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


def _gated_in_kernel(x_ref, wg_ref, wu_ref, h_ref, acc_g, acc_u):
    """One (expert, c-block, f-block) tile of h = silu(x wg) * (x wu)."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    x = x_ref[0].astype(jnp.float32)    # (bc, bd)
    wg = wg_ref[0].astype(jnp.float32)  # (bd, bf)
    wu = wu_ref[0].astype(jnp.float32)
    acc_g[...] += jnp.dot(x, wg, preferred_element_type=jnp.float32)
    acc_u[...] += jnp.dot(x, wu, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        h_ref[0] = (jax.nn.silu(acc_g[...]) * acc_u[...]).astype(h_ref.dtype)


def grouped_gated_ffn_in(
    x: jnp.ndarray,   # (E, C, D)
    w_gate: jnp.ndarray,  # (E, D, F)
    w_up: jnp.ndarray,    # (E, D, F)
    *,
    block_c: int = 128,
    block_f: int = 256,
    block_d: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = interpret_default() if interpret is None else interpret
    vma, (x, w_gate, w_up) = _operands(interpret, x, w_gate, w_up)
    e, c, d = x.shape
    f = w_gate.shape[-1]
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    assert c % bc == 0 and f % bf == 0 and d % bd == 0, (c, f, d, bc, bf, bd)
    grid = (e, c // bc, f // bf, d // bd)
    return pl.pallas_call(
        _gated_in_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e_, i, j, k: (e_, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e_, i, j, k: (e_, k, j)),
            pl.BlockSpec((1, bd, bf), lambda e_, i, j, k: (e_, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, i, j, k: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bf), jnp.float32),
        ],
        interpret=interpret,
        name="moe_gated_in",
    )(x, w_gate, w_up)


def _matmul_kernel(h_ref, w_ref, y_ref, acc):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(
        h_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _done():
        y_ref[0] = acc[...].astype(y_ref.dtype)


def grouped_matmul(
    h: jnp.ndarray,   # (E, C, F)
    w: jnp.ndarray,   # (E, F, D)
    *,
    block_c: int = 128,
    block_d: int = 256,
    block_f: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = interpret_default() if interpret is None else interpret
    vma, (h, w) = _operands(interpret, h, w)
    e, c, f = h.shape
    d = w.shape[-1]
    bc, bd, bf = min(block_c, c), min(block_d, d), min(block_f, f)
    assert c % bc == 0 and d % bd == 0 and f % bf == 0
    grid = (e, c // bc, d // bd, f // bf)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bf), lambda e_, i, j, k: (e_, i, k)),
            pl.BlockSpec((1, bf, bd), lambda e_, i, j, k: (e_, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd), lambda e_, i, j, k: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), h.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        interpret=interpret,
        name="moe_matmul",
    )(h, w)


def expert_ffn(
    x: jnp.ndarray,      # (E, C, D)
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # (E, F, D)
    *,
    interpret: Optional[bool] = None,
    **block_kw,
) -> jnp.ndarray:
    """Full grouped expert FFN: y = (silu(x wg) * (x wu)) wd.

    Raw aligned-shape kernel pair; for the differentiable, auto-padded
    entry point used by the model path see repro.kernels.ops.expert_ffn.
    """
    interpret = interpret_default() if interpret is None else interpret
    h = grouped_gated_ffn_in(x, w_gate, w_up, interpret=interpret, **block_kw)
    return grouped_matmul(h, w_down, interpret=interpret, **block_kw)
