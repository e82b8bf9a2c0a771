"""The yardstick's arithmetic: chip peaks and operation counts from shapes.

Everything here is computed from a configuration file's sizes, never from the
program under test, so a change to the program cannot change what is counted.

* `PEAKS` -- published peaks per `jax.Device.device_kind`. A kind that is not
  in the table is an error, never a default.
* `matmul_params_per_token` -- parameters a token multiplies through (the
  active experts only), the N of MFU's 6·N.
* `train_flops_per_token` / `forward_flops_per_token` -- model FLOPs of the
  algorithm: matmuls and causal attention, no recomputation, no capacity
  padding.
* `expert_ffn_cost` -- FLOPs and HBM bytes of one grouped expert-FFN call on
  its (experts, capacity, d) buffer, whatever kernel implements it.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s per chip
    hbm_bytes: float   # bytes/s per chip
    hbm_capacity: float  # bytes per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes=819e9,
        hbm_capacity=16e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None


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


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per trained token at `seq_len`: 6·N for
    the matmuls, 3x the forward causal attention (a query at position i
    attends i+1 keys, (S+1)/2 on average)."""
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * attention_flops_per_token(
        cfg, (seq_len + 1) / 2.0)


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """Forward model FLOPs of one processed token that attends `context` keys."""
    return 2.0 * matmul_params_per_token(cfg) + attention_flops_per_token(cfg, context)


def expert_capacity(cfg: dict, n_tokens: int) -> int:
    """Rows per expert buffer: ceil(k·n/m · capacity_factor)."""
    return max(int(math.ceil(cfg["top_k"] * n_tokens / cfg["n_experts"]
                             * cfg["capacity_factor"])), 1)


def expert_ffn_cost(cfg: dict, n_experts: int, capacity: int, act_bytes: int = 2):
    """(FLOPs, HBM bytes) of one forward grouped SwiGLU call on an
    (n_experts, capacity, d) buffer: gate, up and down matmuls; bytes are the
    three weight tensors, the input buffer and the output buffer read or
    written once in `act_bytes`-wide elements."""
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    flops = 2.0 * n_experts * capacity * d * f * 3
    nbytes = act_bytes * (3.0 * n_experts * d * f + 2.0 * n_experts * capacity * d)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Peaks):
    """Roofline: the larger of compute time and memory time, and which bounds."""
    t_c, t_m = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
