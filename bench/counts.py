"""The yardstick's arithmetic: chip peaks and operation counts from shapes.

Everything here is computed from a configuration file's sizes, never from the
program under test, so a change to the program cannot change what is counted.

* `PEAKS` -- published peaks per `jax.Device.device_kind`. A kind that is not
  in the table is an error, never a default.
* `matmul_params_per_token`, `attention_flops_per_token`, `moe_layers` --
  the architecture's own counts, which the plain reference that the
  configuration names exports (bench/refs/<model>.py): the parameters a
  token multiplies through (the active experts only, the N of MFU's 6·N),
  the forward attention FLOPs of one query over `context` keys, and the
  number of layers that run the grouped expert FFN. A reference without
  them is an error, never a default.
* `train_flops_per_token` / `forward_flops_per_token` -- model FLOPs of the
  algorithm, composed from the reference's counts: matmuls and attention, no
  recomputation, no capacity padding.
* `expert_capacity` -- rows per expert buffer, over the router's width
  (`n_experts`, as published), as the program sizes it.
* `experts_held` -- the experts this chip holds of each expert layer: the
  configuration's `experts_held`, one chip's share of a layer whose experts
  are divided evenly over several chips, or all `n_experts` where it states
  none.
* `expert_ffn_cost` -- FLOPs and HBM bytes of one grouped expert-FFN call on
  its (experts, capacity, d) buffer, whatever kernel implements it; the
  readers give it the experts held.
"""
from __future__ import annotations

import dataclasses
import math
import os

COUNTED = ("matmul_params_per_token", "attention_flops_per_token", "moe_layers")


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


def reference(cfg: dict):
    """The plain reference module the configuration names (`reference`, a
    path from the repository's root)."""
    from run import ROOT, load_module

    return load_module(os.path.join(ROOT, cfg["reference"]))


def _counted(cfg: dict, name: str):
    fn = getattr(reference(cfg), name, None)
    if fn is None:
        raise AttributeError(f"the reference {cfg['reference']} has no counting function "
                             f"{name!r}; each reference exports {COUNTED}")
    return fn


def matmul_params_per_token(cfg: dict) -> int:
    return _counted(cfg, "matmul_params_per_token")(cfg)


def attention_flops_per_token(cfg: dict, context: float) -> float:
    return _counted(cfg, "attention_flops_per_token")(cfg, context)


def moe_layers(cfg: dict) -> int:
    return _counted(cfg, "moe_layers")(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs per trained token at `seq_len`: 6·N for
    the matmuls, 3x the forward attention at the causal mean context (a query
    at position i attends i+1 keys, (S+1)/2 on average)."""
    return 6.0 * matmul_params_per_token(cfg) + 3.0 * attention_flops_per_token(
        cfg, (seq_len + 1) / 2.0)


def forward_flops_per_token(cfg: dict, context: float) -> float:
    """Forward model FLOPs of one processed token that attends `context` keys."""
    return 2.0 * matmul_params_per_token(cfg) + attention_flops_per_token(cfg, context)


def expert_capacity(cfg: dict, n_tokens: int) -> int:
    """Rows per expert buffer: ceil(k·n/m · capacity_factor), with m the
    router's width (`n_experts`), also where this chip holds a share."""
    return max(int(math.ceil(cfg["top_k"] * n_tokens / cfg["n_experts"]
                             * cfg["capacity_factor"])), 1)


def experts_held(cfg: dict) -> int:
    """Experts this chip holds of each expert layer's `n_experts`: the stated
    `experts_held`, else all of them. The experts of a layer are divided
    evenly over the chips that share it, so the share divides `n_experts`."""
    m = cfg["n_experts"]
    held = cfg.get("experts_held", m)
    if not (isinstance(held, int) and 1 <= held <= m and m % held == 0):
        raise ValueError(f"{cfg.get('name', 'config')}: experts_held {held!r} is not an even "
                         f"share of the router's {m} experts")
    return held


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
