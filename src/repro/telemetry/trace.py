"""Tracing plane: span annotations + profiler capture windows.

Two span flavors, one naming convention ("area/phase", lowercase, slash
separated — e.g. "router/score_adjust", "moe/gemm", "train/fwd_bwd"):

  - `named_span(name)` — `jax.named_scope`: names the ops emitted under it
    in the HLO/jaxpr, so XLA profiles and compiler dumps attribute cost to
    the right phase. Safe inside jit/scan/shard_map; zero runtime cost.
  - `trace_span(name)` — `jax.profiler.TraceAnnotation`: a host-side span
    on the profiler timeline for Python-level phases (compile, flush,
    engine step). Must NOT wrap traced code — use named_span there.

`profile_window("N:M")` parses the launcher `--profile` flag; `Profiler`
starts `jax.profiler.start_trace` when the step counter enters [N, M] and
stops after M, so a capture costs nothing outside the window.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax


def named_span(name: str):
    """In-graph scope: names HLO ops for profile attribution (jit-safe)."""
    return jax.named_scope(name)


def trace_span(name: str):
    """Host-side profiler span for un-traced Python phases."""
    return jax.profiler.TraceAnnotation(name)


def profile_window(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """Parse a --profile 'N:M' flag into an inclusive (start, stop) window."""
    if not spec:
        return None
    try:
        lo_s, hi_s = spec.split(":")
        lo, hi = int(lo_s), int(hi_s)
    except ValueError as e:
        raise ValueError(f"--profile expects 'N:M' (got {spec!r})") from e
    if lo < 0 or hi < lo:
        raise ValueError(f"--profile window must satisfy 0 <= N <= M (got {spec!r})")
    return lo, hi


class Profiler:
    """Capture a jax profiler trace for steps N..M (inclusive).

    Call `step(i)` with the current step index each iteration; the trace
    starts on entering the window and stops after leaving it (or at
    `close()` if the run ends mid-window). Idempotent and inert when
    window is None.
    """

    def __init__(self, window: Optional[Tuple[int, int]], log_dir: str = "profile"):
        self.window = window
        self.log_dir = log_dir
        self.active = False

    def step(self, i: int) -> None:
        if self.window is None:
            return
        lo, hi = self.window
        if not self.active and lo <= i <= hi:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self.active = True
        elif self.active and i > hi:
            jax.profiler.stop_trace()
            self.active = False

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False


__all__ = ["Profiler", "named_span", "profile_window", "trace_span"]
