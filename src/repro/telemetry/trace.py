"""Tracing plane: in-graph scopes, host spans, profiler capture windows.

Two span flavors, one naming convention ("area/phase", lowercase, slash
separated — e.g. "router/score_adjust", "moe/gemm", "serve/fetch"):

  - `named_span(name)` — `jax.named_scope`: names the ops emitted under it
    in the HLO/jaxpr, so XLA profiles and compiler dumps attribute cost to
    the right phase. Safe inside jit/scan/shard_map; zero runtime cost.
    Also usable as a function decorator.
  - `trace_span(name, **attrs)` — a host-side span for un-traced Python
    phases (engine step phases, set-up). It writes a
    `jax.profiler.TraceAnnotation` (seen only while a profiler session is
    open) and always appends `Span(name, start_ns, end_ns, parent, attrs)`
    to a bounded in-process buffer read by `spans()`. Times are
    `time.time_ns()`, the realtime clock of the profiler's host plane and of
    `jax.monitoring`'s time spans; `parent` is the name of the innermost
    enclosing `trace_span` of the same context. Must NOT wrap traced code — use
    named_span there.

Every op of the train step sits under exactly one layer scope: `embed`,
`attn`, `norm`, `router/*`, `moe/*`, `lm_head` or `train/apply` (the scan
carries, remat copies and casts XLA places between them stay unscoped).

Program builds are recorded as host spans too: a `jax.monitoring` listener,
registered at import, turns JAX's trace, lowering and backend-compile time
spans into `setup/trace`, `setup/lower` and `setup/compile` spans with
`program=<function name>`; a compile served by the persistent cache carries
`cache_hit=True`.

`profile_window("N:M")` parses the launcher `--profile` flag; `Profiler`
starts `jax.profiler.start_trace` when the step counter enters [N, M] and
stops after M, so a capture costs nothing outside the window.
"""
from __future__ import annotations

import collections
import contextvars
import os
import re
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax

MAX_SPANS = 1 << 16  # buffer bound; the oldest spans are dropped past it


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    attrs: Dict[str, Any]


_buffer: collections.deque = collections.deque(maxlen=MAX_SPANS)
_n_recorded = 0  # spans recorded since the last clear()
_lock = threading.Lock()
_parent: contextvars.ContextVar = contextvars.ContextVar("repro_trace_parent", default=None)


def _record(span: Span) -> None:
    global _n_recorded
    with _lock:
        _n_recorded += 1
        _buffer.append(span)


def spans() -> List[Span]:
    """The recorded host spans, oldest first."""
    with _lock:
        return list(_buffer)


def dropped() -> int:
    """Spans dropped from the buffer since the last `clear()`."""
    with _lock:
        return _n_recorded - len(_buffer)


def clear() -> None:
    global _n_recorded
    with _lock:
        _buffer.clear()
        _n_recorded = 0


def named_span(name: str):
    """In-graph scope: names HLO ops for profile attribution (jit-safe)."""
    return jax.named_scope(name)


class trace_span:
    """Host-side span for un-traced Python phases. The recorded `Span` and
    the profiler annotation carry the attributes given here, and only
    those."""

    __slots__ = ("name", "attrs", "_annotation", "_parent", "_token", "_start")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> None:
        self._parent = _parent.get()
        self._token = _parent.set(self.name)
        self._annotation.__enter__()
        self._start = time.time_ns()

    def __exit__(self, *exc) -> None:
        end = time.time_ns()
        self._annotation.__exit__(*exc)
        _parent.reset(self._token)
        _record(Span(self.name, self._start, end, self._parent, self.attrs))


# ----------------------------------------------------- program build spans

_SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "setup/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "setup/lower",
    "/jax/core/compile/backend_compile_duration": "setup/compile",
}
_compile_hit = threading.local()


def _program(fun_name: str) -> str:
    """'jit(train_step)' -> 'train_step' (lowering and compile events name
    the module; tracing names the function)."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


def _on_time_span(event: str, start_time: float, end_time: float, **kw) -> None:
    name = _SETUP_EVENTS.get(event)
    if name is None:
        return
    attrs: Dict[str, Any] = {"program": _program(str(kw.get("fun_name", "")))}
    if name == "setup/compile":
        attrs["cache_hit"] = getattr(_compile_hit, "hit", False)
        _compile_hit.hit = False
    _record(Span(name, int(start_time * 1e9), int(end_time * 1e9), _parent.get(), attrs))


def _on_event(event: str, **_kw) -> None:
    # fires inside the backend-compile span it belongs to (jax compiler.py)
    if event == "/jax/compilation_cache/cache_hits":
        _compile_hit.hit = True


jax.monitoring.register_event_time_span_listener(_on_time_span)
jax.monitoring.register_event_listener(_on_event)


# ------------------------------------------------------- profiler windows


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


__all__ = [
    "Profiler",
    "Span",
    "clear",
    "dropped",
    "named_span",
    "profile_window",
    "spans",
    "trace_span",
]
