"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input: the `.xplane.pb` of a traced stretch of a run, in which the harness
wrapped the traced work in one host annotation (`WINDOW`). Output, with
times in seconds:

* `window_s`: length of that annotation; `busy_s`: union of the intervals in
  which an op ran on the device inside it, averaged over the devices.
* `scope_s[name]`: device self time of the ops whose scope path (`tf_op`)
  passes through `name/` (for example `router/`, `moe/gemm/`), summed over
  devices. Self time is an op's duration less the ops nested in it on the
  same line, so a `while` loop and its body count once.
* `device_ops`: the ops that took the most self time, by scope path.
* `idle_gaps`: device idle time inside the window, summed by the innermost
  host annotation that was open at the gap's midpoint.

Device ops are the events of each device plane's "XLA Ops" line. The
device clock in a v5e trace runs about a millisecond off the host's, so each
device is shifted onto the host clock: its first program ("XLA Modules")
starts when the host's first `PjitFunction` call inside the window starts,
and never before the window opens. Gap names are therefore approximate for
gaps shorter than a launch (tens of microseconds).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

try:
    from . import xplane
except ImportError:  # loaded as a plain module from bench/
    import xplane

WINDOW = "bench/window"
PS = 1e-12


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _self_times(events: List[xplane.Event]) -> List[Tuple[xplane.Event, int]]:
    """(event, duration less its nested events) for events of one line."""
    evs = sorted(events, key=lambda e: (e.start_ps, -e.dur_ps))
    out: List[list] = []
    stack: List[list] = []  # [event, end, self]
    for e in evs:
        end = e.start_ps + e.dur_ps
        while stack and stack[-1][1] <= e.start_ps:
            stack.pop()
        rec = [e, end, e.dur_ps]
        if stack and end <= stack[-1][1]:
            stack[-1][2] -= e.dur_ps
        out.append(rec)
        stack.append(rec)
    return [(r[0], max(r[2], 0)) for r in out]


def scope_of(event: xplane.Event) -> str:
    return str(event.stats.get("tf_op") or event.name)


def _short(path: str, limit: int = 140) -> str:
    path = re.sub(r"^jit\([^)]*\)/", "", path).rstrip(":")
    return path if len(path) <= limit else "..." + path[-limit:]


def reduce_trace(path: str, scopes: Iterable[str] = (), n_top: int = 10) -> Dict:
    planes = xplane.read_planes(path)
    host_events: List[xplane.Event] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host_events.extend(line.events)
    windows = [e for e in host_events if e.name == WINDOW]
    if not windows:
        raise ValueError(f"trace {path} has no {WINDOW!r} annotation")
    win = max(windows, key=lambda e: e.dur_ps)
    lo, hi = win.start_ps, win.start_ps + win.dur_ps
    spans = sorted((e for e in host_events
                    if e is not win and e.dur_ps > 0 and e.start_ps < hi
                    and e.start_ps + e.dur_ps > lo), key=lambda e: e.start_ps)
    span_starts = [e.start_ps for e in spans]

    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"trace {path} has no TPU device plane")
    scopes = list(scopes)
    pats = {s: re.compile(r"(^|[/(])" + re.escape(s) + r"(/|\)|$)") for s in scopes}
    busy_total = 0
    scope_ps: Dict[str, int] = defaultdict(int)
    op_ps: Dict[str, int] = defaultdict(int)
    gap_ps: Dict[str, int] = defaultdict(int)
    launches = [e.start_ps for e in spans if e.name.startswith("PjitFunction")
                and e.start_ps >= lo]
    for dev in devices:
        ops = [e for line in dev.lines if line.name == "XLA Ops" for e in line.events]
        mods = [e.start_ps for line in dev.lines if line.name == "XLA Modules"
                for e in line.events]
        if not ops:
            continue
        first = min(mods) if mods else min(e.start_ps for e in ops)
        shift = max((min(launches) if launches else lo) - first, lo - first)
        ops = [xplane.Event(e.name, e.start_ps + shift, e.dur_ps, e.stats) for e in ops]
        ops = [e for e in ops if e.start_ps < hi and e.start_ps + e.dur_ps > lo]
        busy = _clip(_union((e.start_ps, e.start_ps + e.dur_ps) for e in ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for e, self_ps in _self_times(ops):
            sc = scope_of(e)
            op_ps[_short(sc)] += self_ps
            for s, pat in pats.items():
                if pat.search(sc):
                    scope_ps[s] += self_ps
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) // 2
            name, best = "(no host span)", None
            i = bisect.bisect_right(span_starts, mid)
            for sp in reversed(spans[max(0, i - 2000):i]):
                if sp.start_ps + sp.dur_ps >= mid and (best is None or sp.dur_ps < best):
                    name, best = sp.name, sp.dur_ps
            gap_ps[name] += e - s
    n = len(devices)
    top = lambda d: [[k, v * PS] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n_top]]
    return {
        "n_devices": n,
        "window_s": (hi - lo) * PS,
        "busy_s": busy_total * PS / n,
        "scope_s": {s: scope_ps.get(s, 0) * PS for s in scopes},
        "device_ops": top(op_ps),
        "idle_gaps": top(gap_ps),
    }
