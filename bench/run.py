"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload train-16e --seed 7 --seconds 10 --trace 0

A cell (an entry of `workloads` in BENCHMARK.json) names a configuration
(its file under bench/configs/) and a traffic mix (bench/traffic/<mix>.json,
whose `kind` picks the runner bench/kinds/<kind>.py and whose `generator`
picks the file bench/generators/<generator>.py that makes its inputs). The
runner sets the cell up from the seed, measures for `--seconds`, and checks
what the timed path produced against the plain reference named by the
configuration, with the limits in bench/limits/<cell>.json.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer metrics, each read by bench/metrics/<name>.py), `device`, with
`--trace 1` also `breakdown`, and last `checks`: every number compared with
its limit, which are also the last lines of standard error. Without a TPU,
or with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

if __name__ == "__main__":  # the kind runners import this module as `run`
    sys.modules.setdefault("run", sys.modules[__name__])

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")


class SetupError(RuntimeError):
    """The cell cannot run here (no chip, missing files): no result."""


def load_module(path: str, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(
        name or os.path.splitext(os.path.basename(path))[0].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SetupError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Spec:
    """Everything the benchmark's files say about one cell."""

    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic mix's parameters
    limits: dict          # bench/limits/<cell>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list


def load_spec(workload: str, root: str = ROOT) -> Spec:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return spec_from_files(workload, configs[cell["config"]]["file"], cell["traffic"],
                           cell["chips"], e2e, per_layer, root)


def spec_from_files(workload, config_file, traffic, chips, e2e, per_layer, root=ROOT) -> Spec:
    """A cell from its files: configuration, traffic mix, limits."""
    config = _read_json(os.path.join(root, config_file))
    mix = _read_json(os.path.join(root, "bench", "traffic", traffic + ".json"))
    limits = _read_json(os.path.join(root, "bench", "limits", workload + ".json"))
    return Spec(workload, chips, config, mix, limits, e2e, per_layer)


def seed_key(seed: int):
    """A PRNG key from any whole number, including ones past 32 bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def generator(mix: dict):
    """The module that makes a traffic mix's inputs."""
    path = os.path.join(BENCH, "generators", mix["generator"] + ".py")
    if not os.path.isfile(path):
        raise SetupError(f"missing file {os.path.relpath(path, ROOT)}")
    return load_module(path, "generator_" + mix["generator"])


def judge(checks: Dict[str, tuple]) -> bool:
    """`correct`: every compared number finite and within its limit."""
    return all(math.isfinite(v) and v <= lim for v, lim in checks.values())


COMPILES = [0]  # backend compilations in this process, for the window check
CACHE = {"hits": 0, "compile_s": 0.0}


def _count_compile(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILES[0] += 1
        CACHE["compile_s"] += duration


def _count_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        CACHE["hits"] += 1


def setup_jax():
    """The persistent compile cache at one fixed path inside the checkout,
    for the benchmark and for the program alike."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    if _count_compile not in getattr(setup_jax, "_listening", ()):
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        jax.monitoring.register_event_listener(_count_event)
        setup_jax._listening = (_count_compile,)

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    for path in (os.path.join(ROOT, "src"), BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    return jax


def check_devices(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise SetupError(f"needs {chips} chips; JAX found {len(devices)}")
    return devices


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip: the allocator's peak of live buffers
    plus its peak of memory reserved for running programs (their temporaries,
    which `peak_bytes_in_use` leaves out on a TPU)."""
    if devices[0].platform != "tpu":
        return 0
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats)


@dataclasses.dataclass
class Context:
    spec: Spec
    seed: int
    seconds: float
    trace: bool
    devices: Any
    t_start: float
    trace_dir: str = TRACE_DIR


def read_per_layer(spec: Spec, record: dict) -> Dict[str, dict]:
    out = {}
    for m in spec.per_layer:
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def metric_scopes(spec: Spec):
    """Scope paths the cell's per-layer readers ask the trace reduction for."""
    scopes = []
    for m in spec.per_layer:
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        scopes.extend(s for s in getattr(reader, "SCOPES", ()) if s not in scopes)
    return scopes


def run_cell(ctx: Context) -> dict:
    """Drive the cell's kind and assemble the result line."""
    kind = load_module(os.path.join(BENCH, "kinds", ctx.spec.traffic["kind"] + ".py"))
    out = kind.run(ctx)
    checks = out["checks"]  # {name: (value, limit)}
    correct = judge(checks)
    if ctx.trace:
        metrics = read_per_layer(ctx.spec, out["record"])
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in ctx.spec.end_to_end}
    dev = ctx.devices[0]
    result = {
        "correct": bool(correct and out.get("ok", True)),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(ctx.devices), "memory_peak_bytes": out["memory_peak_bytes"]},
    }
    if ctx.trace and out.get("trace"):
        tr = out["trace"]
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        jax = setup_jax()
        devices = check_devices(jax, spec.chips)
    except (SetupError, RuntimeError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    ctx = Context(spec, args.seed, args.seconds, bool(args.trace), devices, T_START)
    result = run_cell(ctx)
    print(f"programs: {COMPILES[0]} built in {CACHE['compile_s']:.1f} s, {CACHE['hits']} of them "
          f"read from the compile cache {CACHE_DIR}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
