"""Benchmark harness entry point — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # standard pass
    PYTHONPATH=src python -m benchmarks.run --full     # full-length repro runs

Prints ``name,us_per_call,derived`` CSV lines per the repo contract.

Benchmarks:
  table2/table3 (+ per-layer tables 4/5, Fig 1/2 data)  -> benchmarks.paper_repro
  router gate overhead ("very small time costs")        -> benchmarks.router_overhead
  step-time model (the >=13% training-time mechanism)   -> benchmarks.steptime_model
  kernel microbench (ADMM iteration + expert GEMM)      -> below
  dispatch plan old-vs-new + Pallas FFN                 -> benchmarks.moe_dispatch
  streaming data pipeline (tokens/s, prefetch overlap)  -> benchmarks.data_pipeline
  serving throughput + multi-tenant offered-load sweep  -> benchmarks.serve_throughput
  roofline table (if dry-run results exist)             -> benchmarks.roofline
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _kernel_microbench():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rows = []
    rng = np.random.default_rng(0)
    n, m, k = 4096, 64, 8
    e = np.exp(rng.standard_normal((n, m)))
    s = jnp.asarray((e / e.sum(-1, keepdims=True)).astype(np.float32))
    q0 = jnp.zeros((m,), jnp.float32)

    fn = jax.jit(lambda s, q: ops.bip_dual_update(s, q, top_k=k, n_iters=4))
    fn(s, q0).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        out = fn(s, q0)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    rows.append({
        "name": f"kernel_bip_admm_T4_n{n}_m{m}",
        "us_per_call": round(us, 1),
        "derived": "interpret-mode CPU",
    })

    ee, c, d, f = 4, 128, 128, 256
    x = jnp.asarray(rng.standard_normal((ee, c, d)).astype(np.float32)) * 0.3
    wg = jnp.asarray(rng.standard_normal((ee, d, f)).astype(np.float32)) * 0.1
    wu = jnp.asarray(rng.standard_normal((ee, d, f)).astype(np.float32)) * 0.1
    wd = jnp.asarray(rng.standard_normal((ee, f, d)).astype(np.float32)) * 0.1
    fn2 = jax.jit(ops.expert_ffn)
    fn2(x, wg, wu, wd).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        y = fn2(x, wg, wu, wd)
    y.block_until_ready()
    us = (time.perf_counter() - t0) / 3 * 1e6
    flops = 6 * ee * c * d * f
    rows.append({
        "name": f"kernel_expert_ffn_e{ee}_c{c}",
        "us_per_call": round(us, 1),
        "derived": f"flops={flops:.2e} (interpret mode)",
    })
    return rows


def _rows(rows) -> None:
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)


def _bench_kernels(args) -> None:
    print("# kernel microbenchmarks", flush=True)
    _rows(_kernel_microbench())


def _bench_moe_dispatch(args) -> None:
    print("# MoE dispatch: sort-based ragged plan vs one-hot/cumsum", flush=True)
    from benchmarks import moe_dispatch

    _rows(moe_dispatch.run(smoke=not args.full))


def _bench_router_overhead(args) -> None:
    print("# router overhead (paper: 'very small time costs')", flush=True)
    from benchmarks import router_overhead

    _rows(router_overhead.run())
    print("# router dual sync sweep on a 4x2 mesh (BENCH_router_sync.json)", flush=True)
    _rows(router_overhead.run_sync_sweep(smoke=not args.full))


def _bench_paper_repro(args) -> None:
    if args.skip_train:
        return
    print("# paper tables 2/3 reproduction (reduced scale)", flush=True)
    from benchmarks import paper_repro

    steps = 300 if args.full else 120
    tables = paper_repro.main(steps=steps)
    for tbl in tables:
        for r in tbl["rows"]:
            print(
                f"{tbl['table']}_{r['strategy']},{r['train_wall_s'] * 1e6:.0f},"
                f"AvgMaxVio={r['AvgMaxVio']};SupMaxVio={r['SupMaxVio']};"
                f"ppl={r['perplexity']}",
                flush=True,
            )


def _bench_balance_sweep(args) -> None:
    if args.skip_train:
        return
    print("# per-step balance-method sweep (paper's step-wise MaxVio lens)", flush=True)
    from benchmarks import balance_sweep

    _rows(balance_sweep.run(smoke=not args.full))


def _bench_data_pipeline(args) -> None:
    if args.skip_train:
        return
    print("# streaming data pipeline (host tokens/s, prefetch overlap)", flush=True)
    from benchmarks import data_pipeline

    _rows(data_pipeline.run(smoke=not args.full))


def _bench_steptime_model(args) -> None:
    print("# step-time model (>=13% saving mechanism)", flush=True)
    from benchmarks import steptime_model

    _rows(steptime_model.run())


def _bench_capacity_ablation(args) -> None:
    print("# capacity-factor ablation (drops vs cf per strategy)", flush=True)
    from benchmarks import capacity_ablation

    _rows(capacity_ablation.run())


def _bench_expert_choice(args) -> None:
    print("# BIP vs Expert-Choice (beyond-paper comparison)", flush=True)
    from benchmarks import expert_choice_compare

    _rows(expert_choice_compare.main())


def _bench_serve_throughput(args) -> None:
    if args.skip_train:
        return
    print("# serving throughput (prefill speedup + multi-tenant sweep)", flush=True)
    from benchmarks import serve_throughput

    # the mesh rows ride along when forced host devices are available
    # (CI exports XLA_FLAGS=--xla_force_host_platform_device_count=8);
    # otherwise the bench prints a skip row and sweeps unsharded only
    argv = ["--out-json", "BENCH_serve_throughput.json", "--mesh", "4x2"]
    if not args.full:
        argv += ["--smoke", "--requests", "16", "--sweep-requests", "12"]
    serve_throughput.main(argv)


def _bench_roofline(args) -> None:
    if os.path.exists("dryrun_results_single.jsonl"):
        print("# roofline (from dry-run artifacts)", flush=True)
        from benchmarks import roofline

        roofline.main(["dryrun_results_single.jsonl"])


# registry: name -> section runner; `python -m benchmarks.run NAME [NAME..]`
# runs a subset, no names runs everything in order
BENCHES = {
    "kernels": _bench_kernels,
    "moe_dispatch": _bench_moe_dispatch,
    "router_overhead": _bench_router_overhead,
    "paper_repro": _bench_paper_repro,
    "balance_sweep": _bench_balance_sweep,
    "data_pipeline": _bench_data_pipeline,
    "steptime_model": _bench_steptime_model,
    "capacity_ablation": _bench_capacity_ablation,
    "expert_choice": _bench_expert_choice,
    "serve_throughput": _bench_serve_throughput,
    "roofline": _bench_roofline,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benchmarks", nargs="*", metavar="NAME",
                    help="benchmark(s) to run (default: all); one of: "
                         + ", ".join(BENCHES))
    ap.add_argument("--full", action="store_true", help="full-length repro runs")
    ap.add_argument("--skip-train", action="store_true", help="skip training benches")
    args = ap.parse_args(argv)

    unknown = [n for n in args.benchmarks if n not in BENCHES]
    if unknown:
        ap.error(
            f"unknown benchmark(s): {', '.join(sorted(unknown))}. "
            f"Registered benchmarks: {', '.join(BENCHES)}"
        )

    selected = args.benchmarks or list(BENCHES)
    for name in selected:
        BENCHES[name](args)


if __name__ == "__main__":
    main()
