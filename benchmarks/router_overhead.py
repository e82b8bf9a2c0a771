"""Router overhead — validates the paper's "very small time costs" claim.

Times route() per strategy on CPU at the paper's gate sizes (n tokens ×
m experts) and reports µs/call plus overhead relative to the vanilla top-k
gate. On TPU the ADMM update is the Pallas kernel (~0.5 ms/iteration at
n=32k, m=128, see kernels/bip_admm.py cost model); the CPU numbers here are
for RELATIVE comparison between strategies only.

Sync sweep (``--sync`` / ``run_sync_sweep``): times the sync='global' dual
update variants on a forced 4x2 host mesh against per-shard 'local' duals —
the PR 5 classic-bisection path (fanout=1, data-dependent bounds), the fused
multi-threshold path (fanout=32, static score bounds), the fused path with an
oracle forecaster window (the warm-start upper bound), and the collective
Pallas kernel — and writes ``BENCH_router_sync.json`` with the measured
step times plus the analytic collective-round counts per dual iteration.
The mesh child re-executes this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (device count is
locked at jax import, so the parent cannot host the mesh itself).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RouterConfig, init_router_state, route


def _time_call(fn, *args, iters: int = 20) -> float:
    fn(*args)  # compile
    jax.block_until_ready(fn(*args).combine_weights)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out.combine_weights)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(n: int = 8192, m: int = 64, k: int = 8) -> List[Dict]:
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    rows = []
    base_us = None
    for strategy, t in [
        ("topk", 0), ("aux_loss", 0), ("lossfree", 0),
        ("bip", 2), ("bip", 4), ("bip", 8), ("bip", 14),
    ]:
        cfg = RouterConfig(
            n_experts=m, top_k=k, strategy=strategy, bip_iters=max(t, 1)
        )
        state = init_router_state(cfg)
        fn = jax.jit(lambda l, s, c=cfg: route(l, s, c))
        us = _time_call(fn, logits, state)
        if strategy == "topk":
            base_us = us
        name = strategy if strategy != "bip" else f"bip_T{t}"
        rows.append(
            {
                "name": f"router_{name}_n{n}_m{m}",
                "us_per_call": round(us, 1),
                "derived": f"overhead_vs_topk={us / base_us:.2f}x",
            }
        )
    return rows


# ------------------------------------------------- sync-mode sweep (mesh)


def _sync_sweep_mesh_body(smoke: bool) -> Dict:
    """Runs INSIDE the forced-8-device child: mesh timings + round counts."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.ref_bip import (
        bip_dual_update,
        bip_dual_update_global,
        bisect_rounds,
    )
    from repro.kernels import ops as kernel_ops

    n_local = 256 if smoke else 1024
    m, k = 64, 8
    t_iters = 2 if smoke else 4
    iters = 5 if smoke else 20
    n_bisect, fanout = 26, 32

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    n_glob = n_local * 4  # data-axis size
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((n_glob, m)) + 1.5 * np.linspace(2, -2, m)[None, :]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    s = jnp.asarray((e / e.sum(-1, keepdims=True)).astype(np.float32))
    q0 = jnp.zeros((m,), jnp.float32)

    def shard(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P("data", None), P(None)), out_specs=P(None)
        ))

    # oracle forecaster window: the true pre-clamp statistic of this batch
    # +- a tight margin (best-case warm-start; the trained EMA approaches it)
    _, _, t_stat = bip_dual_update_global(
        s, q0, top_k=k, n_iters=t_iters, n_bisect=n_bisect, fanout=fanout,
        score_bounds=(0.0, 1.0), with_stats=True,
    )
    w = (t_stat - 1e-5, t_stat + 1e-5)

    variants = {
        # per-shard duals + the production path's single warm-start pmean
        "local": lambda sl, q: jax.lax.pmean(
            bip_dual_update(sl, q, top_k=k, n_iters=t_iters)[0], ("data",)
        ),
        # PR 5 shape: classic bisection, data-dependent pmin/pmax bounds
        "global_pr5_fanout1": lambda sl, q: bip_dual_update_global(
            sl, q, top_k=k, n_iters=t_iters, axis_names=("data",),
            n_bisect=n_bisect, fanout=1,
        )[0],
        # this PR: fused multi-threshold rounds + static score bounds
        "global_fused": lambda sl, q: bip_dual_update_global(
            sl, q, top_k=k, n_iters=t_iters, axis_names=("data",),
            n_bisect=n_bisect, fanout=fanout, score_bounds=(0.0, 1.0),
        )[0],
        # + oracle warm-start window (convergence skips trailing rounds)
        "global_fused_warm": lambda sl, q: bip_dual_update_global(
            sl, q, top_k=k, n_iters=t_iters, axis_names=("data",),
            n_bisect=n_bisect, fanout=fanout, score_bounds=(0.0, 1.0), window=w,
        )[0],
        # collective Pallas ADMM kernel (psum'd histogram counts)
        "kernel_collective": lambda sl, q: kernel_ops.bip_dual_update(
            sl, q, top_k=k, n_iters=t_iters, axis_names=("data",)
        ),
    }

    rounds_pr5 = bisect_rounds(n_bisect, 1) + 2  # + pmin/pmax bound pair
    rounds_fused = bisect_rounds(n_bisect, fanout)
    counts = {
        "local": 0,
        "global_pr5_fanout1": rounds_pr5,
        "global_fused": rounds_fused,
        "global_fused_warm": rounds_fused,  # worst case; warm rounds converge early
        "kernel_collective": 1,  # one (m, n_bins) histogram psum
    }

    rows = []
    t_local = None
    with mesh:
        for name, fn in variants.items():
            sfn = shard(fn)
            jax.block_until_ready(sfn(s, q0))  # compile
            jax.block_until_ready(sfn(s, q0))
            t0 = time.perf_counter()
            for _ in range(iters):
                out = sfn(s, q0)
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / iters * 1e6
            if name == "local":
                t_local = us
            rows.append({
                "name": f"dual_sync_{name}_n{n_glob}_m{m}_T{t_iters}",
                "us_per_call": round(us, 1),
                "derived": (
                    f"collectives_per_iter={counts[name]};"
                    f"vs_local={us / t_local:.2f}x"
                ),
            })

    # full router step (route(): scores + dual update + top-k dispatch +
    # metrics) — the ratio that prices global sync for a training step
    logits_j = jnp.asarray(
        rng.standard_normal((n_glob, m)).astype(np.float32)
        + 1.5 * np.linspace(2, -2, m)[None, :].astype(np.float32)
    )
    base = dict(n_experts=m, top_k=k, strategy="bip", bip_iters=t_iters,
                data_axes=("data",), n_bisect=n_bisect, bisect_fanout=fanout)
    route_cfgs = {
        "local": RouterConfig(sync="local", **base),
        "global_fused": RouterConfig(sync="global", **base),
        "global_forecast": RouterConfig(sync="global", forecast=True, **base),
        "global_kernel": RouterConfig(sync="global", use_kernel=True, **base),
    }
    t_route_local = None
    with mesh:
        for name, cfg in route_cfgs.items():
            st0 = init_router_state(cfg)
            specs = jax.tree.map(lambda _: P(None), st0)

            def block(lg, st, cfg=cfg):
                out = route(lg, st, cfg)
                new = dict(out.state)
                if cfg.sync == "local":
                    new["q"] = jax.lax.pmean(new["q"], ("data",))
                return out.combine_weights, new

            sfn = jax.jit(jax.shard_map(
                block, mesh=mesh,
                in_specs=(P("data", None), specs),
                out_specs=(P("data", None), specs),
            ))
            st = st0
            for _ in range(3):  # prime: warm duals + forecaster EMAs
                w_out, st = sfn(logits_j, st)
            jax.block_until_ready(w_out)
            t0 = time.perf_counter()
            for _ in range(iters):
                w_out, _ = sfn(logits_j, st)
            jax.block_until_ready(w_out)
            us = (time.perf_counter() - t0) / iters * 1e6
            if name == "local":
                t_route_local = us
            rows.append({
                "name": f"route_step_{name}_n{n_glob}_m{m}_T{t_iters}",
                "us_per_call": round(us, 1),
                "derived": f"vs_local={us / t_route_local:.2f}x",
            })

    return {
        "config": {
            "mesh": "4x2 forced host devices", "n_global": n_glob, "m": m,
            "k": k, "bip_iters": t_iters, "n_bisect": n_bisect,
            "bisect_fanout": fanout, "timing_iters": iters, "smoke": smoke,
        },
        "collective_rounds_per_iter": {
            "pr5_classic_bisection": rounds_pr5,
            "fused_multi_threshold": rounds_fused,
            "reduction": f"{rounds_pr5 / rounds_fused:.1f}x",
        },
        "rows": rows,
    }


def run_sync_sweep(smoke: bool = False, out_path: str = "BENCH_router_sync.json") -> List[Dict]:
    """Spawn the forced-8-device child, collect its JSON, write the artifact."""
    env = dict(os.environ)
    # a CPU forced-device tool: the child must never take an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    args = [sys.executable, "-m", "benchmarks.router_overhead", "--sync-child"]
    if smoke:
        args.append("--smoke")
    out = subprocess.run(args, capture_output=True, text=True, env=env, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"sync sweep child failed:\n{out.stderr[-3000:]}")
    result = json.loads(out.stdout.splitlines()[-1])
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    return result["rows"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small sizes, few iters")
    ap.add_argument("--sync", action="store_true",
                    help="run the mesh sync sweep (writes BENCH_router_sync.json)")
    ap.add_argument("--sync-child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.sync_child:
        print(json.dumps(_sync_sweep_mesh_body(smoke=args.smoke)), flush=True)
        return
    if args.sync:
        for r in run_sync_sweep(smoke=args.smoke):
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
        return
    for r in run():
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
