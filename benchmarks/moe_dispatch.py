"""Dispatch/FFN microbenchmark: sort-based ragged plan vs one-hot/cumsum.

    PYTHONPATH=src python -m benchmarks.moe_dispatch           # full shapes
    PYTHONPATH=src python -m benchmarks.moe_dispatch --smoke   # CI guard

Measures, for the minimind-moe-16e (m=16, k=4) and 64e (m=64, k=8) routing
shapes at d_model=512:

1. dispatch+combine wall-clock — the seed formulation ((n·k, m) one-hot,
   serial cumsum, repeat(x, k) + scatter-add pack, clamped-index gather
   combine) vs the sort-based DispatchPlan (stable argsort + segment
   offsets, pack/combine as pure gathers). An identity "FFN" isolates the
   bookkeeping + data movement from the expert GEMMs.
2. a jaxpr audit of the new path: no intermediate of shape (n·k, m) may
   appear (the one-hot/cumsum bookkeeping is gone, not just faster).
3. grouped expert FFN: einsum vs the Pallas kernel pair. On CPU the kernels
   execute in interpret mode (Python per grid cell), so this row is a
   correctness/robustness exercise there; on a TPU they lower to Mosaic.

Emits ``name,us_per_call,derived`` CSV lines (repo contract) and writes
BENCH_moe_dispatch.json with tokens/s and dispatch-µs per shape.
"""
from __future__ import annotations

import argparse
import json
import time

SHAPES = {
    # name -> (n_experts, top_k, d_model)  [minimind-moe configs, Table 1]
    "minimind-moe-16e": (16, 4, 512),
    "minimind-moe-64e": (64, 8, 512),
}


def _old_dispatch(x, idx, w, m, cap, k):
    """Seed formulation, frozen for comparison (see models/moe history)."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    flat = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat, m, dtype=jnp.int32)  # (n*k, m)
    pos_flat = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_flat, flat[:, None], axis=1)[:, 0]
    keep = pos < cap
    src = jnp.repeat(x, k, axis=0) * keep[:, None]
    buf = jnp.zeros((m, cap, d), x.dtype)
    buf = buf.at[flat, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], src, 0.0)
    )
    y = buf  # identity FFN: isolate dispatch + combine
    gathered = y[flat, jnp.where(keep, pos, 0)]
    contrib = jnp.where(keep[:, None], gathered * w.reshape(-1, 1), 0.0)
    return contrib.reshape(n, k, d).sum(axis=1)


def _new_dispatch(x, idx, w, m, cap):
    from repro.core.router import make_dispatch_plan

    plan = make_dispatch_plan(idx, m, cap)
    buf = plan.pack(x)
    return plan.combine(buf, w)


def _assert_no_nk_m_intermediate(fn, args, nk, m):
    """Audit every equation in the jaxpr (incl. sub-jaxprs): no (n·k, m)."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jp):
        for eqn in jp.eqns:
            for v in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(v, "aval", None)
                if aval is not None and tuple(getattr(aval, "shape", ())) == (nk, m):
                    raise AssertionError(
                        f"(n*k, m)=({nk}, {m}) intermediate found: {eqn.primitive}"
                    )
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)


def _time(fn, args, iters):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(smoke: bool = False, out_path: str = "BENCH_moe_dispatch.json"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.platform import interpret_default

    token_counts = [2048] if smoke else [8192, 32768]
    iters = 2 if smoke else 5
    rows = []
    results = {"smoke": smoke, "backend": jax.default_backend(), "shapes": []}
    rng = np.random.default_rng(0)

    for name, (m, k, d) in SHAPES.items():
        for n in token_counts:
            cap = int(np.ceil(k * n / m * 1.25))
            idx = jnp.asarray(rng.integers(0, m, (n, k)), jnp.int32)
            x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
            w = jnp.asarray(rng.random((n, k)), jnp.float32)

            f_old = jax.jit(lambda x, i, w: _old_dispatch(x, i, w, m, cap, k))
            f_new = jax.jit(lambda x, i, w: _new_dispatch(x, i, w, m, cap))
            np.testing.assert_allclose(
                np.asarray(f_old(x, idx, w)),
                np.asarray(f_new(x, idx, w)),
                atol=1e-5,
            )
            _assert_no_nk_m_intermediate(f_new, (x, idx, w), n * k, m)

            t_old = _time(f_old, (x, idx, w), iters)
            t_new = _time(f_new, (x, idx, w), iters)
            rec = {
                "config": name,
                "n_tokens": n,
                "n_experts": m,
                "top_k": k,
                "d_model": d,
                "capacity": cap,
                "dispatch_us_onehot": round(t_old * 1e6, 1),
                "dispatch_us_sorted": round(t_new * 1e6, 1),
                "speedup": round(t_old / t_new, 2),
                "tokens_per_s_onehot": round(n / t_old, 1),
                "tokens_per_s_sorted": round(n / t_new, 1),
                "no_nk_m_intermediate": True,
            }
            results["shapes"].append(rec)
            rows.append({
                "name": f"moe_dispatch_{name}_n{n}",
                "us_per_call": rec["dispatch_us_sorted"],
                "derived": (
                    f"onehot={rec['dispatch_us_onehot']}us;"
                    f"speedup={rec['speedup']}x;"
                    f"tok/s={rec['tokens_per_s_sorted']:.0f}"
                ),
            })

    # grouped FFN: einsum vs Pallas pair (interpret mode off-TPU — see module
    # docstring; kept small so the CI smoke stays cheap)
    for name, (m, k, d) in SHAPES.items():
        # small shapes: interpret mode executes the kernel body per grid
        # cell in Python, so the FFN row stays a bounded-cost exercise off-TPU
        f = 256 if smoke else 1408
        n_ffn = 128 if smoke else 512
        cap = int(np.ceil(k * n_ffn / m * 1.25))
        xb = jnp.asarray(rng.standard_normal((m, cap, d)), jnp.float32) * 0.3
        wg = jnp.asarray(rng.standard_normal((m, d, f)), jnp.float32) * 0.05
        wu = jnp.asarray(rng.standard_normal((m, d, f)), jnp.float32) * 0.05
        wd = jnp.asarray(rng.standard_normal((m, f, d)), jnp.float32) * 0.05
        fn_e = jax.jit(lambda *a: ref.expert_ffn_ref(*a))
        fn_p = jax.jit(lambda *a: ops.expert_ffn(*a))
        t_e = _time(fn_e, (xb, wg, wu, wd), max(1, iters - 1))
        t_p = _time(fn_p, (xb, wg, wu, wd), 1)
        flops = 6 * m * cap * d * f
        rec = {
            "config": name,
            "ffn_tokens": n_ffn,
            "capacity": cap,
            "ffn_us_einsum": round(t_e * 1e6, 1),
            "ffn_us_pallas": round(t_p * 1e6, 1),
            "ffn_flops": flops,
            "pallas_interpret": interpret_default(),
        }
        results["shapes"].append(rec)
        rows.append({
            "name": f"moe_ffn_{name}_c{cap}",
            "us_per_call": rec["ffn_us_pallas"],
            "derived": (
                f"einsum={rec['ffn_us_einsum']}us;flops={flops:.2e};"
                f"interpret={rec['pallas_interpret']}"
            ),
        })

    with open(out_path, "w") as fh:
        json.dump(results, fh, indent=2)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny shapes for CI")
    ap.add_argument("--out", default="BENCH_moe_dispatch.json")
    args = ap.parse_args()
    for r in run(smoke=args.smoke, out_path=args.out):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}", flush=True)


if __name__ == "__main__":
    main()
