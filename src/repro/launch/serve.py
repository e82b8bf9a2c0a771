"""Serving launcher: load a checkpoint (or init fresh), serve a request
stream through the continuous-batching engine (DESIGN.md §Serving).

    PYTHONPATH=src python -m repro.launch.serve --arch minimind-moe-16e \
        --reduced --requests 16 --n-slots 8 --chunk 32 [--ckpt /path/step_N.npz]
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-seq-len", type=int, default=0, help="0 = auto")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data D x model M) device mesh: params/"
                         "cache take the training shardings and MoE layers "
                         "run the expert-parallel dispatch paths")
    # robustness flags (DESIGN.md §Robustness)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget; overdue requests are "
                         "dropped ('expired') or evicted ('deadline')")
    ap.add_argument("--queue-timeout-ms", type=float, default=None,
                    help="max time a request may wait for admission")
    ap.add_argument("--shed-on-full", action="store_true",
                    help="under overload, shed the oldest waiting request "
                         "instead of refusing new submissions")
    ap.add_argument("--inject", action="append", default=None, metavar="SPEC",
                    help="fault injection, e.g. 'slow_step@ms=50' (decode "
                         "slowdown driving deadline misses)")
    # telemetry flags (DESIGN.md §Observability)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="stream per-request lifecycle records + the final "
                         "SLO summary (TTFT/ITL histograms, queue depth, "
                         "live expert load) to this .jsonl/.csv file")
    ap.add_argument("--profile", default=None, metavar="N:M",
                    help="capture a jax.profiler trace of serve steps "
                         "[N, M] into ./profile")
    args = ap.parse_args(argv)

    import jax

    from repro import configs
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine

    setup_compile_cache()
    cfg = configs.reduced_for_smoke(args.arch) if args.reduced else configs.get(args.arch)
    model = build_model(cfg)
    if args.ckpt:
        from repro.checkpoint import load_pytree

        tree = load_pytree(args.ckpt)
        params = tree["params"] if "params" in tree else tree
    else:
        params = model.init(jax.random.PRNGKey(0))

    step_delay = 0.0
    if args.inject:
        from repro.robustness import FaultPlan

        faults = FaultPlan.from_specs(args.inject)
        step_delay = faults.step_delay()
        print("injecting: " + "; ".join(f.describe() for f in faults.faults))

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh

        d, m = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(d, m)
        print(f"serving on a {d}x{m} mesh ({mesh.size} devices)")

    from repro.telemetry import open_sink, profile_window

    sink = open_sink(args.telemetry)
    max_seq_len = args.max_seq_len or (args.prompt_len + args.gen + 1)
    eng = ContinuousBatchingEngine(
        model,
        params,
        n_slots=args.n_slots,
        chunk_size=args.chunk,
        max_seq_len=max_seq_len,
        temperature=args.temperature,
        eos_id=args.eos_id,
        default_deadline=args.deadline_ms / 1e3 if args.deadline_ms else None,
        queue_timeout=(
            args.queue_timeout_ms / 1e3 if args.queue_timeout_ms else None
        ),
        shed_on_full=args.shed_on_full,
        step_delay=step_delay,
        sink=sink,
        profile=profile_window(args.profile) if args.profile else None,
        mesh=mesh,
    )
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, (plen,))
        while True:
            r = eng.submit(prompt, args.gen, ignore_eos=args.eos_id is None)
            if r is not None:
                break
            eng.step()  # waiting queue full: drain a step, then retry
        reqs.append(r)
    eng.run()

    for r in reqs[:4]:
        print(f"req {r.req_id}: prompt[{len(r.prompt)}] -> {r.output} ({r.finish_reason})")
    total = eng.prefill_tokens + eng.decode_tokens
    print(
        f"served {len(reqs)} requests over {eng.n_slots} slots in {eng.n_steps} "
        f"steps ({total} tokens: {eng.prefill_tokens} prefill / {eng.decode_tokens} decode)"
    )
    if eng.n_deadline_missed or eng.n_shed:
        print(
            f"deadline misses: {eng.n_deadline_missed} "
            f"({eng.n_deadline_missed / max(len(reqs), 1):.1%}), "
            f"shed/timeout: {eng.n_shed}"
        )
    if cfg.is_moe:
        load = eng.expert_load
        mean = max(load.mean(), 1e-9)
        print(f"per-expert load: {load.astype(int).tolist()} (MaxVio {load.max()/mean - 1.0:.3f})")
    slo = eng.telemetry.emit_summary()
    print(
        f"SLO: ttft p50 {1e3 * slo['ttft']['p50']:.1f} ms / "
        f"p99 {1e3 * slo['ttft']['p99']:.1f} ms, "
        f"itl p50 {1e3 * slo['itl']['p50']:.1f} ms / "
        f"p99 {1e3 * slo['itl']['p99']:.1f} ms, "
        f"queue depth max {slo['queue_depth_max']}"
    )
    eng.close()
    if sink is not None:
        sink.close()
        print(f"telemetry -> {args.telemetry}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
