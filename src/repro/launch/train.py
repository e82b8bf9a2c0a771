"""Training launcher.

Local (CPU / small mesh):
    PYTHONPATH=src python -m repro.launch.train --arch minimind-moe-16e \
        --steps 200 --batch 8 --seq-len 128 [--method bip|lossfree|aux_loss] \
        [--mesh 4x2] [--micro 2] [--ckpt-dir ck --ckpt-every 50 --resume]

Real-text corpus (streaming pipeline, DESIGN.md §Data):
    PYTHONPATH=src python -m repro.launch.train --arch minimind-moe-16e \
        --data corpus_dir_or_glob --tokenizer tok.json \
        [--pack-mode pack|pack_nocross|pad] [--shuffle-buffer 64] [--prefetch 2]

    --data points at .jsonl ({"text": ...} per line) / .txt shards. The
    tokenizer at --tokenizer is loaded if present, otherwise trained on the
    corpus to the arch's vocab size and saved there (and copied into
    --ckpt-dir so the run is reproducible from its artifacts). The loader
    shards documents over hosts (jax.process_index/count), its cursor is
    checkpointed with the TrainState, and --resume seeks it in O(1) —
    bit-exact, no prefix replay. --prefetch N (0 disables) double-buffers
    host tokenize/pack/H2D against device steps.

Production (TPU pod; one process per host, standard jax.distributed):
    python -m repro.launch.train --arch llama4-scout-17b-a16e --production \
        --coordinator $COORD --num-hosts $N --host-id $ID

Both mesh paths (--production's 16x16 / 2x16x16 pod mesh and --mesh's DxM
host mesh over local devices) feed the SAME sharded train step: explicit
in/out shardings from repro.distributed.sharding, donated TrainState,
microbatch gradient accumulation (see repro.training.loop).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys


def _build_data_stream(cfg, args, faults=None):
    """Resolve shards + tokenizer, return (BatchStream, tokenizer).

    The tokenizer is loaded from --tokenizer when the file exists, else
    trained on the corpus to cfg.vocab_size and saved there; a copy also
    lands in --ckpt-dir so checkpoints are self-describing."""
    import os
    import shutil

    import jax

    from repro.data import (
        ByteBPETokenizer,
        Prefetcher,
        ShardedTextLoader,
        resolve_shards,
        train_tokenizer_from_files,
    )

    shards = resolve_shards(args.data)
    tok_path = args.tokenizer or (
        os.path.join(args.ckpt_dir, "tokenizer.json") if args.ckpt_dir else None
    )
    if tok_path and os.path.exists(tok_path):
        tokenizer = ByteBPETokenizer.load(tok_path)
        print(f"tokenizer <- {tok_path} (vocab {tokenizer.vocab_size})")
    else:
        tokenizer = train_tokenizer_from_files(shards, vocab_size=cfg.vocab_size)
        print(
            f"tokenizer trained on {len(shards)} shard(s): "
            f"{len(tokenizer.merges)} merges, vocab {tokenizer.vocab_size}"
        )
        if tok_path:
            tokenizer.save(tok_path)
            print(f"tokenizer -> {tok_path}")
    assert tokenizer.vocab_size <= cfg.vocab_size, (
        f"tokenizer vocab {tokenizer.vocab_size} exceeds model vocab {cfg.vocab_size}"
    )
    if args.ckpt_dir and tok_path != os.path.join(args.ckpt_dir, "tokenizer.json"):
        os.makedirs(args.ckpt_dir, exist_ok=True)
        if tok_path:
            shutil.copy(tok_path, os.path.join(args.ckpt_dir, "tokenizer.json"))
        else:
            tokenizer.save(os.path.join(args.ckpt_dir, "tokenizer.json"))

    stream = ShardedTextLoader(
        shards,
        tokenizer,
        batch_size=args.batch,
        seq_len=args.seq_len,
        pack_mode=args.pack_mode,
        rank=jax.process_index(),
        world_size=jax.process_count(),
        shuffle_buffer=args.shuffle_buffer,
        seed=args.data_seed,
        io_retries=args.io_retries,
        open_fn=faults.open_fn() if faults is not None else None,
    )
    if faults is not None:
        stream = faults.wrap_stream(stream)  # flaky_stream / stall_prefetch
    if args.prefetch > 0:
        # one retry per injected/transient stream crash, plus headroom
        stream = Prefetcher(stream, depth=args.prefetch, retries=args.io_retries)
    return stream, tokenizer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--strategy", "--method", dest="strategy", default=None,
                    help="routing strategy override; any name in the "
                         "balancer registry (repro.core.registered_balancers; "
                         "--method is the legacy alias)")
    ap.add_argument("--bip-iters", type=int, default=None)
    ap.add_argument("--sync", default=None, choices=["local", "global"],
                    help="BIP dual sync across data shards on a mesh: 'local' "
                         "solves per-shard duals and averages the warm start, "
                         "'global' psums the dual order statistics so every "
                         "device holds the single-device duals (DESIGN.md "
                         "§Global-sync). Without --mesh/--production, "
                         "'global' still switches the single-device dual "
                         "solver to the threshold/bisection form (the mesh "
                         "reference numerics, bypassing use_kernel)")
    ap.add_argument("--n-bisect", type=int, default=None,
                    help="bits of bisection resolution for the sync='global' "
                         "dual order statistic (default 26)")
    ap.add_argument("--bisect-fanout", type=int, default=None,
                    help="thresholds probed per fused bisection round; one "
                         "collective per round shrinks the bracket "
                         "(fanout+1)x (default 32 -> 6 rounds)")
    ap.add_argument("--forecast", action="store_true",
                    help="carry the dual forecaster (EMA of the order "
                         "statistic) in router state and warm-start each "
                         "bisection with its predicted bracket")
    ap.add_argument("--forecast-decay", type=float, default=None)
    ap.add_argument("--forecast-margin", type=float, default=None)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--micro", type=int, default=1,
                    help="microbatches per step (gradient accumulation)")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke-scale) variant of --arch")
    ap.add_argument("--bf16", action="store_true",
                    help="bf16 compute (master params/moments stay fp32)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the full TrainState every N steps (0 = only final)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --ckpt-dir and continue")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out-json", default=None,
                    help="write the run summary to this JSON file")
    # telemetry flags (DESIGN.md §Observability)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="stream per-step metric records (per-layer expert "
                         "load histograms, MaxVio, dual health, guard "
                         "events) to this .jsonl/.csv file; summarize with "
                         "`python -m repro.telemetry.metrics_report PATH`")
    ap.add_argument("--flush-every", type=int, default=10,
                    help="telemetry ring-buffer window: steps buffered on "
                         "device between asynchronous host drains")
    ap.add_argument("--profile", default=None, metavar="N:M",
                    help="capture a jax.profiler trace of train steps "
                         "[N, M] into ./profile (view with TensorBoard)")
    # real-text data pipeline flags
    ap.add_argument("--data", default=None,
                    help="corpus dir / glob / file of .jsonl|.txt shards "
                         "(default: synthetic stream)")
    ap.add_argument("--tokenizer", default=None,
                    help="tokenizer JSON path; trained on --data and saved "
                         "here if missing (default: <ckpt-dir>/tokenizer.json)")
    ap.add_argument("--pack-mode", default="pack",
                    choices=["pack", "pack_nocross", "pad"],
                    help="document packing: 'pack' = EOS-joined stream, "
                         "'pack_nocross' adds within-document attention/loss "
                         "masking, 'pad' = one document per sequence")
    ap.add_argument("--shuffle-buffer", type=int, default=64,
                    help="documents held in the loader's shuffle buffer")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="prefetch queue depth (0 = tokenize/pack inline)")
    ap.add_argument("--data-seed", type=int, default=0,
                    help="loader shuffle seed")
    # robustness flags (DESIGN.md §Robustness)
    ap.add_argument("--guard", default=None, choices=["skip", "rollback", "raise"],
                    help="anomaly policy for non-finite loss/grads: 'skip' "
                         "keeps the pre-step state (escalating to LR drops "
                         "and rollback if persistent), 'rollback' restores "
                         "the newest valid checkpoint and replays, 'raise' "
                         "fails fast")
    ap.add_argument("--spike-factor", type=float, default=0.0,
                    help="loss-spike anomaly threshold as a multiple of the "
                         "recent median (0 disables; implies --guard skip "
                         "when no policy is given)")
    ap.add_argument("--spike-window", type=int, default=8,
                    help="finite losses in the spike reference window")
    ap.add_argument("--guard-duals", action="store_true",
                    help="router dual-health watchdog: reset a layer's "
                         "carried q / forecaster EMAs to safe init when "
                         "non-finite or runaway")
    ap.add_argument("--inject", action="append", default=None, metavar="SPEC",
                    help="fault injection, repeatable: 'nan_grad@step=3', "
                         "'ckpt_corrupt@step=0,mode=bitflip', "
                         "'flaky_open@p=0.3,p_read=0.1', 'flaky_stream@at=2'; "
                         "see repro.robustness.faults")
    ap.add_argument("--io-retries", type=int, default=3,
                    help="consecutive shard open/read failures retried with "
                         "backoff before the loader raises")
    # mesh flags
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="host mesh over local devices, e.g. 4x2 = 4-way data x 2-way model")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    if args.production and args.coordinator:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_hosts,
            process_id=args.host_id,
        )

    from repro import configs
    from repro.data import make_batches
    from repro.data.synthetic import SyntheticBatchStream
    from repro.models import build_model
    from repro.training import train_loop
    from repro.training.loop import evaluate_ppl

    if args.strategy is not None:
        # resolve through the balancer registry so unknown names fail here
        # with the registered list, not deep inside config construction
        from repro.core import get_balancer

        try:
            get_balancer(args.strategy)
        except ValueError as e:
            ap.error(str(e))

    cfg = configs.reduced_for_smoke(args.arch) if args.reduced else configs.get(args.arch)
    if (
        args.strategy or args.bip_iters or args.sync or args.n_bisect
        or args.bisect_fanout or args.forecast or args.guard_duals
        or args.forecast_decay is not None or args.forecast_margin is not None
    ):
        routing = dataclasses.replace(
            cfg.routing,
            strategy=args.strategy or cfg.routing.strategy,
            bip_iters=args.bip_iters or cfg.routing.bip_iters,
            sync=args.sync or cfg.routing.sync,
            n_bisect=args.n_bisect or cfg.routing.n_bisect,
            bisect_fanout=args.bisect_fanout or cfg.routing.bisect_fanout,
            forecast=args.forecast or cfg.routing.forecast,
            forecast_decay=(
                cfg.routing.forecast_decay
                if args.forecast_decay is None else args.forecast_decay
            ),
            forecast_margin=(
                cfg.routing.forecast_margin
                if args.forecast_margin is None else args.forecast_margin
            ),
            guard_duals=args.guard_duals or cfg.routing.guard_duals,
        )
        cfg = dataclasses.replace(cfg, routing=routing)
    if args.bf16:
        import jax.numpy as jnp

        cfg = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16)

    mesh = None
    if args.production:
        from repro.distributed import make_mesh_ctx
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.multi_pod)
        model = build_model(cfg, make_mesh_ctx(mesh))
    elif args.mesh:
        from repro.distributed import make_mesh_ctx
        from repro.launch.mesh import make_host_mesh

        data, model_par = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data, model_par)
        model = build_model(cfg, make_mesh_ctx(mesh))
    else:
        model = build_model(cfg)

    print(
        f"training {cfg.name} [{cfg.family}]"
        f" method={cfg.routing.strategy if cfg.is_moe else 'n/a'}"
        f" sync={cfg.routing.sync if cfg.is_moe else 'n/a'}"
        f" mesh={dict(mesh.shape) if mesh is not None else None}"
        f" micro={args.micro}"
        f" data={args.data or 'synthetic'}"
    )
    faults = None
    if args.inject:
        from repro.robustness import FaultPlan

        faults = FaultPlan.from_specs(args.inject)
        print("injecting: " + "; ".join(f.describe() for f in faults.faults))
    guard = None
    if args.guard or args.spike_factor:
        from repro.robustness import GuardConfig

        guard = GuardConfig(
            policy=args.guard or "skip",
            spike_factor=args.spike_factor,
            spike_window=args.spike_window,
        )
    if args.data:
        batches, tokenizer = _build_data_stream(cfg, args, faults)
    else:
        batches = SyntheticBatchStream(cfg, args.batch, args.seq_len, args.steps)
        if faults is not None:
            batches = faults.wrap_stream(batches)
    telemetry = sink = None
    if args.telemetry or args.profile:
        from repro.telemetry import (
            Profiler,
            TrainTelemetry,
            open_sink,
            profile_window,
        )

        sink = open_sink(args.telemetry)
        telemetry = TrainTelemetry(
            sink=sink,
            flush_every=args.flush_every,
            run_meta={
                "arch": cfg.name,
                "strategy": cfg.routing.strategy if cfg.is_moe else None,
                "sync": cfg.routing.sync if cfg.is_moe else None,
                "steps": args.steps,
                "flush_every": args.flush_every,
            },
            profiler=(
                Profiler(profile_window(args.profile)) if args.profile else None
            ),
        )
    try:
        state, log = train_loop(
            model,
            batches,
            lr=args.lr,
            total_steps=args.steps,
            log_every=args.log_every,
            mesh=mesh,
            microbatches=args.micro,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every or (args.steps if args.ckpt_dir else 0),
            resume=args.resume,
            guard=guard,
            faults=faults,
            telemetry=telemetry,
        )
    finally:
        if sink is not None:
            sink.close()
            print(f"telemetry -> {args.telemetry}")
    if args.data:
        # in-sample by construction: same shards as training (only the
        # shuffle seed differs) — reported as train_corpus_ppl, not test_ppl
        import itertools

        from repro.data import ShardedTextLoader, resolve_shards

        test = itertools.islice(
            ShardedTextLoader(
                resolve_shards(args.data), tokenizer,
                batch_size=args.batch, seq_len=args.seq_len,
                pack_mode=args.pack_mode, seed=args.data_seed + 1, epochs=1,
            ),
            4,
        )
    else:
        test = make_batches(cfg, args.batch, args.seq_len, 4, split="test")
    ppl = evaluate_ppl(model, state, test)
    summary = {
        "arch": cfg.name,
        "method": cfg.routing.strategy if cfg.is_moe else None,
        "sync": cfg.routing.sync if cfg.is_moe else None,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "microbatches": args.micro,
        "data": args.data,
        "pack_mode": args.pack_mode if args.data else None,
        **log.summary(),
        # a real --data corpus has no held-out split here: the eval pass
        # re-reads the training shards, so label it honestly
        ("train_corpus_ppl" if args.data else "test_ppl"): ppl,
    }
    print(json.dumps(summary, indent=1, default=float))
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(summary, f, indent=1, default=float)

    if args.ckpt_dir:
        print(f"checkpoint -> {args.ckpt_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
