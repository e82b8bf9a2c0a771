"""Readings that a training cell's correctness limits are set from (PERF.md).

    python bench/calibrate.py --workload train-16e --seeds 11 12 13 \
        [--save bench/tests/fixtures/train-16e.readings.json]

In one process, for each seed: the program's first steps, as a run drives
them, against the float32 reference; the control (the reference at float8
e4m3, the precision below the configured bfloat16) in the program's place;
and the planted fault that leaves half of each batch out. One JSON line per
seed and reading, with the compared numbers; the lower reading of a limit is
the largest sound program reading over the seeds, the upper one the smallest
control or fault reading. `--save` also writes every reading as compared
(losses and per-leaf norms), which bench/tests/test_control.py judges
against the cell's limits file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def calibrate_train(spec, seeds, out=print):
    """(rows of compared numbers, {seed: {reading: raw reading}})."""
    from kinds.train import Trainer, compare

    import jax
    import numpy as np

    tr = Trainer(spec)
    mix, cfg = spec.traffic, spec.config
    leaves = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        tr.ref.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))[0]]

    def worst(prog, ref):
        prog, ref = np.asarray(prog), np.asarray(ref)
        gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
        i = int(np.argmax(gap))
        return f"{leaves[i]} {gap[i]:.3e} (next {np.sort(gap)[-2]:.3e})"

    refs = {p: tr.ref.TrainReference(cfg, mix["optimizer"], p) for p in ("f32", "fp8")}
    rows, raw = [], {}
    for seed in seeds:
        t0 = time.perf_counter()
        state, batches, k_w = tr.start(seed)
        state, prog = tr.check_steps(state, batches, k_w)
        del state
        batches = batches[:mix["check_steps"]]
        t1 = time.perf_counter()
        p0 = tr.init(k_w)
        ref = refs["f32"].readings(p0, batches, mix["microbatches"])
        t2 = time.perf_counter()
        readings = {
            "program": prog,
            "control_fp8": refs["fp8"].readings(p0, batches, mix["microbatches"]),
            "fault_half_batch": refs["f32"].readings(p0, batches, mix["microbatches"],
                                                     drop_half=True),
        }
        raw[str(seed)] = {"reference": ref, **readings}
        for name, r in readings.items():
            row = {"seed": seed, "reading": name, **compare(r, ref),
                   "worst_grad_leaf": worst(r["grad1"], ref["grad1"]),
                   "worst_change_leaf": worst(r["change"], ref["change"])}
            if name == "program":
                row.update(program_s=t1 - t0, reference_s=t2 - t1, losses=r["losses"],
                           ref_losses=ref["losses"])
            rows.append(row)
            out(json.dumps(row))
    return rows, raw


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--save", help="write every raw reading to this JSON file")
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    jax = run.setup_jax()
    run.check_devices(jax, spec.chips)
    if spec.traffic["kind"] != "train":
        raise SystemExit(f"no calibration for kind {spec.traffic['kind']!r}")
    _, raw = calibrate_train(spec, args.seeds, out=lambda s: print(s, flush=True))
    if args.save:
        os.makedirs(os.path.dirname(os.path.abspath(args.save)), exist_ok=True)
        with open(args.save, "w") as fh:
            json.dump({"workload": args.workload, "device_kind": jax.devices()[0].device_kind,
                       "readings": raw}, fh, indent=1)


if __name__ == "__main__":
    main()
