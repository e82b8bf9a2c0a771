"""Set-up time spent building the train step's program (s): the host spans
`setup/trace`, `setup/lower` and `setup/compile` whose `program` is
`train_step`, which repro.telemetry.trace records in memory from JAX's
own build events in this process (a compile served by the persistent cache
counts the load). The part of `setup_s` that a program change can shorten.

A program without the span buffer reads nothing. A program with it must
have recorded the train step's build: if not, the reading would be
silently wrong, so the reader raises."""

import sys

PROGRAM = "train_step"


def read(rec):
    from repro.telemetry import trace

    if not hasattr(trace, "spans"):
        print("step_build_s.train: the program records no host spans", file=sys.stderr)
        return None
    mine = [s for s in trace.spans()
            if s.name.startswith("setup/") and s.attrs.get("program") == PROGRAM]
    if not mine:
        raise RuntimeError(f"no setup/* span names the program {PROGRAM!r}: the train "
                           "step was built under another name or not recorded")
    return sum(s.end_ns - s.start_ns for s in mine) * 1e-9
