"""Model FLOP/s utilization of the whole train step (%): model FLOPs per
token (6·N_active + attention, counts.train_flops_per_token from the counts
of the configuration's reference; no recomputation, no capacity padding) x
the run's tokens/s over chips x peak."""

import counts


def read(rec):
    if not rec.get("tokens_per_s"):
        return None
    flops = rec["train_flops_per_token"] * rec["tokens_per_s"]
    return 100.0 * flops / (rec["chips"] * counts.peaks_for(rec["device_kind"]).bf16_flops)
