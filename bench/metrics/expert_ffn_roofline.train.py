"""Expert FFN's share of its roofline (%): the least time of the grouped
SwiGLU work a train step asks for, counted from the configuration's shapes
(each MoE layer's (experts, capacity, d) buffer per microbatch; forward, the
forward recomputed under remat='block', and the backward at twice the
forward), over the device time of the ops under `moe/gemm`. The work is
compute-bound at these widths (counts.least_time says which)."""

import counts

SCOPES = ("moe/gemm",)


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["scope_s"].get("moe/gemm"):
        return None
    cfg, mix = rec["config"], rec["traffic"]
    tokens_per_mb = mix["batch"] // mix["microbatches"] * mix["seq_len"]
    cap = counts.expert_capacity(cfg, tokens_per_mb)
    flops, nbytes = counts.expert_ffn_cost(cfg, cfg["n_experts"], cap)
    t_call, _bound = counts.least_time(flops, nbytes, counts.peaks_for(rec["device_kind"]))
    fwd_equiv = 3 + (1 if mix["program"].get("remat") == "block" else 0)
    calls = counts.moe_layers(cfg) * mix["microbatches"] * fwd_equiv * rec["steps_traced"]
    return 100.0 * t_call * calls * tr["n_devices"] / tr["scope_s"]["moe/gemm"]
