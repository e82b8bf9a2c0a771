"""Expert FFN backward's share of its roofline (%): the least time of the
backward of each grouped SwiGLU call, counted at twice the forward's work
(the convention of expert_ffn_roofline.train; counts.expert_ffn_cost) for
every MoE layer (counts.moe_layers), microbatch and traced step, over the
device time of the ops that do that work in kernels/ops' custom VJP: the dgrads
(`moe/gemm/bwd/dgrad`) and the wgrads (`moe/gemm/bwd/wgrad`). The gate/up
recompute (`moe/gemm/bwd/remat`) is forward work and is left out of both."""

import counts

SCOPES = ("moe/gemm/bwd/dgrad", "moe/gemm/bwd/wgrad")


def read(rec):
    tr = rec.get("trace")
    t_bwd = sum(tr["scope_s"].get(s, 0.0) for s in SCOPES) if tr else 0.0
    if not t_bwd:
        return None
    cfg, mix = rec["config"], rec["traffic"]
    tokens_per_mb = mix["batch"] // mix["microbatches"] * mix["seq_len"]
    cap = counts.expert_capacity(cfg, tokens_per_mb)
    flops, nbytes = counts.expert_ffn_cost(cfg, cfg["n_experts"], cap)
    t_call, _bound = counts.least_time(flops, nbytes, counts.peaks_for(rec["device_kind"]))
    calls = counts.moe_layers(cfg) * mix["microbatches"] * rec["steps_traced"]
    return 100.0 * 2 * t_call * calls * tr["n_devices"] / t_bwd
