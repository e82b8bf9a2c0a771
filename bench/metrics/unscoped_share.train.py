"""Share of device busy time spent in ops outside every layer scope of the
train step (%): the scan carries, remat copies and casts XLA places
between the disjoint layer scopes `embed`, `attn`, `norm`, `router`,
`moe`, `lm_head`, `train/apply` and `mlp` (a dense FFN layer's, such as a
leading dense layer's; a model without one has no op under it). Read only
where the program has the layer map (an `attn` scope)."""

SCOPES = ("embed", "attn", "norm", "router", "moe", "lm_head", "train/apply", "mlp")


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0 or not tr["scope_s"].get("attn"):
        return None
    busy = tr["busy_s"] * tr["n_devices"]
    return 100.0 * (busy - sum(tr["scope_s"].get(s, 0.0) for s in SCOPES)) / busy
