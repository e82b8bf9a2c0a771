"""Device self time of the ops under the `attn` scope (all of
models/common attention: the q/k/v projections, RoPE, the chunked score
scan with its softmax, the output projection, and their backward) over
device busy time (%)."""

SCOPES = ("attn",)


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0 or not tr["scope_s"].get("attn"):
        return None
    return 100.0 * tr["scope_s"]["attn"] / (tr["busy_s"] * tr["n_devices"])
