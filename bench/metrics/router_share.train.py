"""Device time of the ops under the router's scopes (`router/*`: score and
dual update, select, state update) over device busy time (%)."""

SCOPES = ("router",)


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0 or not tr["scope_s"].get("router"):
        return None
    return 100.0 * tr["scope_s"]["router"] / (tr["busy_s"] * tr["n_devices"])
