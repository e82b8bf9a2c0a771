"""How the benchmark builds the system under test from a configuration file.

The configuration file states the model that runs. `model_config` starts
from the repository's own config of the same architecture (`repo_config`)
and sets on it every field the file states:

* flat keys, the published sizes that the reference and the counts read
  too: each key of `TOP` sets the field of its name where the file states
  it; `d_ff` (the dense FFN width) defaults to `moe_d_ff`; `n_experts`,
  `top_k`, `router`, `bip_iters`, `score_fn` and `capacity_factor` set
  `RoutingSpec` fields (`ROUTING`);
* `"fields"`, an object of any further `ModelConfig` fields
  (`attn_pattern`, `window_size`, `moe_pattern`, ...), with `"routing"`
  inside it an object of `RoutingSpec` fields. JSON lists become tuples and
  dtype names `jnp` dtypes; a name that is no field is an error.

After the build every stated value must equal the built config's, so
neither the repository's config nor a traffic switch can override the file
unnoticed. `options` are the program switches a traffic mix chooses
(remat, Pallas kernels).
"""
from __future__ import annotations

import dataclasses

TOP = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "moe_d_ff",
       "vocab_size", "n_shared_experts", "rope_theta", "rms_norm_eps", "max_seq_len",
       "attn_chunk", "tie_embeddings", "param_dtype", "compute_dtype")
ROUTING = {"n_experts": "n_experts", "top_k": "top_k", "router": "strategy",
           "bip_iters": "bip_iters", "score_fn": "score_fn",
           "capacity_factor": "capacity_factor"}
ROUTING_OPTIONS = ("use_kernel", "moe_impl")


def stated_fields(cfg: dict) -> dict:
    """`ModelConfig` field -> value as the file states it; `routing` maps
    `RoutingSpec` fields. A field stated twice with two values is an error."""
    top = {k: cfg[k] for k in TOP if k in cfg}
    if "d_ff" not in top and "moe_d_ff" in top:
        top["d_ff"] = top["moe_d_ff"]
    routing = {v: cfg[k] for k, v in ROUTING.items() if k in cfg}
    extra = dict(cfg.get("fields", {}))
    for src, dst in ((extra.pop("routing", {}), routing), (extra, top)):
        if not isinstance(src, dict):
            raise ValueError(f"{cfg['name']}: states {src!r}, not an object of fields")
        for k, v in src.items():
            if k in dst and dst[k] != v:
                raise ValueError(f"{cfg['name']}: {k!r} is stated twice, as {dst[k]!r} and {v!r}")
            dst[k] = v
    if routing:
        top["routing"] = routing
    return top


def _convert(obj, stated: dict, where: str) -> dict:
    """The stated values as `obj`'s fields hold them: lists as tuples, dtype
    names as jnp dtypes, a nested config as a dict of its own fields."""
    import jax.numpy as jnp

    if not isinstance(stated, dict):
        raise ValueError(f"{where}: states {stated!r}, not an object of fields")
    have = {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}
    out = {}
    for k, v in stated.items():
        if k not in have:
            raise ValueError(f"{where}: {type(obj).__name__} has no field {k!r}")
        cur = have[k]
        if dataclasses.is_dataclass(cur):
            v = _convert(cur, v, f"{where}.{k}")
        elif isinstance(v, list):
            v = tuple(v)
        elif k.endswith("_dtype") and not isinstance(cur, str):
            v = getattr(jnp, v)
        out[k] = v
    return out


def _replace(obj, values: dict):
    return dataclasses.replace(obj, **{
        k: _replace(getattr(obj, k), v) if isinstance(v, dict) else v
        for k, v in values.items()})


def _differences(obj, values: dict, where: str) -> list:
    out = []
    for k, v in values.items():
        got = getattr(obj, k)
        if isinstance(v, dict):
            out += _differences(got, v, f"{where}.{k}")
        elif got != v:
            out.append(f"{where}.{k}: stated {v!r}, built {got!r}")
    return out


def model_config(cfg: dict, **options):
    from repro import configs

    base = configs.get(cfg["repo_config"])
    stated = _convert(base, stated_fields(cfg), cfg["name"])
    switches = _convert(base, {
        **{k: v for k, v in options.items() if k not in ROUTING_OPTIONS},
        "routing": {k: options[k] for k in ROUTING_OPTIONS if k in options}}, "options")
    out = _replace(base, {**stated, **switches,
                          "routing": {**stated.get("routing", {}), **switches["routing"]}})
    out.validate()
    wrong = _differences(out, stated, cfg["name"])
    if wrong:
        raise ValueError("the built config differs from the file:\n" + "\n".join(wrong))
    return out


def check_params(model, shapes: dict) -> None:
    """The program's parameter tree must have the reference's layout."""
    import jax

    got = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda s: tuple(s.shape), got)
    want = jax.tree.map(tuple, shapes, is_leaf=lambda s: isinstance(s, tuple))
    if got != want:
        raise ValueError(f"parameter layout differs from the reference's:\n{got}\n{want}")
