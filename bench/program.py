"""How the benchmark builds the system under test from a configuration file.

The configuration file states the model's sizes and dtypes; `model_config`
starts from the repository's own config of the same model and sets every
stated number on it, so what runs is what the file says. `options` are the
program switches a traffic mix chooses (remat, Pallas kernels).
"""
from __future__ import annotations

import dataclasses

TOP = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "vocab_size",
       "n_shared_experts", "rope_theta", "rms_norm_eps", "max_seq_len", "attn_chunk",
       "tie_embeddings")
ROUTING = {"n_experts": "n_experts", "top_k": "top_k", "router": "strategy",
           "bip_iters": "bip_iters", "score_fn": "score_fn",
           "capacity_factor": "capacity_factor"}


def model_config(cfg: dict, **options):
    import jax.numpy as jnp
    from repro import configs

    base = configs.get(cfg["repo_config"])
    routing = dataclasses.replace(
        base.routing, **{v: cfg[k] for k, v in ROUTING.items()},
        **{k: options.pop(k) for k in ("use_kernel", "moe_impl") if k in options})
    out = dataclasses.replace(
        base, **{k: cfg[k] for k in TOP}, d_ff=cfg["moe_d_ff"], moe_d_ff=cfg["moe_d_ff"],
        param_dtype=getattr(jnp, cfg["param_dtype"]),
        compute_dtype=getattr(jnp, cfg["compute_dtype"]), routing=routing, **options)
    out.validate()
    return out


def check_params(model, shapes: dict) -> None:
    """The program's parameter tree must have the reference's layout."""
    import jax

    got = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda s: tuple(s.shape), got)
    want = jax.tree.map(tuple, shapes, is_leaf=lambda s: isinstance(s, tuple))
    if got != want:
        raise ValueError(f"parameter layout differs from the reference's:\n{got}\n{want}")
