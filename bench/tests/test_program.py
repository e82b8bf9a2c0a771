"""How a configuration file becomes the ModelConfig that runs
(bench/program.py): the minimind files build what they built before the file
could state fields, stated fields build as stated, and a name that is no
field, or a built value that differs from a stated one, is an error."""
import dataclasses
import json
import os

import numpy as np
import pytest

import program
from repro import configs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# each minimind file's build as the flat-keys-only program.py made it,
# as stated and with the train_8k mix's program switches
BEFORE = os.path.join(BENCH, "tests", "fixtures", "model_configs.json")


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def _jsonable(v):
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return np.dtype(v).name


@pytest.mark.parametrize("mix", ["as_stated", "train_8k"])
@pytest.mark.parametrize("name", ["minimind-moe-16e", "minimind-moe-64e"])
def test_minimind_builds_as_before(name, mix):
    options = {} if mix == "as_stated" else _json("traffic", mix + ".json")["program"]
    got = program.model_config(_json("configs", name + ".json"), **options)
    assert _jsonable(dataclasses.asdict(got)) == _json("tests", "fixtures",
                                                      "model_configs.json")[name][mix]


def _windowed():
    cfg = _json("configs", "minimind-moe-16e.json")
    cfg.update(name="windowed", d_ff=2816, tie_embeddings=False, fields={
        "attn_pattern": ["local", "global"], "window_size": 1024,
        "moe_pattern": [False, True, True, True], "qk_norm": True,
        "routing": {"norm_topk_prob": True, "sync": "global"}})
    return cfg


def test_stated_fields_build_as_stated():
    import jax.numpy as jnp

    got = program.model_config(_windowed(), remat="block", use_kernel=True)
    assert (got.d_ff, got.moe_d_ff, got.tie_embeddings) == (2816, 1408, False)
    assert got.attn_pattern == ("local", "global") and got.window_size == 1024
    assert got.moe_pattern == (False, True, True, True) and got.qk_norm
    assert got.routing.norm_topk_prob and got.routing.sync == "global"
    assert (got.routing.n_experts, got.routing.top_k, got.routing.strategy) == (16, 4, "bip")
    assert got.remat == "block" and got.routing.use_kernel
    assert got.param_dtype == jnp.float32 and got.compute_dtype == jnp.bfloat16
    kinds = got.layer_kinds()
    assert kinds[:4] == (("local", "dense"), ("global", "moe"), ("local", "moe"),
                         ("global", "moe"))


def test_d_ff_defaults_to_the_expert_width():
    cfg = _json("configs", "minimind-moe-16e.json")
    assert "d_ff" not in cfg
    assert program.model_config(cfg).d_ff == cfg["moe_d_ff"]


def test_unstated_flat_keys_keep_the_repository_value():
    cfg = _json("configs", "minimind-moe-16e.json")
    del cfg["rope_theta"], cfg["bip_iters"]
    got = program.model_config(cfg)
    base = configs.get(cfg["repo_config"])
    assert got.rope_theta == base.rope_theta
    assert got.routing.bip_iters == base.routing.bip_iters


@pytest.mark.parametrize("fields,match", [
    ({"attn_window": 1024}, "ModelConfig has no field 'attn_window'"),
    ({"routing": {"n_expert_groups": 2}}, "RoutingSpec has no field 'n_expert_groups'"),
    ({"routing": 4}, "not an object of fields"),
])
def test_unknown_field_is_an_error(fields, match):
    cfg = dict(_json("configs", "minimind-moe-16e.json"), fields=fields)
    with pytest.raises(ValueError, match=match):
        program.model_config(cfg)


def test_a_field_stated_twice_is_an_error():
    cfg = dict(_json("configs", "minimind-moe-16e.json"), fields={"n_layers": 4})
    with pytest.raises(ValueError, match="'n_layers' is stated twice"):
        program.model_config(cfg)
    cfg = dict(_json("configs", "minimind-moe-16e.json"), fields={"routing": {"top_k": 2}})
    with pytest.raises(ValueError, match="'top_k' is stated twice"):
        program.model_config(cfg)


def test_a_repository_config_that_overrides_the_file_is_an_error(monkeypatch):
    """A repository config whose construction puts its own value over a
    stated one (here: one that pins its window) is caught after the build."""

    @dataclasses.dataclass(frozen=True)
    class Pinned(configs.ModelConfig):
        def __post_init__(self):
            object.__setattr__(self, "window_size", 4096)

    base = configs.get("minimind-moe-16e")
    pinned = Pinned(**{f.name: getattr(base, f.name) for f in dataclasses.fields(base)})
    monkeypatch.setattr(configs, "get", lambda name: pinned)
    cfg = _windowed()
    with pytest.raises(ValueError, match=r"windowed.window_size: stated 1024, built 4096"):
        program.model_config(cfg)
    cfg["fields"]["window_size"] = 4096
    assert program.model_config(cfg).window_size == 4096


def test_a_traffic_switch_that_overrides_the_file_is_an_error():
    cfg = _windowed()
    cfg["fields"].update(remat="none", routing={"use_kernel": False})
    with pytest.raises(ValueError, match="built 'block'"):
        program.model_config(cfg, remat="block")
    with pytest.raises(ValueError, match=r"routing.use_kernel: stated False, built True"):
        program.model_config(cfg, use_kernel=True)
