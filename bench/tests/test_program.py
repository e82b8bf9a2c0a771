"""How a configuration file becomes the ModelConfig that runs
(bench/program.py): the minimind files build what they built before the file
could state fields (every field the fixture lists at its value, every other
field at its declared default), stated fields build as stated, and a name
that is no field, or a built value that differs from a stated one, is an
error."""
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


def _before(name, mix="as_stated"):
    with open(BEFORE) as fh:
        return json.load(fh)[name][mix]


def _jsonable(v):
    if dataclasses.is_dataclass(v):
        return _jsonable(dataclasses.asdict(v))
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return np.dtype(v).name


def _default(field):
    if field.default is not dataclasses.MISSING:
        return field.default
    if field.default_factory is not dataclasses.MISSING:
        return field.default_factory()
    return dataclasses.MISSING


def departures(built, before: dict, where: str) -> list:
    """Where `built` departs from `before`, the fixture's record of an earlier
    build: a field it lists at another value (nested configs field by field),
    a field it lists that the build no longer has, or a field it does not list
    at other than the default its dataclass declares. A field added with an
    inert default leaves the build as it was and passes."""
    fields = {f.name: f for f in dataclasses.fields(built)}
    out = [f"{where}.{k}: in the fixture, not a field of the build"
           for k in before if k not in fields]
    for k, field in fields.items():
        got = getattr(built, k)
        if k not in before:
            default = _default(field)
            if default is dataclasses.MISSING or _jsonable(got) != _jsonable(default):
                out.append(f"{where}.{k}: not in the fixture, built {got!r}, "
                           f"declared default {default!r}")
        elif dataclasses.is_dataclass(got) and isinstance(before[k], dict):
            out += departures(got, before[k], f"{where}.{k}")
        elif _jsonable(got) != before[k]:
            out.append(f"{where}.{k}: built {_jsonable(got)!r}, before {before[k]!r}")
    return out


@pytest.mark.parametrize("mix", ["as_stated", "train_8k"])
@pytest.mark.parametrize("name", ["minimind-moe-16e", "minimind-moe-64e"])
def test_minimind_builds_as_before(name, mix):
    options = {} if mix == "as_stated" else _json("traffic", mix + ".json")["program"]
    got = program.model_config(_json("configs", name + ".json"), **options)
    assert departures(got, _before(name, mix), name) == []


@dataclasses.dataclass(frozen=True)
class _Routing(configs.RoutingSpec):
    held_share: int = 0


@dataclasses.dataclass(frozen=True)
class _WithRank(configs.ModelConfig):
    latent_rank: int = 0


@dataclasses.dataclass(frozen=True)
class _WithRouting(configs.ModelConfig):
    routing: configs.RoutingSpec = _Routing()


def _as(cls, built, **values):
    return cls(**{f.name: getattr(built, f.name) for f in dataclasses.fields(built)}, **values)


@pytest.mark.parametrize("added", ["latent_rank", "routing.held_share"])
def test_the_comparator_takes_a_new_field_only_at_its_default(added):
    before = _before("minimind-moe-16e")
    built = program.model_config(_json("configs", "minimind-moe-16e.json"))
    if added == "latent_rank":
        at_default, changed = _as(_WithRank, built), _as(_WithRank, built, latent_rank=512)
    else:
        routing = _as(_Routing, built.routing)
        at_default = dataclasses.replace(_as(_WithRouting, built), routing=routing)
        changed = dataclasses.replace(at_default, routing=dataclasses.replace(
            routing, held_share=8))
    assert departures(at_default, before, "m") == []
    assert [d.split(":")[0] for d in departures(changed, before, "m")] == ["m." + added]


def test_the_comparator_refuses_a_changed_or_removed_field():
    before = _before("minimind-moe-16e")
    built = program.model_config(_json("configs", "minimind-moe-16e.json"))
    changed = dataclasses.replace(built, routing=dataclasses.replace(built.routing, top_k=2))
    assert [d.split(":")[0] for d in departures(changed, before, "m")] == ["m.routing.top_k"]
    assert departures(dataclasses.replace(built, window_size=1024), before, "m")
    removed = dict(before, attn_window=1024)
    assert [d.split(":")[0] for d in departures(built, removed, "m")] == ["m.attn_window"]


def test_experts_held_is_a_routing_field():
    cfg = {"name": "share", "n_experts": 64, "top_k": 6, "experts_held": 8}
    assert program.stated_fields(cfg) == {
        "routing": {"n_experts": 64, "top_k": 6, "experts_held": 8}}


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
