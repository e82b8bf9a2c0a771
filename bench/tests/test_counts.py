"""The counting functions against hand counts for both configurations."""
import json
import os

import pytest

import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


# per layer: attention 2·512·512 (q, o) + 2·512·128 (k, v of 2 KV heads),
# router 512·m, (top-k + 1 shared) experts of 3·512·1408; plus the tied
# unembedding 6400·512
@pytest.mark.parametrize("name,k,m,want", [
    ("minimind-moe-16e", 4, 16, 8 * (2 * 512 * 512 + 2 * 512 * 128 + 512 * 16 + 5 * 3 * 512 * 1408) + 6400 * 512),
    ("minimind-moe-64e", 8, 64, 8 * (2 * 512 * 512 + 2 * 512 * 128 + 512 * 64 + 9 * 3 * 512 * 1408) + 6400 * 512),
])
def test_matmul_params_per_token(name, k, m, want):
    cfg = _cfg(name)
    assert (cfg["top_k"], cfg["n_experts"]) == (k, m)
    assert counts.matmul_params_per_token(cfg) == want


def test_hand_counts_in_millions():
    # 98M and 168M with 8 KV heads; minimind's 2 KV heads take 3.1M off each
    assert round(counts.matmul_params_per_token(_cfg("minimind-moe-16e")) / 1e5) == 951
    assert round(counts.matmul_params_per_token(_cfg("minimind-moe-64e")) / 1e5) == 1645


def test_train_flops_per_token_16e():
    cfg = _cfg("minimind-moe-16e")
    attn = 3 * 8 * 4 * 8 * 64 * (8192 + 1) / 2  # fwd + bwd, causal mean context
    want = 6 * counts.matmul_params_per_token(cfg) + attn
    assert counts.train_flops_per_token(cfg, 8192) == pytest.approx(want)
    assert counts.train_flops_per_token(cfg, 8192) == pytest.approx(771.9e6, rel=1e-3)


def test_expert_ffn_cost_and_roofline():
    cfg = _cfg("minimind-moe-16e")
    cap = counts.expert_capacity(cfg, 8192)
    assert cap == 2560  # ceil(4 · 8192 / 16 · 1.25)
    flops, nbytes = counts.expert_ffn_cost(cfg, 16, cap)
    assert flops == 2 * 16 * 2560 * 512 * 1408 * 3
    assert nbytes == 2 * (3 * 16 * 512 * 1408 + 2 * 16 * 2560 * 512)
    t, bound = counts.least_time(flops, nbytes, counts.peaks_for("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(flops / 197e12)


def test_experts_held_defaults_to_the_routers_width():
    for name in ("minimind-moe-16e", "minimind-moe-64e"):
        cfg = _cfg(name)
        assert "experts_held" not in cfg
        assert counts.experts_held(cfg) == cfg["n_experts"]


@pytest.mark.parametrize("held,ok", [(8, True), (64, True), (1, True), (0, False),
                                     (65, False), (6, False)])
def test_experts_held_is_an_even_share(held, ok):
    cfg = dict(_cfg("minimind-moe-64e"), experts_held=held)
    if ok:
        assert counts.experts_held(cfg) == held
    else:
        with pytest.raises(ValueError, match="not an even share of the router's 64"):
            counts.experts_held(cfg)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")
