"""Balancer-registry refactor suite.

The pluggable-balancer API (core/balancers.py) must be a pure refactor for
the four paper strategies: `route()` through the registry produces
BITWISE-identical RouterOutput fields and state trajectories to the frozen
pre-refactor implementation (tests/_legacy_router.py) — including masked
serving rows, guard_duals + forecast state, local_shards vmapping, and
sync='global' on a forced 4x2 host mesh. On top of that: smokes for the
registry additions (phi / lpr / expert_choice), checkpoint-resume
bit-exactness for lpr's 2-D prototype leaves, registry error messages, and
the expert-choice serving/decode rejection.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, "tests")
from _forced_devices import PRELUDE, run_code
from _legacy_router import legacy_route

from repro.core import (
    RouterConfig,
    get_balancer,
    init_router_state,
    registered_balancers,
    route,
)

LEGACY = ("topk", "aux_loss", "lossfree", "bip")
N, M, K = 64, 16, 4


def _logits_stream(seed, steps, n=N, m=M):
    rng = np.random.default_rng(seed)
    # mild expert-popularity skew so balancing methods have work to do
    skew = np.linspace(1.0, -1.0, m)[None, :]
    return [
        jnp.asarray(rng.standard_normal((n, m)) + skew, jnp.float32)
        for _ in range(steps)
    ]


def _assert_trajectory_parity(cfg, steps=5, token_mask=None, local_shards=1):
    st_new = init_router_state(cfg)
    st_old = dict(st_new)
    seed = sum(ord(c) for c in cfg.strategy)
    for t, logits in enumerate(_logits_stream(seed, steps)):
        o_new = route(
            logits, st_new, cfg, token_mask=token_mask, local_shards=local_shards
        )
        o_old = legacy_route(
            logits, st_old, cfg, token_mask=token_mask, local_shards=local_shards
        )
        np.testing.assert_array_equal(
            np.asarray(o_new.combine_weights), np.asarray(o_old.combine_weights)
        )
        np.testing.assert_array_equal(
            np.asarray(o_new.expert_index), np.asarray(o_old.expert_index)
        )
        np.testing.assert_array_equal(
            np.asarray(o_new.aux_loss), np.asarray(o_old.aux_loss)
        )
        assert set(o_new.state) == set(o_old.state)
        for key in o_new.state:
            np.testing.assert_array_equal(
                np.asarray(o_new.state[key]),
                np.asarray(o_old.state[key]),
                err_msg=f"strategy={cfg.strategy} step={t} state[{key!r}]",
            )
        st_new, st_old = o_new.state, o_old.state


@pytest.mark.parametrize("strategy", LEGACY)
def test_registry_parity_plain(strategy):
    _assert_trajectory_parity(RouterConfig(n_experts=M, top_k=K, strategy=strategy))


@pytest.mark.parametrize("strategy", LEGACY)
def test_registry_parity_masked_serving_rows(strategy):
    mask = jnp.asarray(np.random.default_rng(7).random(N) > 0.4)
    _assert_trajectory_parity(
        RouterConfig(n_experts=M, top_k=K, strategy=strategy), token_mask=mask
    )


@pytest.mark.parametrize("strategy", LEGACY)
def test_registry_parity_guard_duals(strategy):
    _assert_trajectory_parity(
        RouterConfig(n_experts=M, top_k=K, strategy=strategy, guard_duals=True)
    )


@pytest.mark.parametrize("strategy", LEGACY)
def test_registry_parity_global_singledevice(strategy):
    # sync='global' with no mesh: the threshold/bisection solver for bip,
    # degenerate (empty-axis) psums for lossfree
    _assert_trajectory_parity(
        RouterConfig(n_experts=M, top_k=K, strategy=strategy, sync="global")
    )


def test_registry_parity_bip_forecast_guard():
    _assert_trajectory_parity(
        RouterConfig(
            n_experts=M, top_k=K, strategy="bip",
            sync="global", forecast=True, guard_duals=True,
        ),
        steps=6,
    )


def test_registry_parity_bip_no_warm_start_and_local_shards():
    _assert_trajectory_parity(
        RouterConfig(n_experts=M, top_k=K, strategy="bip", bip_warm_start=False)
    )
    _assert_trajectory_parity(
        RouterConfig(n_experts=M, top_k=K, strategy="bip"), local_shards=4
    )


def test_registry_parity_norm_topk_sigmoid():
    _assert_trajectory_parity(
        RouterConfig(
            n_experts=M, top_k=K, strategy="bip",
            norm_topk_prob=True, score_fn="sigmoid",
        )
    )


def test_registry_parity_global_mesh_4x2():
    """Bitwise parity of route() vs the frozen legacy router under
    shard_map on a forced 4x2 mesh, sync='global' (psum'd dual stats /
    selection histograms over the data axis), 3-step state trajectories."""
    run_code(
        PRELUDE
        + r"""
sys.path.insert(0, "tests")
from repro.core import RouterConfig, init_router_state, route
from _legacy_router import legacy_route

n, m, k = 64, 16, 4
mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
for strategy in ("topk", "aux_loss", "lossfree", "bip", "bip_forecast"):
    forecast = strategy == "bip_forecast"
    cfg = RouterConfig(
        n_experts=m, top_k=k,
        strategy="bip" if forecast else strategy,
        sync="global", data_axes=("data",),
        forecast=forecast, guard_duals=True,
    )

    def pair(logits, st_new, st_old):
        o_new = route(logits, st_new, cfg)
        o_old = legacy_route(logits, st_old, cfg)
        return (
            (o_new.combine_weights, o_new.expert_index, o_new.aux_loss,
             o_new.state),
            (o_old.combine_weights, o_old.expert_index, o_old.aux_loss,
             o_old.state),
        )

    st = init_router_state(cfg)
    state_spec = jax.tree.map(lambda _: P(), st)
    fn = jax.jit(jax.shard_map(
        pair, mesh=mesh,
        in_specs=(P("data", None), state_spec, state_spec),
        out_specs=((P("data", None), P("data", None), P(), state_spec),) * 2,
        check_vma=False,
    ))
    st_new, st_old = st, dict(st)
    rng = np.random.default_rng(3)
    for t in range(3):
        logits = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
        (w_n, i_n, a_n, st_new), (w_o, i_o, a_o, st_old) = fn(
            logits, st_new, st_old
        )
        for a, b in ((w_n, w_o), (i_n, i_o), (a_n, a_o)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (strategy, t)
        for key in st_new:
            assert np.array_equal(
                np.asarray(st_new[key]), np.asarray(st_old[key])
            ), (strategy, t, key)
print("mesh parity ok")
"""
    )


# ------------------------------------------------------------ new methods


def test_registry_lists_all_methods():
    assert set(registered_balancers()) >= {
        "topk", "aux_loss", "lossfree", "bip", "phi", "lpr", "expert_choice"
    }


@pytest.mark.parametrize("strategy", ["phi", "lpr", "expert_choice"])
def test_new_method_smoke(strategy):
    cfg = RouterConfig(n_experts=M, top_k=K, strategy=strategy)
    st = init_router_state(cfg)
    for logits in _logits_stream(11, 6):
        out = route(logits, st, cfg)
        st = out.state
        assert np.isfinite(np.asarray(out.combine_weights)).all()
        assert np.isfinite(float(out.metrics["max_vio"]))
        idx = np.asarray(out.expert_index)
        if strategy == "expert_choice":
            # sentinel slots allowed (uncovered tokens), never beyond m
            assert idx.max() <= M and float(out.metrics["max_vio"]) <= 0.25
            assert {"coverage_full", "coverage_zero"} <= set(out.metrics)
        else:
            assert idx.max() < M
    if strategy == "phi":
        # recentred log-correction: mean(phi) == 0 up to float error
        assert abs(float(np.asarray(st["q"]).mean())) < 1e-6
    if strategy == "lpr":
        assert st["proto"].shape == (M, M)


def test_phi_balances_skewed_stream_better_than_topk():
    vios = {}
    for strategy in ("topk", "phi"):
        cfg = RouterConfig(n_experts=M, top_k=K, strategy=strategy, phi_lr=0.05)
        st = init_router_state(cfg)
        last = None
        for logits in _logits_stream(5, 20):
            out = route(logits, st, cfg)
            st, last = out.state, float(out.metrics["max_vio"])
        vios[strategy] = last
    assert vios["phi"] < vios["topk"]


def test_lpr_stack_state_tiles_2d_leaves():
    import dataclasses

    import repro.configs as configs
    from repro.models.stack import init_stack_router_states

    cfg = configs.reduced_for_smoke("minimind_moe_16e")
    cfg = dataclasses.replace(
        cfg, routing=dataclasses.replace(cfg.routing, strategy="lpr")
    )
    states = init_stack_router_states(cfg)
    moe_states = [s for s in states if s is not None]
    assert moe_states, "minimind config must have MoE positions"
    m = cfg.routing.n_experts
    for st in moe_states:
        reps = st["q"].shape[0]
        assert st["q"].shape == (reps, m)
        assert st["proto"].shape == (reps, m, m)
        # every layer starts at the identity prototype
        np.testing.assert_array_equal(
            np.asarray(st["proto"]), np.stack([np.eye(m)] * reps)
        )


def test_lpr_checkpoint_resume_bit_exact(tmp_path):
    """The (m, m) prototype leaf round-trips the npz checkpoint store and a
    resumed trajectory is bitwise-identical to the uninterrupted one."""
    from repro.checkpoint.store import CheckpointManager

    cfg = RouterConfig(n_experts=M, top_k=K, strategy="lpr")
    stream = _logits_stream(23, 6)

    st = init_router_state(cfg)
    uninterrupted = []
    for logits in stream:
        out = route(logits, st, cfg)
        st = out.state
        uninterrupted.append(st)

    store = CheckpointManager(str(tmp_path))
    st = init_router_state(cfg)
    for logits in stream[:3]:
        st = route(logits, st, cfg).state
    store.save(3, st)
    _, restored = store.restore(3)
    for key in st:
        np.testing.assert_array_equal(np.asarray(st[key]), restored[key])
    st = jax.tree.map(jnp.asarray, restored)
    for t, logits in enumerate(stream[3:]):
        st = route(logits, st, cfg).state
        for key in st:
            np.testing.assert_array_equal(
                np.asarray(st[key]),
                np.asarray(uninterrupted[3 + t][key]),
                err_msg=f"resume step {t} state[{key!r}]",
            )


# ----------------------------------------------------- API contract edges


def test_unknown_strategy_error_lists_registered():
    with pytest.raises(ValueError, match="registered:.*bip.*lpr"):
        RouterConfig(n_experts=M, top_k=K, strategy="nope")
    with pytest.raises(ValueError, match="unknown routing strategy"):
        get_balancer("also-nope")


def test_balance_sweep_methods_flag_resolves_registry():
    sys.path.insert(0, ".")
    from benchmarks.balance_sweep import MATRIX_METHODS, _resolve_methods

    assert _resolve_methods(None, ("bip",)) == ("bip",)
    assert _resolve_methods("phi, lpr", ("bip",)) == ("phi", "lpr")
    assert set(MATRIX_METHODS) == set(registered_balancers())
    with pytest.raises(ValueError, match="registered:"):
        _resolve_methods("bip,bogus", ("bip",))


def test_expert_choice_rejects_serving_mask():
    cfg = RouterConfig(n_experts=M, top_k=K, strategy="expert_choice")
    mask = jnp.ones((N,), bool)
    with pytest.raises(NotImplementedError, match="training-only"):
        route(jnp.zeros((N, M)), init_router_state(cfg), cfg, token_mask=mask)


def test_expert_choice_rejects_serving_engine():
    import dataclasses

    import repro.configs as configs
    from repro.models import build_model
    from repro.serving.engine import ContinuousBatchingEngine

    cfg = configs.reduced_for_smoke("minimind_moe_16e")
    cfg = dataclasses.replace(
        cfg, routing=dataclasses.replace(cfg.routing, strategy="expert_choice")
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="training-only"):
        ContinuousBatchingEngine(model, params, n_slots=2, chunk_size=8)


def test_unsupported_combo_warns_once():
    import repro.core.balancers as balancers_mod

    balancers_mod._warned.discard("kernel-unused-lossfree")
    cfg = RouterConfig(n_experts=M, top_k=K, strategy="lossfree", use_kernel=True)
    st = init_router_state(cfg)
    logits = _logits_stream(1, 1)[0]
    with pytest.warns(UserWarning, match="use_kernel.*ignored"):
        route(logits, st, cfg)
    # second call: warn-once
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        route(logits, st, cfg)


def test_routing_spec_single_validation_path():
    from repro.configs.base import RoutingSpec

    with pytest.raises(ValueError, match="registered:"):
        RoutingSpec(n_experts=8, top_k=2, strategy="bogus")
    # dense default (0 experts) stays inert — no validation crash
    RoutingSpec()
    spec = RoutingSpec(n_experts=8, top_k=2, strategy="lpr", lpr_blend=0.3)
    rcfg = spec.to_router_config(data_axes=("data",))
    assert rcfg.strategy == "lpr"
    assert rcfg.lpr_blend == 0.3
    assert rcfg.data_axes == ("data",)
