"""Unit + property tests for the routing core (Algorithm 1/2 reference)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    RouterConfig,
    balance_metrics,
    bip_dual_update,
    bip_dual_update_global,
    bip_dual_update_threshold,
    bip_route_reference,
    init_router_state,
    kth_largest,
    kth_largest_threshold,
    route,
)
from repro.core.lp_oracle import greedy_balanced_objective, routing_objective, solve_plp

jax.config.update("jax_enable_x64", False)


def _scores(rng, n, m, skew=0.0):
    """Softmax scores with an optional popularity skew (collapse pressure)."""
    logits = rng.standard_normal((n, m)).astype(np.float32)
    logits += skew * np.linspace(2.0, -2.0, m)[None, :]
    return jax.nn.softmax(jnp.asarray(logits), axis=-1)


# ---------------------------------------------------------------- kth largest


@given(
    n=st.integers(4, 200),
    kth=st.integers(0, 10),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_kth_largest_matches_numpy(n, kth, seed):
    kth = min(kth, n - 1)
    x = np.random.default_rng(seed).standard_normal((n,)).astype(np.float32)
    got = kth_largest(jnp.asarray(x), kth)
    want = np.sort(x)[::-1][kth]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@given(
    n=st.integers(8, 300),
    kth=st.integers(0, 40),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_threshold_kth_partitions_correctly(n, kth, seed):
    """The bisected threshold must admit <= kth elements strictly above it,
    and the set {x > thr} must be exactly the top-kth set when values are
    distinct (which standard normals are, a.s.)."""
    kth = min(kth, n - 1)
    x = np.random.default_rng(seed).standard_normal((n,)).astype(np.float32)
    thr = np.asarray(kth_largest_threshold(jnp.asarray(x), kth, n_bisect=40))
    above = int((x > thr).sum())
    assert above <= kth
    # distinct values: everything strictly greater than the true kth+1-th
    # largest must stay above the threshold.
    want = np.sort(x)[::-1][kth]
    assert int((x > want + 1e-5).sum()) <= above + kth  # sanity
    np.testing.assert_allclose(thr, want, atol=2e-5)


# ------------------------------------------------------------- dual update


def test_dual_update_balances_skewed_scores():
    """Under heavy popularity skew, raw top-k collapses but s - q is balanced."""
    rng = np.random.default_rng(0)
    n, m, k = 512, 16, 4
    s = _scores(rng, n, m, skew=2.0)
    # raw top-k: badly unbalanced
    raw = balance_metrics(jax.lax.top_k(s, k)[1].astype(jnp.int32), m, k)
    assert float(raw["max_vio"]) > 1.0
    w, idx, q = bip_route_reference(s, jnp.zeros((m,)), top_k=k, n_iters=8)
    bal = balance_metrics(idx, m, k)
    assert float(bal["max_vio"]) < 0.15, float(bal["max_vio"])
    # gate values must be the raw scores of selected experts
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    )
    assert np.all(np.asarray(q) >= 0.0)


@given(seed=st.integers(0, 2**31 - 1), t=st.sampled_from([2, 4, 8]))
@settings(max_examples=10, deadline=None)
def test_dual_update_threshold_matches_topk_variant(seed, t):
    rng = np.random.default_rng(seed)
    n, m, k = 256, 8, 2
    s = _scores(rng, n, m, skew=1.0)
    q_ref, p_ref = bip_dual_update(s, jnp.zeros((m,)), top_k=k, n_iters=t)
    q_thr, p_thr = bip_dual_update_threshold(
        s, jnp.zeros((m,)), top_k=k, n_iters=t, n_bisect=40
    )
    np.testing.assert_allclose(np.asarray(q_ref), np.asarray(q_thr), atol=3e-5)
    np.testing.assert_allclose(np.asarray(p_ref), np.asarray(p_thr), atol=3e-5)


def _selection_sets(s, q, k):
    """Per-row top-k index sets under corrected scores, plus the boundary
    gap (k-th minus (k+1)-th corrected value) that prices tie fragility."""
    corrected = np.asarray(s) - np.asarray(q)[None, :]
    order = np.argsort(-corrected, axis=-1, kind="stable")
    sets = [frozenset(row[:k]) for row in order]
    kth = np.take_along_axis(corrected, order, -1)
    gaps = kth[:, k - 1] - kth[:, k]
    return sets, gaps


@given(
    seed=st.integers(0, 2**31 - 1),
    t=st.sampled_from([1, 2, 4, 8]),
    warm=st.floats(0.0, 0.3),
    skew=st.floats(0.0, 2.0),
)
@settings(max_examples=25, deadline=None)
def test_threshold_vs_sort_dual_selection_set_equivalence(seed, t, warm, skew):
    """The threshold (bisection) dual update is the sync='global' building
    block: the expert SETS it selects must match the sort-based oracle's
    for every token whose top-k boundary gap exceeds the bisection
    resolution (~6e-8 at n_bisect=40 over softmax ranges; tokens inside
    that band are capacity-marginal and LP-degenerate — either choice is
    an optimal assignment). Warm-start duals exercise the carried-q path."""
    rng = np.random.default_rng(seed)
    n, m, k = 256, 16, 4
    s = _scores(rng, n, m, skew=skew)
    q0 = jnp.asarray(rng.uniform(0, warm, (m,)).astype(np.float32))
    q_ref, _ = bip_dual_update(s, q0, top_k=k, n_iters=t)
    q_thr, _ = bip_dual_update_threshold(s, q0, top_k=k, n_iters=t, n_bisect=40)
    np.testing.assert_allclose(np.asarray(q_ref), np.asarray(q_thr), atol=3e-5)
    sets_ref, gaps = _selection_sets(s, q_ref, k)
    sets_thr, _ = _selection_sets(s, q_thr, k)
    robust = gaps > 3e-4  # >=10x the dual atol: no margin flake
    assert robust.sum() > 0  # the property must not be vacuous
    mismatched = [
        i for i in range(n) if robust[i] and sets_ref[i] != sets_thr[i]
    ]
    assert not mismatched, (mismatched[:5], gaps[mismatched[:5]])


@given(
    seed=st.integers(0, 2**31 - 1),
    t=st.sampled_from([2, 4]),
    frac=st.floats(0.2, 0.9),
)
@settings(max_examples=25, deadline=None)
def test_masked_dual_update_equals_dense_subset(seed, t, frac):
    """Masked padding rows (the serving path) must be invisible: the dual
    from the masked update over (real + padding) rows equals the sort-based
    update over just the real rows, and the selection sets on real rows
    agree outside the degenerate boundary band. Also pins the all-True
    mask to the unmasked threshold variant."""
    rng = np.random.default_rng(seed)
    n, m, k = 192, 8, 2
    s = _scores(rng, n, m, skew=1.0)
    q0 = jnp.asarray(rng.uniform(0, 0.2, (m,)).astype(np.float32))
    mask = rng.random(n) < frac
    mask[0] = True  # never all-padding
    jmask = jnp.asarray(mask)

    q_m, _ = bip_dual_update_global(
        s, q0, top_k=k, n_iters=t, token_mask=jmask, n_bisect=40
    )
    q_dense, _ = bip_dual_update(
        jnp.asarray(np.asarray(s)[mask]), q0, top_k=k, n_iters=t
    )
    np.testing.assert_allclose(np.asarray(q_m), np.asarray(q_dense), atol=3e-5)

    s_real = np.asarray(s)[mask]
    sets_m, gaps = _selection_sets(s_real, q_m, k)
    sets_d, _ = _selection_sets(s_real, q_dense, k)
    robust = gaps > 3e-4
    mismatched = [
        i for i in range(len(sets_m)) if robust[i] and sets_m[i] != sets_d[i]
    ]
    assert not mismatched, mismatched[:5]

    # all-True mask == the unmasked threshold variant (same bisection)
    q_all, _ = bip_dual_update_global(
        s, q0, top_k=k, n_iters=t, token_mask=jnp.ones((n,), bool), n_bisect=40
    )
    q_thr, _ = bip_dual_update_threshold(s, q0, top_k=k, n_iters=t, n_bisect=40)
    np.testing.assert_allclose(np.asarray(q_all), np.asarray(q_thr), atol=1e-6)


@given(
    seed=st.integers(0, 2**31 - 1),
    t=st.sampled_from([2, 4]),
    frac=st.floats(0.0, 0.9),
)
@settings(max_examples=25, deadline=None)
def test_sort_dual_mask_is_exact(seed, t, frac):
    """The sort form's masked update (the serving path of a sync='local'
    gate) is exact, not approximate: bitwise the unmasked update over the
    real rows alone, bitwise the unmasked update under an all-True mask,
    and an all-padding call leaves the warm start unchanged."""
    rng = np.random.default_rng(seed)
    n, m, k = 96, 8, 2
    s = _scores(rng, n, m, skew=1.0)
    q0 = jnp.asarray(rng.uniform(0, 0.2, (m,)).astype(np.float32))
    mask = rng.random(n) >= frac
    mask[0] = True
    q_m, _ = bip_dual_update(s, q0, top_k=k, n_iters=t, token_mask=jnp.asarray(mask))
    q_real, _ = bip_dual_update(
        jnp.asarray(np.asarray(s)[mask]), q0, top_k=k, n_iters=t
    )
    np.testing.assert_array_equal(np.asarray(q_m), np.asarray(q_real))
    q_all, _ = bip_dual_update(s, q0, top_k=k, n_iters=t, token_mask=jnp.ones((n,), bool))
    q_plain, _ = bip_dual_update(s, q0, top_k=k, n_iters=t)
    np.testing.assert_array_equal(np.asarray(q_all), np.asarray(q_plain))
    q_idle, _ = bip_dual_update(s, q0, top_k=k, n_iters=t, token_mask=jnp.zeros((n,), bool))
    np.testing.assert_array_equal(np.asarray(q_idle), np.asarray(q0))


# ------------------------------------------- fused multi-threshold bisection


@given(
    n=st.integers(8, 300),
    kth=st.integers(0, 40),
    fanout=st.sampled_from([2, 7, 15, 32]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_fused_fanout_threshold_matches_classic_bisection(n, kth, fanout, seed):
    """fanout>1 probes F thresholds per fused count and must land on the
    same order statistic as classic bisection (fanout=1): each within its
    bracket resolution of the true sort value, and both must keep the
    partition property (<= kth elements strictly above the threshold)."""
    kth = min(kth, n - 1)
    x = np.random.default_rng(seed).standard_normal((n,)).astype(np.float32)
    want = np.sort(x)[::-1][kth]
    for f in (1, fanout):
        thr = np.asarray(
            kth_largest_threshold(jnp.asarray(x), kth, n_bisect=26, fanout=f)
        )
        assert int((x > thr).sum()) <= kth, (f, thr, want)
        np.testing.assert_allclose(thr, want, atol=2e-5, err_msg=f"fanout={f}")


@given(
    seed=st.integers(0, 2**31 - 1),
    fanout=st.sampled_from([1, 4, 32]),
    good=st.sampled_from([True, False]),
)
@settings(max_examples=25, deadline=None)
def test_forecast_window_valid_and_stale(seed, fanout, good):
    """A valid predicted bracket must not change the answer (it only
    tightens round 0); a stale bracket — shifted entirely off the
    statistic — must fail the in-round validity check (count(w_lo) > kth
    >= count(w_hi)) and fall back to the full range, also unchanged."""
    rng = np.random.default_rng(seed)
    n, kth = 200, 10
    x = rng.standard_normal((n,)).astype(np.float32)
    want = np.sort(x)[::-1][kth]
    if good:
        w = (jnp.float32(want - 0.05), jnp.float32(want + 0.05))
    else:
        w = (jnp.float32(want + 1.0), jnp.float32(want + 2.0))
    thr = np.asarray(
        kth_largest_threshold(
            jnp.asarray(x), kth, n_bisect=26, fanout=fanout, window=w
        )
    )
    assert int((x > thr).sum()) <= kth
    np.testing.assert_allclose(thr, want, atol=2e-5)


@given(
    seed=st.integers(0, 2**31 - 1),
    t=st.sampled_from([2, 4]),
    fanout=st.sampled_from([2, 8, 32]),
)
@settings(max_examples=15, deadline=None)
def test_global_dual_fused_fanout_matches_sort_oracle(seed, t, fanout):
    """The production sync='global' configuration — fanout>1, static
    softmax score bounds, cold forecaster window (zeros: stale, must be
    ignored) — tracks the sort-based oracle across warm-started duals."""
    rng = np.random.default_rng(seed)
    n, m, k = 256, 16, 4
    s = _scores(rng, n, m, skew=1.5)
    q0 = jnp.asarray(rng.uniform(0, 0.1, (m,)).astype(np.float32))
    q_ref, p_ref = bip_dual_update(s, q0, top_k=k, n_iters=t)
    zeros = jnp.zeros((m,), jnp.float32)
    q_g, p_g = bip_dual_update_global(
        s, q0, top_k=k, n_iters=t, n_bisect=26, fanout=fanout,
        score_bounds=(0.0, 1.0), window=(zeros, zeros),
    )
    np.testing.assert_allclose(np.asarray(q_g), np.asarray(q_ref), atol=3e-5)
    np.testing.assert_allclose(np.asarray(p_g), np.asarray(p_ref), atol=3e-5)


@given(
    seed=st.integers(0, 2**31 - 1),
    fanout=st.sampled_from([4, 32]),
    frac=st.floats(0.2, 0.9),
)
@settings(max_examples=15, deadline=None)
def test_masked_dual_update_fanout_matches_dense_subset(seed, fanout, frac):
    """Fused fanout composes with the token mask (the serving path): the
    masked update at fanout>1 still equals the sort-based update over just
    the real rows."""
    rng = np.random.default_rng(seed)
    n, m, k = 192, 8, 2
    s = _scores(rng, n, m, skew=1.0)
    q0 = jnp.asarray(rng.uniform(0, 0.2, (m,)).astype(np.float32))
    mask = rng.random(n) < frac
    mask[0] = True
    q_m, _ = bip_dual_update_global(
        s, q0, top_k=k, n_iters=2, token_mask=jnp.asarray(mask), n_bisect=26,
        fanout=fanout,
    )
    q_dense, _ = bip_dual_update(
        jnp.asarray(np.asarray(s)[mask]), q0, top_k=k, n_iters=2
    )
    np.testing.assert_allclose(np.asarray(q_m), np.asarray(q_dense), atol=3e-5)


def test_global_dual_with_stats_returns_preclamp_statistic():
    """with_stats=True returns the pre-clamp order statistic t consistent
    with q = max(0, t), and leaves the (q, p) values unchanged — the
    forecaster EMA update in route() relies on both."""
    rng = np.random.default_rng(13)
    n, m, k = 256, 16, 4
    s = _scores(rng, n, m, skew=1.5)
    q0 = jnp.zeros((m,))
    q2, p2 = bip_dual_update_global(s, q0, top_k=k, n_iters=4, fanout=32,
                                    score_bounds=(0.0, 1.0))
    q3, p3, t3 = bip_dual_update_global(s, q0, top_k=k, n_iters=4, fanout=32,
                                        score_bounds=(0.0, 1.0), with_stats=True)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q3))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(p3))
    np.testing.assert_array_equal(
        np.asarray(q3), np.maximum(0.0, np.asarray(t3))
    )


def test_forecast_route_state_evolves_and_preserves_duals():
    """route(sync='global', forecast=True) must carry 'q_ema'/'q_err' in
    its state, update them every call, and leave the dual trajectory
    within bisection resolution of the forecast-off path."""
    rng = np.random.default_rng(14)
    n, m, k = 256, 8, 2
    cfg_on = RouterConfig(n_experts=m, top_k=k, strategy="bip", bip_iters=4,
                          sync="global", forecast=True)
    cfg_off = RouterConfig(n_experts=m, top_k=k, strategy="bip", bip_iters=4,
                           sync="global")
    st_on, st_off = init_router_state(cfg_on), init_router_state(cfg_off)
    assert set(st_on) == {"q", "q_ema", "q_err"}
    for step in range(5):
        logits = jnp.asarray(
            (rng.standard_normal((n, m))
             + 1.5 * np.linspace(2, -2, m)[None, :]).astype(np.float32))
        st_on = route(logits, st_on, cfg_on).state
        st_off = route(logits, st_off, cfg_off).state
        np.testing.assert_allclose(
            np.asarray(st_on["q"]), np.asarray(st_off["q"]), atol=1e-6,
            err_msg=f"step {step}: forecast warm-start perturbed the dual")
    assert float(jnp.abs(st_on["q_ema"]).max()) > 0.0
    assert float(jnp.abs(st_on["q_err"]).max()) > 0.0


def test_global_dual_update_single_shard_matches_sort_oracle():
    """bip_dual_update_global with axis_names=() and no mask reproduces the
    independent sort-based oracle up to bisection resolution (the
    sync='global' route branch relies on this for the unsharded reference
    trajectory; bip_dual_update_threshold is an alias of the global
    implementation, so the oracle is the only independent check)."""
    rng = np.random.default_rng(11)
    n, m, k = 256, 16, 4
    s = _scores(rng, n, m, skew=1.5)
    q0 = jnp.asarray(rng.uniform(0, 0.1, (m,)).astype(np.float32))
    q_g, p_g = bip_dual_update_global(s, q0, top_k=k, n_iters=4, n_bisect=40)
    q_s, p_s = bip_dual_update(s, q0, top_k=k, n_iters=4)
    np.testing.assert_allclose(np.asarray(q_g), np.asarray(q_s), atol=3e-5)
    np.testing.assert_allclose(np.asarray(p_g), np.asarray(p_s), atol=3e-5)


def test_route_global_sync_single_device_matches_threshold_duals():
    """route(sync='global') off-mesh must carry the threshold-solver duals
    (not the sort-based ones): the warm-start state equals a direct
    bip_dual_update_global call on the same scores."""
    rng = np.random.default_rng(12)
    n, m, k = 256, 8, 2
    cfg = RouterConfig(n_experts=m, top_k=k, strategy="bip", bip_iters=4,
                       sync="global")
    logits = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    out = route(logits, init_router_state(cfg), cfg)
    s = jax.nn.softmax(logits, axis=-1)
    q_direct, _ = bip_dual_update_global(s, jnp.zeros((m,)), top_k=k, n_iters=4)
    np.testing.assert_allclose(
        np.asarray(out.state["q"]), np.asarray(q_direct), atol=1e-7
    )
    assert float(out.metrics["max_vio"]) < 0.3


def test_objective_near_lp_optimum():
    """BIP-routed assignment objective should approach the LP upper bound and
    beat the greedy balanced heuristic."""
    rng = np.random.default_rng(1)
    n, m, k = 128, 8, 2
    s = np.asarray(_scores(rng, n, m, skew=1.5))
    _, lp_opt = solve_plp(s, k)
    _, idx, _ = bip_route_reference(jnp.asarray(s), jnp.zeros((m,)), top_k=k, n_iters=8)
    obj = routing_objective(s, np.asarray(idx))
    greedy = greedy_balanced_objective(s, k)
    vio = float(balance_metrics(idx, m, k)["max_vio"])
    # The ADMM routing is only approximately capacity-feasible (MaxVio > 0),
    # so its objective may exceed the LP optimum by at most the mass of the
    # overflow tokens; it must sit in a tight band around the LP optimum and
    # beat the greedy balanced heuristic.
    assert vio < 0.2, vio
    assert 0.93 * lp_opt <= obj <= (1.0 + vio) * lp_opt, (obj, lp_opt, vio)
    assert obj >= 0.98 * greedy, (obj, greedy)


def test_warm_start_persists_and_improves_first_step():
    """Paper's headline: balance from the FIRST batch, and q warm-start keeps
    subsequent batches balanced with tiny T."""
    rng = np.random.default_rng(2)
    n, m, k = 512, 16, 4
    q = jnp.zeros((m,))
    vios = []
    for step in range(8):
        s = _scores(rng, n, m, skew=2.0)
        _, idx, q = bip_route_reference(s, q, top_k=k, n_iters=4)
        vios.append(float(balance_metrics(idx, m, k)["max_vio"]))
    # cold adversarial start needs a couple of batches of warm-up at T=4; the
    # paper's T in {2,4} works because init-time router scores are near-uniform.
    assert max(vios[2:]) < 0.35, vios
    assert np.mean(vios[2:]) < 0.2, vios  # AvgMaxVio-like, steady state


# ------------------------------------------------------------------- router


@pytest.mark.parametrize("strategy", ["topk", "aux_loss", "lossfree", "bip"])
def test_route_api_all_strategies(strategy):
    rng = np.random.default_rng(3)
    n, m, k = 256, 8, 2
    cfg = RouterConfig(n_experts=m, top_k=k, strategy=strategy, bip_iters=4)
    state = init_router_state(cfg)
    logits = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    out = jax.jit(lambda l, s: route(l, s, cfg))(logits, state)
    assert out.combine_weights.shape == (n, k)
    assert out.expert_index.shape == (n, k)
    assert out.expert_index.dtype == jnp.int32
    assert np.all(np.asarray(out.expert_index) >= 0)
    assert np.all(np.asarray(out.expert_index) < m)
    assert np.isfinite(np.asarray(out.combine_weights)).all()
    # expert indices unique per token
    idx = np.asarray(out.expert_index)
    assert all(len(set(r)) == k for r in idx)
    if strategy == "aux_loss":
        assert float(out.aux_loss) > 0.0
    else:
        assert float(out.aux_loss) == 0.0


def test_route_bip_beats_others_on_skew():
    rng = np.random.default_rng(4)
    n, m, k = 512, 16, 4
    logits = jnp.asarray(
        (rng.standard_normal((n, m)) + 2.0 * np.linspace(2, -2, m)[None, :]).astype(
            np.float32
        )
    )
    vios = {}
    for strat in ["topk", "aux_loss", "lossfree", "bip"]:
        cfg = RouterConfig(n_experts=m, top_k=k, strategy=strat, bip_iters=8)
        out = route(logits, init_router_state(cfg), cfg)
        vios[strat] = float(out.metrics["max_vio"])
    assert vios["bip"] < 0.25
    assert vios["bip"] < vios["topk"]
    assert vios["bip"] < vios["aux_loss"]  # on the FIRST batch
    assert vios["bip"] < vios["lossfree"]  # lossfree needs many batches


def test_route_local_shards_mode():
    rng = np.random.default_rng(5)
    n, m, k = 512, 8, 2
    cfg = RouterConfig(n_experts=m, top_k=k, strategy="bip", bip_iters=8, sync="local")
    logits = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    out = route(logits, init_router_state(cfg), cfg, local_shards=4)
    assert float(out.metrics["max_vio"]) < 0.3
    assert out.state["q"].shape == (m,)


def test_gradients_flow_only_through_scores():
    """d(loss)/d(logits) must exist and be finite; q must be stop-gradient."""
    rng = np.random.default_rng(6)
    n, m, k = 64, 8, 2
    cfg = RouterConfig(n_experts=m, top_k=k, strategy="bip", bip_iters=2)
    logits = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))

    def loss(l):
        out = route(l, init_router_state(cfg), cfg)
        return jnp.sum(out.combine_weights ** 2)

    g = jax.grad(loss)(logits)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0.0
