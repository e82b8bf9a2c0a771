"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import balance_metrics, bip_topk
from repro.core.ref_bip import bip_dual_update as exact_dual
from repro.kernels import bip_admm, moe_gemm, ops, ref


def _scores(seed, n, m, skew=1.0):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((n, m)) + skew * np.linspace(2, -2, m)[None, :]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    return jnp.asarray((e / e.sum(-1, keepdims=True)).astype(np.float32))


# ------------------------------------------------------------- BIP kernel


@pytest.mark.parametrize("n,m,k", [(256, 8, 2), (512, 16, 4), (300, 4, 1), (1024, 64, 8)])
def test_bip_iteration_p_matches_exact(n, m, k):
    """The kernel's row-price p must match the exact (k+1)-th largest."""
    s = _scores(0, n, m)
    q = jnp.asarray(np.random.default_rng(1).uniform(0, 0.3, (m,)), jnp.float32)
    p_kern, cnt = bip_admm.bip_admm_iteration(s, q, top_k=k)
    p_ref = ref.bip_iteration_ref(s, q, top_k=k)
    np.testing.assert_allclose(np.asarray(p_kern), np.asarray(p_ref), atol=1e-6)
    # histogram counts match the oracle
    cnt_ref = ref.histogram_counts_ref(s, p_ref, n_bins=512)
    np.testing.assert_allclose(np.asarray(cnt), np.asarray(cnt_ref), atol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bip_iteration_dtype_sweep(dtype):
    s = _scores(2, 384, 16).astype(dtype)
    q = jnp.zeros((16,), jnp.float32)
    p_kern, cnt = bip_admm.bip_admm_iteration(s, q, top_k=4)
    p_ref = ref.bip_iteration_ref(s.astype(jnp.float32), q, top_k=4)
    np.testing.assert_allclose(np.asarray(p_kern), np.asarray(p_ref), atol=5e-3)


@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.sampled_from([128, 257, 512, 1000]),
    m=st.sampled_from([4, 8, 16, 64]),
    k=st.sampled_from([1, 2, 4]),
    t=st.sampled_from([2, 4]),
)
@settings(max_examples=12, deadline=None)
def test_bip_dual_update_kernel_close_to_exact(seed, n, m, k, t):
    """Full T-iteration kernel q vs exact oracle: within histogram resolution,
    and — the property that actually matters — the resulting ROUTING is as
    balanced as the exact router's."""
    k = min(k, m)
    s = _scores(seed, n, m, skew=1.5)
    q0 = jnp.zeros((m,), jnp.float32)
    q_kern = ops.bip_dual_update(s, q0, top_k=k, n_iters=t)
    q_ref, _ = exact_dual(s, q0, top_k=k, n_iters=t)
    np.testing.assert_allclose(
        np.asarray(q_kern), np.asarray(q_ref), atol=2.0 / 512 + 5e-3
    )
    _, idx_k = bip_topk(s, q_kern, k)
    _, idx_r = bip_topk(s, q_ref, k)
    vio_k = float(balance_metrics(idx_k, m, k)["max_vio"])
    vio_r = float(balance_metrics(idx_r, m, k)["max_vio"])
    # cold starts at tiny T can leave both unbalanced; the kernel must simply
    # track the oracle's balance, not beat it.
    assert vio_k <= 1.3 * vio_r + 0.3, (vio_k, vio_r)


@pytest.mark.parametrize("n,m,k", [(512, 16, 4), (1000, 64, 8)])
def test_bip_kernel_masked_matches_exact_masked(n, m, k):
    """The kernel's serving form: masked rows are invisible (bitwise the
    kernel on the real rows alone — the histogram counts are the same exact
    integers), q tracks the exact masked dual within histogram resolution,
    and an all-padding call leaves q unchanged."""
    s = _scores(6, n, m, skew=1.5)
    mask = np.random.default_rng(7).random(n) < 0.4
    q0 = jnp.zeros((m,), jnp.float32)
    q_k = ops.bip_dual_update(s, q0, top_k=k, n_iters=4, token_mask=jnp.asarray(mask))
    q_alone = ops.bip_dual_update(s[np.flatnonzero(mask)], q0, top_k=k, n_iters=4)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_alone))
    q_r, _ = exact_dual(s, q0, top_k=k, n_iters=4, token_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r), atol=2.0 / 512 + 5e-3)
    q_idle = ops.bip_dual_update(s, q_r, top_k=k, n_iters=4, token_mask=jnp.zeros((n,), bool))
    np.testing.assert_array_equal(np.asarray(q_idle), np.asarray(q_r))


def test_bip_kernel_in_router_end_to_end():
    """RouterConfig(use_kernel=True) routes as balanced as the oracle path."""
    from repro.core import RouterConfig, init_router_state, route

    s_logits = jnp.asarray(
        np.random.default_rng(3).standard_normal((512, 16)).astype(np.float32)
        + 1.5 * np.linspace(2, -2, 16)[None, :]
    )
    cfg_k = RouterConfig(n_experts=16, top_k=4, strategy="bip", bip_iters=8, use_kernel=True)
    cfg_r = RouterConfig(n_experts=16, top_k=4, strategy="bip", bip_iters=8)
    out_k = route(s_logits, init_router_state(cfg_k), cfg_k)
    out_r = route(s_logits, init_router_state(cfg_r), cfg_r)
    assert float(out_k.metrics["max_vio"]) < 0.3
    assert abs(float(out_k.metrics["max_vio"]) - float(out_r.metrics["max_vio"])) < 0.2


def test_route_global_kernel_single_device_matches_kernel_dual():
    """route(use_kernel=True, sync='global') off-mesh carries the kernel's
    duals (the collective branch with axis_names=()), not the threshold
    solver's."""
    from repro.core import RouterConfig, init_router_state, route

    logits = jnp.asarray(
        np.random.default_rng(5).standard_normal((512, 16)).astype(np.float32)
        + 1.5 * np.linspace(2, -2, 16)[None, :]
    )
    cfg = RouterConfig(
        n_experts=16, top_k=4, strategy="bip", bip_iters=4,
        sync="global", use_kernel=True,
    )
    out = route(logits, init_router_state(cfg), cfg)
    s = jax.nn.softmax(logits, axis=-1)
    q_direct = ops.bip_dual_update(
        jax.lax.stop_gradient(s), jnp.zeros((16,)), top_k=4, n_iters=4
    )
    np.testing.assert_allclose(
        np.asarray(out.state["q"]), np.asarray(q_direct), atol=1e-7
    )


def test_bip_kernel_collective_matches_reference_on_mesh():
    """Collective kernel (psum'd histogram counts) on a forced 4x2 mesh:
    q must be BITWISE equal to the single-device kernel on the gathered
    batch (the global histogram is identical — small exact integers), and
    within histogram resolution of the reference global dual."""
    from _forced_devices import PRELUDE, run_code as _run

    _run(PRELUDE + r"""
from repro.core.ref_bip import bip_dual_update_global
from repro.kernels import ops

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))

for n, m, k, t in ((512, 16, 4, 4), (1024, 64, 8, 2)):
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((n, m)) + 1.5 * np.linspace(2, -2, m)[None, :]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    s = jnp.asarray((e / e.sum(-1, keepdims=True)).astype(np.float32))
    q0 = jnp.zeros((m,), jnp.float32)

    def collective(s_loc, q, k=k, t=t):
        return ops.bip_dual_update(s_loc, q, top_k=k, n_iters=t,
                                   axis_names=("data",))

    fn = jax.shard_map(collective, mesh=mesh,
                       in_specs=(P("data", None), P(None)), out_specs=P(None))
    with mesh:
        q_mesh = np.asarray(jax.device_get(jax.jit(fn)(s, q0)))

    q_single = np.asarray(ops.bip_dual_update(s, q0, top_k=k, n_iters=t))
    np.testing.assert_array_equal(q_mesh, q_single,
                                  err_msg=f"m={m}: mesh vs single kernel")

    q_ref, _ = bip_dual_update_global(s, q0, top_k=k, n_iters=t, n_bisect=40)
    np.testing.assert_allclose(q_mesh, np.asarray(q_ref), atol=2.0 / 512 + 5e-3,
                               err_msg=f"m={m}: mesh kernel vs reference")
print("OK")
""")


def test_bip_kernel_capacity_slack():
    """k >= m: the token constraint selects everything and the capacity
    index runs past the column length -> q stays zero (true slack)."""
    s = _scores(4, 8, 4)
    q = ops.bip_dual_update(s, jnp.zeros((4,)), top_k=4, n_iters=4)
    np.testing.assert_array_equal(np.asarray(q), 0.0)


def test_bip_kernel_fractional_capacity_matches_exact():
    """n*k < m (fractional capacity < 1): kernel must track the exact dual,
    which puts q at the column max (rank 0) — not zero."""
    s = _scores(4, 8, 16)
    q_k = ops.bip_dual_update(s, jnp.zeros((16,)), top_k=1, n_iters=4)
    q_r, _ = exact_dual(s, jnp.zeros((16,)), top_k=1, n_iters=4)
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_r), atol=1e-4)


# ----------------------------------------------------------- MoE GEMMs


@pytest.mark.parametrize(
    "e,c,d,f", [(4, 128, 64, 128), (2, 256, 128, 256), (8, 128, 32, 64)]
)
def test_grouped_gated_ffn_in_allclose(e, c, d, f):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((e, c, d)).astype(np.float32)) * 0.3
    wg = jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1
    wu = jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1
    got = moe_gemm.grouped_gated_ffn_in(x, wg, wu, block_c=64, block_f=64, block_d=32)
    want = ref.gated_ffn_in_ref(x, wg, wu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize(
    "e,c,f,d", [(4, 128, 64, 128), (2, 64, 128, 64)]
)
def test_grouped_matmul_allclose(e, c, f, d):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((e, c, f)).astype(np.float32)) * 0.3
    w = jnp.asarray(rng.standard_normal((e, f, d)).astype(np.float32)) * 0.1
    got = moe_gemm.grouped_matmul(h, w, block_c=64, block_d=64, block_f=32)
    want = ref.grouped_matmul_ref(h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize(
    "e,c,d,f",
    [(3, 40, 96, 200), (2, 128, 64, 128), (1, 1, 32, 48), (4, 130, 50, 260)],
)
def test_ops_expert_ffn_autopad_allclose(e, c, d, f):
    """ops.expert_ffn pads arbitrary (c, d, f) to MXU-aligned multiples,
    runs the kernel pair, and slices back — zero padding must be exact."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((e, c, d)).astype(np.float32)) * 0.3
    wg = jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1
    wu = jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1
    wd = jnp.asarray(rng.standard_normal((e, f, d)).astype(np.float32)) * 0.1
    got = ops.expert_ffn(x, wg, wu, wd)
    want = ref.expert_ffn_ref(x, wg, wu, wd)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_ops_expert_ffn_custom_vjp_grads_match_einsum():
    """The custom_vjp backward (grouped dgrad/wgrad GEMMs) must match einsum
    autodiff to fp32 tolerance for every operand."""
    rng = np.random.default_rng(8)
    e, c, d, f = 3, 40, 96, 200
    args = (
        jnp.asarray(rng.standard_normal((e, c, d)).astype(np.float32)) * 0.3,
        jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1,
        jnp.asarray(rng.standard_normal((e, d, f)).astype(np.float32)) * 0.1,
        jnp.asarray(rng.standard_normal((e, f, d)).astype(np.float32)) * 0.1,
    )
    g_k = jax.grad(lambda *a: jnp.sum(jnp.sin(ops.expert_ffn(*a))), argnums=(0, 1, 2, 3))(*args)
    g_r = jax.grad(lambda *a: jnp.sum(jnp.sin(ref.expert_ffn_ref(*a))), argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 3e-2)])
def test_expert_ffn_dtype_sweep(dtype, atol):
    rng = np.random.default_rng(2)
    e, c, d, f = 2, 128, 64, 128
    x = jnp.asarray(rng.standard_normal((e, c, d)), dtype) * 0.3
    wg = jnp.asarray(rng.standard_normal((e, d, f)), dtype) * 0.1
    wu = jnp.asarray(rng.standard_normal((e, d, f)), dtype) * 0.1
    wd = jnp.asarray(rng.standard_normal((e, f, d)), dtype) * 0.1
    got = moe_gemm.expert_ffn(x, wg, wu, wd, block_c=64, block_f=64, block_d=32)
    want = ref.expert_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )
