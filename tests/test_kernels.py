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
    got = moe_gemm.grouped_gated_ffn_in(x, wg, wu, tiles=moe_gemm.Tiles(64, 32, 64))
    want = ref.gated_ffn_in_ref(x, wg, wu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize(
    "e,c,f,d,trans_a",
    [
        pytest.param(4, 128, 64, 128, False, id="4-128-64-128"),
        pytest.param(2, 64, 128, 64, False, id="2-64-128-64"),
        pytest.param(4, 128, 64, 128, True, id="4-128-64-128-trans_a"),
        pytest.param(2, 64, 128, 64, True, id="2-64-128-64-trans_a"),
    ],
)
def test_grouped_matmul_allclose(e, c, f, d, trans_a):
    """Blocks smaller than the shape (a split contraction accumulates over
    grid steps); with trans_a the left operand is read as stored (F, C)."""
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((e, c, f)).astype(np.float32)) * 0.3
    w = jnp.asarray(rng.standard_normal((e, f, d)).astype(np.float32)) * 0.1
    a = jnp.swapaxes(h, 1, 2) if trans_a else h
    got = moe_gemm.grouped_matmul(a, w, trans_a=trans_a, tiles=moe_gemm.Tiles(64, 32, 64))
    want = ref.grouped_matmul_ref(h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# (experts, capacity, d_model, d_ff) the model path runs the kernels at:
# minimind-moe-16e and -64e training, a decode step (capacity padded to 128),
# and the small shapes of the tests here
PICK_SHAPES = {
    "16e": (16, 2560, 512, 1408),
    "64e": (64, 1280, 512, 1408),
    "decode": (16, 128, 512, 1408),
    "small": (3, 40, 96, 200),
    "one_row": (1, 1, 32, 48),
    "f1408_c640": (2, 640, 128, 1408),
}
# each grouped product of ops.expert_ffn as (rows, contraction, cols, right
# operands, transposed left operand) of the padded (c, d, f)
PHASES = {
    "fwd_in": lambda c, d, f: (c, d, f, 2, False),
    "fwd_out": lambda c, d, f: (c, f, d, 1, False),
    "remat": lambda c, d, f: (c, d, f, 1, False),
    "dgrad_dh": lambda c, d, f: (c, d, f, 1, False),
    "dgrad_dx": lambda c, d, f: (c, f, d, 1, False),
    "wgrad_dwd": lambda c, d, f: (f, c, d, 1, True),
    "wgrad_dwg": lambda c, d, f: (d, c, f, 1, True),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("phase", sorted(PHASES))
@pytest.mark.parametrize("shape", sorted(PICK_SHAPES))
def test_pick_tiles_aligned_dividing_and_in_budget(shape, phase, dtype):
    """Every block is a multiple of 128 or its whole dimension, divides the
    padded dimension, and the step fits the budget the picker states."""
    _, c, d, f = PICK_SHAPES[shape]
    pad = lambda v: -(-v // 128) * 128
    m, k, n, n_rhs, trans_a = PHASES[phase](pad(c), pad(d), pad(f))
    t = moe_gemm.pick_tiles(m, k, n, dtype, n_rhs=n_rhs, trans_a=trans_a)
    for block, dim in zip(t, (m, k, n)):
        assert block % 128 == 0 or block == dim, (t, m, k, n)
        assert dim % block == 0, (t, m, k, n)
    itemsize = jnp.dtype(dtype).itemsize
    assert moe_gemm.vmem_bytes(t, k, itemsize, n_rhs, trans_a) <= moe_gemm.VMEM_BUDGET


def test_pick_tiles_takes_whole_dims_at_train_16e():
    """At the 16e training widths a bf16 product takes F = 1408 and the
    D = 512 contraction whole (no 128-wide fallback, no accumulator loop),
    so each forward kernel runs a few dozen grid steps, not thousands."""
    t = moe_gemm.pick_tiles(2560, 512, 1408, jnp.bfloat16, n_rhs=2)
    assert (t.bk, t.bn) == (512, 1408)
    assert 16 * (2560 // t.bm) <= 64
    t = moe_gemm.pick_tiles(2560, 1408, 512, jnp.bfloat16)
    assert (t.bk, t.bn) == (1408, 512)


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


@pytest.mark.parametrize(
    "dtype,shape,atol",
    [
        pytest.param(jnp.float32, (2, 128, 64, 128), 3e-5, id="float32-3e-05"),
        pytest.param(jnp.bfloat16, (2, 128, 64, 128), 3e-2, id="bfloat16-0.03"),
        # F = 11 x 128 and C = 5 x 128: blocks of the whole dimension
        pytest.param(jnp.bfloat16, (2, 640, 128, 1408), 3e-2, id="bfloat16-0.03-f1408-c640"),
    ],
)
def test_expert_ffn_dtype_sweep(dtype, shape, atol):
    """ops.expert_ffn on each operand dtype against the einsum reference
    computed in f32 from the same values: the forward to `atol`, and the
    custom-VJP gradients of every operand to 1e-4 (f32) or 1e-2 (bf16,
    which rounds h, dg and du between products) of each one's largest
    entry."""
    rng = np.random.default_rng(2)
    e, c, d, f = shape
    x = jnp.asarray(rng.standard_normal((e, c, d)), dtype) * 0.3
    wg = jnp.asarray(rng.standard_normal((e, d, f)), dtype) * 0.1
    wu = jnp.asarray(rng.standard_normal((e, d, f)), dtype) * 0.1
    wd = jnp.asarray(rng.standard_normal((e, f, d)), dtype) * 0.1
    got = ops.expert_ffn(x, wg, wu, wd)
    want = ref.expert_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
    g_k = jax.grad(loss(ops.expert_ffn), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    g_r = jax.grad(loss(ref.expert_ffn_ref), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    tol = 1e-4 if dtype == jnp.float32 else 1e-2
    for a, b in zip(g_k, g_r):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max())
