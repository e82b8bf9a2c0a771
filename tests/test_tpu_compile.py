"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: each case lowers a kernel entry point at the
paper's real widths with Mosaic (interpret=False) for a v5e topology that is
described, not attached, and asserts the compiled program holds the kernel
(`tpu_custom_call`). This catches what the CPU interpreter cannot: layouts
Mosaic refuses, scoped-VMEM overruns, unpartitionable kernels.

The topology is described inside a module-scoped fixture — never while a
module is imported — because only one process at a time may load the TPU
library, and the persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import bip_admm, moe_gemm, ops

# (experts, capacity, d_model, d_ff): minimind-moe-16e / -64e expert widths at
# the capacity of one 8192-token sequence (capacity_factor 1.25), and a 16e
# decode step, whose capacity ops.expert_ffn pads to 128. The kernels take the
# tiles moe_gemm.pick_tiles gives each shape, so a picked tile that overruns
# Mosaic's scoped VMEM fails here.
FFN_WIDTHS = {
    "16e": (16, 2560, 512, 1408),
    "64e": (64, 1280, 512, 1408),
    "16e_decode": (16, 128, 512, 1408),
}
# (experts, top_k, tokens of one 2 x 8192 training step)
DUAL_WIDTHS = {"16e": (16, 4, 16384), "64e": (64, 8, 16384)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("width", sorted(FFN_WIDTHS))
def test_grouped_gated_ffn_in_compiles(one_chip, width):
    e, c, d, f = FFN_WIDTHS[width]
    fn = lambda x, wg, wu: moe_gemm.grouped_gated_ffn_in(x, wg, wu, interpret=False)
    assert _has_kernel(
        fn, _sds((e, c, d), one_chip), _sds((e, d, f), one_chip), _sds((e, d, f), one_chip)
    )


@pytest.mark.parametrize("width", sorted(FFN_WIDTHS))
def test_grouped_matmul_compiles(one_chip, width):
    e, c, d, f = FFN_WIDTHS[width]
    fn = lambda h, w: moe_gemm.grouped_matmul(h, w, interpret=False)
    assert _has_kernel(fn, _sds((e, c, f), one_chip), _sds((e, f, d), one_chip))


@pytest.mark.parametrize("width", sorted(FFN_WIDTHS))
def test_grouped_matmul_transposed_lhs_compiles(one_chip, width):
    """The wgrad form h^T @ dy, reading h as stored."""
    e, c, d, f = FFN_WIDTHS[width]
    fn = lambda h, dy: moe_gemm.grouped_matmul(h, dy, trans_a=True, interpret=False)
    assert _has_kernel(fn, _sds((e, c, f), one_chip), _sds((e, c, d), one_chip))


@pytest.mark.parametrize("width", sorted(FFN_WIDTHS))
@pytest.mark.parametrize("pass_", ["forward", "grad"])
def test_ops_expert_ffn_compiles(one_chip, width, pass_):
    e, c, d, f = FFN_WIDTHS[width]
    args = (
        _sds((e, c, d), one_chip),
        _sds((e, d, f), one_chip),
        _sds((e, d, f), one_chip),
        _sds((e, f, d), one_chip),
    )
    ffn = lambda *a: ops.expert_ffn(*a, interpret=False)
    if pass_ == "grad":
        fn = jax.grad(
            lambda *a: jnp.sum(ffn(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3)
        )
    else:
        fn = ffn
    assert _has_kernel(fn, *args)


@pytest.mark.parametrize("width", sorted(DUAL_WIDTHS))
def test_bip_admm_iteration_compiles(one_chip, width):
    m, k, n = DUAL_WIDTHS[width]
    fn = lambda s, q: bip_admm.bip_admm_iteration(s, q, top_k=k, interpret=False)
    assert _has_kernel(
        fn, _sds((n, m), one_chip, jnp.float32), _sds((m,), one_chip, jnp.float32)
    )


@pytest.mark.parametrize("width", sorted(DUAL_WIDTHS))
@pytest.mark.parametrize("masked", [False, True])
def test_bip_dual_update_compiles(one_chip, width, masked):
    m, k, n = DUAL_WIDTHS[width]
    args = [_sds((n, m), one_chip, jnp.float32), _sds((m,), one_chip, jnp.float32)]
    if masked:
        args.append(_sds((n,), one_chip, jnp.bool_))
    fn = lambda s, q, *mask: ops.bip_dual_update(
        s, q, top_k=k, n_iters=4, interpret=False,
        token_mask=mask[0] if mask else None,
    )
    assert _has_kernel(fn, *args)


def test_bip_dual_update_collective_compiles_on_mesh(topo):
    """The sync='global' form inside a vma-checked shard_map over 4 chips:
    the per-shard kernel plus psum'd histogram counts."""
    m, k, n = DUAL_WIDTHS["64e"]
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    fn = jax.shard_map(
        lambda s, q: ops.bip_dual_update(
            s, q, top_k=k, n_iters=4, interpret=False, axis_names=("data",)
        ),
        mesh=mesh,
        in_specs=(P("data", None), P(None)),
        out_specs=P(None),
    )
    s = jax.ShapeDtypeStruct((n, m), jnp.float32, sharding=NamedSharding(mesh, P("data", None)))
    q = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=NamedSharding(mesh, P(None)))
    text = jax.jit(fn).lower(s, q).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


@pytest.mark.parametrize("pass_", ["forward", "grad"])
def test_ops_expert_ffn_compiles_expert_parallel(topo, pass_):
    """ops.expert_ffn inside a vma-checked shard_map with the experts over a
    4-chip 'model' axis, as the EP paths call it (use_kernel=True): the
    dispatched tokens arrive replicated, the weights vary over 'model'."""
    e, c, d, f = FFN_WIDTHS["64e"]
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    ffn = lambda *a: ops.expert_ffn(*a, interpret=False)
    if pass_ == "grad":
        body = jax.grad(
            lambda *a: jnp.sum(ffn(*a).astype(jnp.float32)), argnums=(1, 2, 3)
        )
        out_specs = (P("model"),) * 3
    else:
        body, out_specs = ffn, P("model")
    fn = jax.shard_map(
        lambda x, *w: body(x[: e // 4], *w),  # this shard's experts' tokens
        mesh=mesh,
        in_specs=(P(),) + (P("model"),) * 3,
        out_specs=out_specs,
    )
    sh = lambda spec: NamedSharding(mesh, spec)
    args = (
        _sds((e, c, d), sh(P())),
        _sds((e, d, f), sh(P("model"))),
        _sds((e, d, f), sh(P("model"))),
        _sds((e, f, d), sh(P("model"))),
    )
    assert _has_kernel(fn, *args)
