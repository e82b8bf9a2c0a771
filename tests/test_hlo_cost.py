"""Loop-aware HLO cost model: validated against XLA on loop-free programs
and against hand-computed trip-count math on scanned programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import (
    analyze,
    analyze_compiled,
    parse_hlo,
    xla_cost_analysis,
)


def _compile(fn, *specs, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*specs).compile()


def test_matches_xla_on_loopfree_matmul():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compile(lambda x: x @ x, a)
    got = analyze_compiled(c)
    want = xla_cost_analysis(c)["flops"]
    assert abs(got.flops - want) / want < 1e-6


def test_scan_trip_count_multiplies():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def scanned(x):
        def body(c, _):
            return c @ x, None

        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    c = _compile(scanned, a)
    got = analyze_compiled(c)
    per_mm = 2 * 256 * 256 * 256
    np.testing.assert_allclose(got.flops, 7 * per_mm, rtol=1e-6)


def test_nested_scan_trip_counts():
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def nested(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ x, None

            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    c = _compile(nested, a)
    got = analyze_compiled(c)
    per_mm = 2 * 128 * 128 * 128
    np.testing.assert_allclose(got.flops, 15 * per_mm, rtol=1e-6)


def test_collectives_counted_with_trip_counts():
    import os
    import subprocess
    import sys

    # needs >1 device: run in a subprocess with forced host devices
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, "src")
from repro.launch.hlo_cost import analyze_compiled
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4,), ("m",), axis_types=(jax.sharding.AxisType.Auto,))
s = NamedSharding(mesh, P("m", None))
a = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=s)

def f(x):
    def body(c, _):
        return c + jax.lax.with_sharding_constraint(
            jnp.broadcast_to(jnp.sum(x), x.shape), s), None
    y, _ = jax.lax.scan(body, x, None, length=6)
    return y

c = jax.jit(f, in_shardings=s, out_shardings=s).lower(a).compile()
cost = analyze_compiled(c)
assert cost.collective_total > 0, cost.collectives
# the sum's all-reduce sits inside the 6-trip loop OR is hoisted; either
# way the analysis must produce a finite positive count
print("OK", cost.collective_total)
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_traffic_includes_dot_operands():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = _compile(lambda x: x @ x, a)
    got = analyze_compiled(c)
    # >= result + 2 reads of the operand (one buffer, read twice): 3 MB
    assert got.traffic >= 3 * 512 * 512 * 4


def test_parse_hlo_structure():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = _compile(lambda x: jnp.tanh(x @ x), a)
    comps = parse_hlo(c.as_text())
    assert "__entry__" in comps
    all_ops = [op.opcode for comp in comps.values() for op in comp.ops]
    assert "dot" in all_ops or any("fusion" in o for o in all_ops)
