"""Where Pallas kernels run: Mosaic on a TPU, the interpreter on the CPU."""
from __future__ import annotations

import jax
from jax import lax


def interpret_default() -> bool:
    """Interpret mode exactly when JAX's default backend is the CPU.

    Every kernel entry point resolves interpret=None through this. On a TPU
    the kernels lower to Mosaic, and a kernel Mosaic refuses is a compile
    error, never a silent switch to the interpreter or to a reference.
    """
    return jax.default_backend() == "cpu"


def varying_operands(*xs):
    """(vma, xs): a kernel's varying mesh axes inside a shard_map, and its
    operands cast up to them.

    A pallas_call's outputs vary over every mesh axis any operand varies
    over; operands that vary over fewer are cast to the same type so the
    kernel body type-checks under check_vma. Outside a shard_map the set is
    empty and the operands pass through unchanged.
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in xs))

    def cast(x):
        missing = tuple(sorted(vma - jax.typeof(x).vma))
        return lax.pcast(x, missing, to="varying") if missing else x

    return vma, tuple(cast(x) for x in xs)
