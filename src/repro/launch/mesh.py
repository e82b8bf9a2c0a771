"""Production mesh definitions (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization and only
then calls make_production_mesh().
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips (one v5e pod) or 2x16x16 = 512 chips (two pods).

    Axes: 'pod' spans the inter-pod DCN/ICI boundary, 'data' carries batch
    (+ FSDP weight shards), 'model' carries tensor/expert parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model places activations with with_sharding_constraint,
    # which refuses the Explicit axes jax.make_mesh defaults to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    bf16_flops: float  # FLOP/s
    hbm_bw: float      # bytes/s
    ici_bw: float      # bytes/s per link, per direction
    source: str


# keyed by jax.Device.device_kind
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peaks_for(device_kind: str) -> DevicePeaks:
    """Peaks of `device_kind`; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
