"""Production mesh construction. A FUNCTION, not a module-level constant:
importing this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A device mesh whose axes are all ``Auto``: the rules path places
    arrays with explicit NamedShardings and the engine's programs run
    under ``shard_map``, neither of which takes ``Explicit`` axes (the
    default of ``jax.make_mesh``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods of
    16x16 = 512 chips (pod, data, model); the pod axis multiplies data
    parallelism and is the axis the dry-run proves out for cross-pod
    (DCN-class) collectives."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e-class hardware constants used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link (~per-direction)
    "hbm_bytes": 16e9,           # capacity per chip
}
