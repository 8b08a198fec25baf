"""Compiles for a described TPU v5e, no chip attached.

The Pallas kernels of the main path are compiled by the TPU compiler at
smollm-135m's real layouts. Tiling and VMEM refusals that interpret mode
cannot show surface here. The topology is described inside a fixture
(never at import), so every test worker collects the same tests and
only the one given this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.collective_exec import make_layout
from repro.kernels.bucket_combine import bucket_combine
from repro.models.registry import get_api, get_config


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache here, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (overlap mode, block_groups): the eager engine combines the full buffer
# in one launch per round; the pipelined one launches per readiness group
@pytest.mark.parametrize("mode,block_groups", [("eager", 1),
                                               ("pipelined", 1),
                                               ("pipelined", 4)])
def test_bucket_combine_compiles_at_smollm_layout(mode, block_groups,
                                                  one_chip,
                                                  no_compile_cache):
    spec = get_api(get_config("smollm-135m")).param_spec()
    lay = make_layout(spec, block_groups=block_groups)
    rows = ({lay.n_buckets} if mode == "eager"
            else set(lay.group_buckets))
    for nb in sorted(rows):
        x = jax.ShapeDtypeStruct((nb, lay.bucket_elems), jnp.float32,
                                 sharding=one_chip)
        gate = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
        for op in ("add", "copy"):
            fn = jax.jit(lambda a, y, g, op=op: bucket_combine(a, y, g,
                                                               op=op))
            hlo = fn.lower(x, x, gate).compile().as_text()
            assert "tpu_custom_call" in hlo, (nb, op)
