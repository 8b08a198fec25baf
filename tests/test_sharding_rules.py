"""Sharding-policy tests: every derived spec divides its dim on the
production mesh (the property the dry-run enforces end-to-end), plus the
schedule-equivalence test on host devices via subprocess (device count must
be set before jax init, so it cannot run in this process)."""
import subprocess
import sys

import jax
import pytest

from repro.configs import ALL_ARCHS, SHAPES
from repro.models.registry import get_api, get_config
from repro.sharding.policies import (axis_size, decode_state_specs,
                                     make_rules)
from repro.sharding.rules import param_specs


def mesh_stub():
    """An abstract 16x16 mesh (no devices needed for spec derivation)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_divide(arch):
    mesh = mesh_stub()
    cfg = get_config(arch)
    api = get_api(cfg)
    rules = make_rules(mesh, cfg)
    pspec = api.param_spec()
    specs = param_specs(pspec, rules)
    flat_p = jax.tree_util.tree_leaves_with_path(pspec)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: hasattr(x, "_normalized_spec"))
    from jax.sharding import PartitionSpec as P
    flat_s = jax.tree_util.tree_leaves(specs,
                                       is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            n = axis_size(mesh, ax)
            assert dim % n == 0, (arch, path, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["qwen2-72b", "zamba2-7b", "xlstm-125m",
                                  "mixtral-8x7b", "whisper-small"])
def test_decode_state_specs_divide(arch):
    mesh = mesh_stub()
    cfg = get_config(arch)
    api = get_api(cfg)
    rules = make_rules(mesh, cfg)
    for batch, window in ((128, 32768), (1, 8192)):
        st = api.decode_state_spec(batch, window)
        specs = decode_state_specs(rules, cfg, st, mesh, batch=batch)
        from jax.sharding import PartitionSpec as P
        flat_p = jax.tree_util.tree_leaves(st)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            for dim, ax in zip(leaf.shape,
                               tuple(spec) + (None,) * leaf.ndim):
                if ax is None:
                    continue
                assert dim % axis_size(mesh, ax) == 0, \
                    (arch, batch, leaf.shape, spec)


def test_small_model_dp_over_model_replicates_params():
    mesh = mesh_stub()
    cfg = get_config("smollm-135m")
    rules = make_rules(mesh, cfg, dp_over_model=True)
    assert rules.logical["batch"] == ("data", "model")
    assert rules.logical["heads"] is None
    assert rules.logical["ff"] is None


def test_schedule_equivalence_subprocess():
    """phaser/recursive-doubling/halving-doubling all-reduce == psum on an
    8-device host platform (subprocess: device count is init-locked)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.collective import ALLREDUCE_KINDS, PhaserCollective
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6)
want = jnp.broadcast_to(x.sum(0), (8, 6))
for kind in ALLREDUCE_KINDS:
    pc = PhaserCollective(8, "data", kind=kind)
    f = jax.shard_map(pc.all_reduce, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
    assert jnp.allclose(f(x), want), kind
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                          "PYTHONPATH": "src"},
                         cwd=__import__("os").path.dirname(
                             __import__("os").path.dirname(__file__)),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
