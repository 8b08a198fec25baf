"""The plain float32 reference agrees with the program at a size the CPU
holds: the forward pass with ``models/transformer.py``, and a whole run
of each cell, whose checks then read as good as zero."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model_ref, program
from repro.models import transformer
from bench_tiny import TINY, tiny_cell


def test_forward_matches_the_program_in_float32():
    cfg = dict(tiny_cell("smollm-135m.train").config, torch_dtype="float32")
    api = program.model_api(cfg)
    params = model_ref.make_weights(cfg, 2 ** 32 + 7, jnp.float32)
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        prog, _, _ = jax.jit(lambda p, t: transformer.forward(
            api.cfg, p, t))(params, jnp.asarray(toks, jnp.int32))
    ref = model_ref._seq_logits(params, jnp.asarray(toks[:1], jnp.int32),
                                model_ref._cfg_items(cfg), None)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_weights_depend_on_the_seed_alone():
    cfg = tiny_cell("smollm-135m.train").config
    a = model_ref.make_weights(cfg, 5, jnp.bfloat16)
    b = model_ref.make_weights(cfg, 5, jnp.bfloat16)
    c = model_ref.make_weights(cfg, 6, jnp.bfloat16)
    fa, fb, fc = (model_ref.flat_leaves(x) for x in (a, b, c))
    assert all(bool(jnp.all(fa[k] == fb[k])) for k in fa)
    assert not bool(jnp.all(fa["embed"] == fc["embed"]))
    assert fa["blocks/ln1"].dtype == jnp.bfloat16


@pytest.mark.parametrize("name", ["smollm-135m.train",
                                  "granite-3-2b.serve"])
def test_a_float32_run_agrees_with_the_reference(name):
    cell = tiny_cell(name, "float32")
    line, _ = harness.run_cell(cell, seed=2 ** 31 + 11, seconds=1.0,
                               traced=False, t_start=0.0, require_tpu=False)
    res = json.loads(line)
    assert res["correct"], res
    for k, c in res["checks"].items():
        assert c["value"] <= 1e-5, (k, c)
