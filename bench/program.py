"""The system under test, as the cells build it from their files.

Everything here calls the program's public entry points; the
configuration's family module maps the published keys onto the
program's ``ModelConfig`` fields.
"""
from __future__ import annotations

from typing import Dict

from bench import model_ref
from bench.harness import BenchError, family


def model_api(config: Dict):
    """The program's model for a configuration file, with a check that
    its parameters have the layout and shapes the seeded weights take."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.models.registry import get_api
    cfg = ModelConfig(name=config["name"],
                      **family(config).program_kwargs(config))
    api = get_api(cfg)
    got = jax.tree_util.tree_map(lambda s: tuple(s.shape), api.param_spec())
    want = model_ref.param_shapes(config)
    if got != want:
        raise BenchError(f"the program's parameters {got} differ from "
                         f"the layout the benchmark makes {want}")
    return api
