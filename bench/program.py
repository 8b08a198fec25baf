"""The system under test, as the cells build it from their files.

Everything here calls the program's public entry points; the
configuration file's published keys map onto the program's
``ModelConfig`` fields one to one.
"""
from __future__ import annotations

from typing import Dict

from bench import model_ref
from bench.harness import BenchError

# published config.json key -> the program's ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype"}


def model_api(config: Dict):
    """The program's model for a configuration file, with a check that
    its parameters have the layout and shapes the seeded weights take."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.models.registry import get_api
    kw = {f: config[k] for k, f in FIELDS.items()}
    cfg = ModelConfig(name=config["name"], family="dense",
                      head_dim=model_ref.model_sizes(config)["hd"], **kw)
    api = get_api(cfg)
    got = jax.tree_util.tree_map(lambda s: tuple(s.shape), api.param_spec())
    want = model_ref.param_shapes(config)
    if got != want:
        raise BenchError(f"the program's parameters {got} differ from "
                         f"the layout the benchmark makes {want}")
    return api
