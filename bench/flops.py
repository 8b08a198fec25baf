"""Operations and bytes the algorithms need, from shapes alone.

Counts are of the work the model requires, not of what the program
happens to compute: nothing recomputed, no padding, causal attention
counted over the positions each query may see. A model's counts come
from its family module (``bench/families/<family>.py``); a family whose
decode reads recurrent state counts that state's bytes in
``decode_step``.
"""
from __future__ import annotations

from typing import Dict

from bench.harness import family


def param_count(config: Dict) -> int:
    """Every parameter once; a tied embedding counts once."""
    return family(config).param_count(config)


def matmul_params(config: Dict) -> int:
    """Parameters that multiply each token's activations."""
    return family(config).matmul_params(config)


def train_flops_per_token(config: Dict, seq: int) -> float:
    """Model FLOPs of the forward and backward of one token of a causal
    sequence of ``seq`` tokens, nothing recomputed."""
    return family(config).train_flops_per_token(config, seq)


def decode_step(config: Dict, live_slots: int, live_positions: int,
                weight_bytes: int = 2, kv_bytes: int = 2) -> Dict:
    """Least work of one decode step over ``live_slots`` sequences whose
    caches hold ``live_positions`` positions in all: ``{"flops",
    "bytes"}``."""
    return family(config).decode_step(config, live_slots, live_positions,
                                      weight_bytes, kv_bytes)


def bucket_combine_bytes(rows: int, bucket_elems: int,
                         itemsize: int = 4) -> int:
    """One launch reads the accumulator and the incoming buffer and
    writes the result."""
    return 3 * rows * bucket_elems * itemsize


def least_time(flops: float, nbytes: float, peaks: Dict) -> Dict:
    tf = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return {"s": max(tf, tb), "bound": "flops" if tf >= tb else "bytes"}
