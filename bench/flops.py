"""Operations and bytes the algorithms need, from shapes alone.

Counts are of the work the model requires, not of what the program
happens to compute: nothing recomputed, no padding, causal attention
counted over the positions each query may see.
"""
from __future__ import annotations

from typing import Dict

from bench.model_ref import model_sizes


def param_count(config: Dict) -> int:
    """Every parameter once; a tied embedding counts once."""
    s = model_sizes(config)
    D, F, V, L = s["D"], s["F"], s["V"], s["L"]
    q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
    per_layer = D * q + 2 * D * kv + q * D + 3 * D * F + 2 * D
    return L * per_layer + V * D * (1 if s["tied"] else 2) + D


def matmul_params(config: Dict) -> int:
    """Parameters that multiply each token's activations: every
    projection and the output head (the embedding lookup multiplies
    nothing; a tied embedding is the head)."""
    s = model_sizes(config)
    D, F, V, L = s["D"], s["F"], s["V"], s["L"]
    q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
    return L * (D * q + 2 * D * kv + q * D + 3 * D * F) + V * D


def train_flops_per_token(config: Dict, seq: int) -> float:
    """Forward and backward of one token of a causal sequence of
    ``seq``: 6 x the parameters (the tied embedding once, as the output
    matmul), plus attention's scores and values, 6 * L * S * q_dim on
    average over the causal positions (2 * 2 * S/2 per head dimension,
    forward, times 3)."""
    s = model_sizes(config)
    attn = 6 * s["L"] * seq * s["H"] * s["hd"]
    return 6.0 * param_count(config) + attn


def decode_step(config: Dict, live_slots: int, live_positions: int,
                weight_bytes: int = 2, kv_bytes: int = 2) -> Dict:
    """Least work of one decode step over ``live_slots`` sequences whose
    caches hold ``live_positions`` positions in all: the weights read
    once, the keys and values of the live positions read once."""
    s = model_sizes(config)
    q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
    flops = (2.0 * matmul_params(config) * live_slots
             + 4.0 * s["L"] * q * live_positions)
    by = (param_count(config) * weight_bytes
          + 2.0 * s["L"] * kv * live_positions * kv_bytes)
    return {"flops": flops, "bytes": by}


def bucket_combine_bytes(rows: int, bucket_elems: int,
                         itemsize: int = 4) -> int:
    """One launch reads the accumulator and the incoming buffer and
    writes the result."""
    return 3 * rows * bucket_elems * itemsize


def least_time(flops: float, nbytes: float, peaks: Dict) -> Dict:
    tf = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return {"s": max(tf, tb), "bound": "flops" if tf >= tb else "bytes"}
