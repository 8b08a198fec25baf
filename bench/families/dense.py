"""The dense decoder block that SmolLM and Granite share, as the
benchmark builds, checks and counts it.

Llama-style: RMSNorm, rotary positions with the half-split rotation,
grouped-query causal attention, SwiGLU MLP, tied embedding. Everything
here that depends on the block: the published keys as the program's
``ModelConfig`` fields, the parameter layout and the init rule of each
leaf, the plain float32 forward, and the operations and bytes the block
needs. ``bench/model_ref.py`` and ``bench/flops.py`` reach it through
``harness.family``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import model_ref

# published config.json key -> the program's ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype"}


def model_sizes(config: Dict) -> Dict:
    """The sizes of a configuration file under short names."""
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": D, "H": H,
            "KV": config["num_key_value_heads"],
            "hd": config.get("head_dim") or D // H,
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "tied": bool(config["tie_word_embeddings"])}


def program_kwargs(config: Dict) -> Dict:
    """The program's ``ModelConfig`` fields for a configuration file."""
    kw = {f: config[k] for k, f in FIELDS.items()}
    return dict(kw, family="dense", head_dim=model_sizes(config)["hd"])


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def param_shapes(config: Dict) -> Dict:
    """Shapes in the program's layout: per-layer leaves stacked on a
    leading layer axis, projections stored (in, out)."""
    s = model_sizes(config)
    L, D, F, V = s["L"], s["D"], s["F"], s["V"]
    q, kv = s["H"] * s["hd"], s["KV"] * s["hd"]
    shapes = {"embed": (V, D), "final_norm": (D,),
              "blocks": {"ln1": (L, D), "ln2": (L, D),
                         "attn": {"wq": (L, D, q), "wk": (L, D, kv),
                                  "wv": (L, D, kv), "wo": (L, q, D)},
                         "mlp": {"gate": (L, D, F), "up": (L, D, F),
                                 "down": (L, F, D)}}}
    if not s["tied"]:
        shapes["lm_head"] = (V, D)
    return shapes


def init_leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm"):
        return jnp.ones(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    # embedding rows at 0.02; projections at 1/sqrt(fan-in)
    scale = 0.02 if name in ("embed", "lm_head") else 1 / math.sqrt(shape[-2])
    return (x * scale).astype(dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (B, S, H, hd); the two halves of each head rotate together."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, S, hd/2)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(h, lp, pos, sz, ein):
    f32 = lambda a: a.astype(jnp.float32)
    B, S, D = h.shape
    H, KV, hd = sz["H"], sz["KV"], sz["hd"]
    x = _rms(h, f32(lp["ln1"]), sz["eps"])
    q = ein("bsd,de->bse", x, f32(lp["wq"])).reshape(B, S, H, hd)
    k = ein("bsd,de->bse", x, f32(lp["wk"])).reshape(B, S, KV, hd)
    v = ein("bsd,de->bse", x, f32(lp["wv"])).reshape(B, S, KV, hd)
    q, k = _rope(q, pos, sz["theta"]), _rope(k, pos, sz["theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    sc = ein("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    o = ein("bhqk,bkhe->bqhe", w, v).reshape(B, S, H * hd)
    h = h + ein("bse,ed->bsd", o, f32(lp["wo"]))
    x = _rms(h, f32(lp["ln2"]), sz["eps"])
    g = ein("bsd,df->bsf", x, f32(lp["gate"]))
    u = ein("bsd,df->bsf", x, f32(lp["up"]))
    return h + ein("bsf,fd->bsd", jax.nn.silu(g) * u, f32(lp["down"]))


def logits(config: Dict, params: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """(B, S) tokens -> (B, S, V) float32 logits; layer by layer, each
    layer recomputed in the backward pass rather than kept. ``quant``
    as ``model_ref.ein_for`` takes it."""
    sz = model_sizes(config)
    ein = model_ref.ein_for(quant)
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    emb = params["embed"].astype(jnp.float32)
    h = emb[tokens]
    blocks = params["blocks"]
    per_layer = {"ln1": blocks["ln1"], "ln2": blocks["ln2"],
                 **blocks["attn"], **blocks["mlp"]}

    @jax.checkpoint
    def body(h, lp):
        return _layer(h, lp, pos, sz, ein), None

    h, _ = jax.lax.scan(body, h, per_layer)
    h = _rms(h, params["final_norm"].astype(jnp.float32), sz["eps"])
    head = params.get("lm_head", params["embed"]).astype(jnp.float32)
    return ein("bsd,vd->bsv", h, head)


# ---------------------------------------------------------------------------
# operations and bytes (see bench/flops.py)
# ---------------------------------------------------------------------------
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
