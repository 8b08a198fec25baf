"""Plain float32 reference of the models the cells run, and the seeded
weights both sides use.

Nothing here imports the program. What depends on the block lives in
the configuration's family module, ``bench/families/<family>.py``
(``harness.family``): its parameter shapes and the init rule of each
leaf, and its forward pass. This module keeps what every family shares:
the seeded weights, made from the family's shapes in the program's
layout and stored in the dtype the configuration states, the loss and
gradient in blocks of rows, AdamW as the train cells run it, and the
gap of each served token. The reference is straightforward
``jax.numpy`` at float32 and the highest matmul precision; it upcasts
the stored weights as it reads them. ``quant="fp8"`` rounds every
matmul operand to float8 e4m3 with a per-tensor scale: the control, one
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import family, family_module

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(config: Dict) -> Dict:
    """The family's parameter shapes, in the program's layout."""
    return family(config).param_shapes(config)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    k = jax.random.fold_in(jax.random.key(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, seed >> 31)


def _paths(shapes, prefix=""):
    out = []
    for k in sorted(shapes):
        v = shapes[k]
        p = f"{prefix}/{k}" if prefix else k
        out += [(p, v)] if _is_shape(v) else _paths(v, p)
    return out


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return tree


@functools.partial(jax.jit, static_argnames=("fam", "shape_items",
                                             "dtype"))
def _make(key, fam, shape_items, dtype):
    init = family_module(fam).init_leaf
    return {p: init(jax.random.fold_in(key, i), p, s, dtype)
            for i, (p, s) in enumerate(shape_items)}


def make_weights(config: Dict, seed: int, dtype=jnp.bfloat16,
                 device=None) -> Dict:
    """All weights of ``config`` from ``seed``, in one jitted call on the
    device, in the program's layout: leaf ``i`` of the sorted paths
    draws from the seed's key folded with ``i``, by the family's init
    rule for that leaf."""
    items = tuple(_paths(param_shapes(config)))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _nest(_make(key, config["family"], items,
                       jnp.dtype(dtype).name))


def flat_leaves(tree) -> Dict[str, jax.Array]:
    flat = {}

    def walk(node, prefix):
        for k in sorted(node):
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(node[k], dict):
                walk(node[k], p)
            else:
                flat[p] = node[k]
    walk(tree, "")
    return flat


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with a per-tensor scale; the gradient passes
    through unchanged."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0 + 1e-30)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def ein_for(quant: Optional[str]):
    """The reference's einsum: float32 at the highest precision, or with
    both operands rounded to float8 first (``quant="fp8"``, the
    control). Every matmul of a family's forward goes through it."""
    qz = _fp8 if quant == "fp8" else (lambda x: x)

    def ein(spec, a, b):
        return jnp.einsum(spec, qz(a), qz(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def logits(config: Dict, params: Dict, tokens: jax.Array,
           quant: Optional[str] = None) -> jax.Array:
    """(B, S) tokens -> (B, S, V) float32 logits by the family's
    forward."""
    return family(config).logits(config, params, tokens, quant)


def xent(lg: jax.Array, targets: jax.Array) -> jax.Array:
    """Summed next-token cross-entropy over all positions."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - gold)


# ---------------------------------------------------------------------------
# training: loss, gradient and AdamW, the update the train cells run
# ---------------------------------------------------------------------------
def _loss_sum(params, tokens, targets, config, quant):
    return xent(logits(config, params, tokens, quant), targets)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _block_grad(params, tokens, targets, cfg_items, quant):
    config = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_loss_sum)(
            jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params),
            tokens, targets, config, quant)


def _frozen(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    if isinstance(v, list):
        return tuple(_frozen(x) for x in v)
    return v


def _cfg_items(config: Dict):
    """The configuration as a static argument of the reference's jitted
    calls: its keys and values, lists and nested groups as tuples."""
    return tuple(sorted((k, _frozen(v)) for k, v in config.items()))


def loss_and_grad(config: Dict, params: Dict, tokens: np.ndarray,
                  targets: np.ndarray, *, block_rows: int,
                  rows: Optional[Sequence[int]] = None,
                  quant: Optional[str] = None,
                  devices: Optional[Sequence] = None):
    """Mean loss and float32 gradient over ``rows`` of the batch (all
    rows by default), ``block_rows`` rows at a time. With ``devices``
    the blocks take turns on them and are summed on the first, in the
    same order as on one device."""
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    blocks = [rows[i:i + block_rows] for i in range(0, len(rows),
                                                    block_rows)]
    if devices:
        copies = [jax.device_put(params, d) for d in devices]
        parts = []
        for j, r in enumerate(blocks):
            d = j % len(devices)
            parts.append(_block_grad(
                copies[d], jax.device_put(tokens[r], devices[d]),
                jax.device_put(targets[r], devices[d]),
                _cfg_items(config), quant))
        parts = jax.device_put(parts, devices[0])
        del copies
    else:
        parts = (_block_grad(params, jnp.asarray(tokens[r]),
                             jnp.asarray(targets[r]), _cfg_items(config),
                             quant) for r in blocks)
    total, grad = 0.0, None
    for l, g in parts:
        total = total + l
        grad = g if grad is None else jax.tree_util.tree_map(jnp.add,
                                                              grad, g)
    n = len(rows) * tokens.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grad)


def adamw_lr(opt: Dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay
    to zero at ``total_steps``; ``step`` counts from 1."""
    base, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * base * (1 + math.cos(math.pi * prog))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "clip", "decay_rank",
                                             "dtype"))
def _adamw(params, grads, mu, nu, step, lr, *, b1, b2, eps, wd, clip,
           decay_rank, dtype):
    gn = jnp.sqrt(sum(jnp.sum(g * g)
                      for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gn + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(p, g, m, v):
        p32 = p.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        d = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if p.ndim >= decay_rank:
            d = d + wd * p32
        return (p32 - lr * d).astype(dtype), m, v

    out = jax.tree_util.tree_map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), grads, pick(1), pick(2)


def train_readings(config: Dict, opt: Dict, params0: Dict,
                   batches: Sequence[Dict[str, np.ndarray]], *,
                   block_rows: int, quant: Optional[str] = None,
                   rows: Optional[Sequence[int]] = None,
                   devices: Optional[Sequence] = None) -> Dict:
    """Drive the reference through ``len(batches)`` AdamW steps from
    ``params0``. Returns each step's loss, the first step's clipped
    gradient per leaf and each leaf's change over all the steps, the
    last two as float32 norms on the host, and the first gradient
    itself as float32 arrays on the host (``grad_tensors``)."""
    dtype = jax.tree_util.tree_leaves(params0)[0].dtype.name
    params = params0
    mu = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params0)
    nu = mu
    losses, first_grad, first_tensors = [], None, None
    for i, b in enumerate(batches):
        loss, g = loss_and_grad(config, params, b["tokens"], b["targets"],
                                block_rows=block_rows, quant=quant,
                                rows=rows, devices=devices)
        step = i + 1
        params, clipped, mu, nu = _adamw(
            params, g, mu, nu, jnp.float32(step),
            jnp.float32(adamw_lr(opt, step)), b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], wd=opt["weight_decay"], clip=opt["clip_norm"],
            decay_rank=opt["decay_min_rank"], dtype=dtype)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(clipped)
            first_tensors = host_leaves(clipped)
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, params0))
    return {"loss": losses, "grad": first_grad, "change": change,
            "grad_tensors": first_tensors}


def host_leaves(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float32)
            for k, v in flat_leaves(tree).items()}


def leaf_norms(tree) -> Dict[str, float]:
    flat = flat_leaves(tree)
    norms = _norms(flat)
    return {k: float(v) for k, v in norms.items()}


@jax.jit
def _norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


# ---------------------------------------------------------------------------
# serving: the gap of each served token under the reference's best
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _seq_logits(params, tokens, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        return logits(dict(cfg_items), params, tokens, quant)[0]


def served_gaps(config: Dict, params: Dict, prompt: np.ndarray,
                served: Sequence[int], *, pad_to: int,
                quant: Optional[str] = None) -> np.ndarray:
    """For each served token, how far the reference's logit for it lies
    below the reference's best at that position. With ``quant`` the
    served tokens are still the context, but the gap read is that of the
    token the quantised reference puts first."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = len(seq)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds {pad_to}")
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :n] = seq
    at = np.arange(len(prompt) - 1, n)          # positions that chose
    ref = np.asarray(_seq_logits(params, jnp.asarray(toks),
                                 _cfg_items(config), None))[at]
    if quant is None:
        chosen = np.asarray(served, np.int64)
    else:
        low = np.asarray(_seq_logits(params, jnp.asarray(toks),
                                     _cfg_items(config), quant))[at]
        chosen = low.argmax(-1)
    return ref.max(-1) - ref[np.arange(len(at)), chosen]
