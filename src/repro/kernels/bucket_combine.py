"""Fused bucket-combine Pallas kernel for the collective execution engine.

One schedule round over the bucketed gradient buffer is one fused kernel
launch: instead of ~hundreds of per-leaf adds (one XLA op per pytree
leaf), the flattened gradient rides a (n_buckets, bucket_elems) f32
buffer and the local reduce of a ``lax.ppermute`` round is a single
grid-over-buckets elementwise kernel. The round's *gate* — whether this
device is a destination of the round's partial permutation — is a scalar
in SMEM, so the same compiled kernel serves every round of the schedule:

* ``op="add"``  — reduce rounds: ``acc + gate * incoming``
* ``op="copy"`` — broadcast/hydration rounds: ``gate ? incoming : acc``

A VMEM block is 8 bucket rows (one f32 sublane tile; the TPU compiler
takes a block whose last two dims divide by (8, 128) or equal the
operand's), or all rows of an operand with fewer than 8. The engine pads
every larger group to a multiple of 8 buckets (``buckets.make_layout``)
and caps a bucket row at ``MAX_BUCKET_BYTES``, so the three operands,
double-buffered, stay inside ``VMEM_BUDGET``. Off-TPU callers run the
same kernel body under the interpreter.

**Variable-group launch**: the grid is derived from the operand's row
count, so the same kernel serves the eager executor (one launch over
the full ``(n_buckets, bucket_elems)`` buffer per round) and the
pipelined executor (one launch per readiness group per round, each with
that group's own bucket count). A zero-row group is a no-op without a
launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8                         # rows per block (f32 tile height)
# under v5e's 16 MiB default scoped VMEM: 3 operands (acc, incoming,
# out), each double-buffered, of one block of SUBLANES bucket rows
VMEM_BUDGET = 12 * 1024 * 1024
MAX_BUCKET_BYTES = VMEM_BUDGET // (3 * 2 * SUBLANES)   # 256 KiB


def _combine_kernel(gate_ref, acc_ref, y_ref, o_ref, *, op: str):
    g = gate_ref[0, 0] != 0
    acc = acc_ref[...]
    y = y_ref[...]
    if op == "add":
        o_ref[...] = acc + jnp.where(g, y, jnp.zeros_like(y))
    else:  # "copy": round destinations take the incoming value wholesale
        o_ref[...] = jnp.where(g, y, acc)


def bucket_combine(acc: jax.Array, y: jax.Array, gate: jax.Array, *,
                   op: str = "add", interpret: bool = False) -> jax.Array:
    """Combine one ppermute round into the bucketed accumulator.

    ``acc``/``y``: (rows, bucket_elems) — the full buffer or one
    readiness group's sub-buffer (the grid follows the operand, so group
    sizes may vary launch to launch); ``gate``: scalar bool/int (is this
    device a destination this round); ``op``: "add" | "copy".
    """
    assert acc.ndim == 2 and acc.shape == y.shape, (acc.shape, y.shape)
    assert op in ("add", "copy"), op
    nb, be = acc.shape
    if nb == 0:
        return acc
    assert be * acc.dtype.itemsize <= MAX_BUCKET_BYTES, \
        f"bucket row of {be} elems exceeds the VMEM block budget"
    rows = min(nb, SUBLANES)
    assert nb % rows == 0, \
        f"{nb} bucket rows: pad to a multiple of {SUBLANES}"
    kernel = functools.partial(_combine_kernel, op=op)
    gate2 = jnp.asarray(gate).astype(jnp.int32).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, be), lambda i: (i, 0)),
            pl.BlockSpec((rows, be), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, be), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        interpret=interpret,
    )(gate2, acc, y)
