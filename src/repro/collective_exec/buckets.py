"""Bucketed flattening of gradient pytrees for the execution engine.

The grad pytree is raveled leaf-by-leaf into an f32 vector, the *alive
flag* (1.0 for a contributing worker, 0.0 for a departed one) is
appended, and the vector is zero-padded up to a ``(n_buckets,
bucket_elems)`` buffer whose rows are lane-aligned (multiples of 128)
and VMEM-sized. One ``lax.ppermute`` round then moves the whole buffer
and one fused Pallas kernel launch combines it — instead of one op per
pytree leaf.

Because the alive flag rides the same all-reduce as the payload, the
reduced buffer's flag slot holds the live contributor count: the masked
mean (``sum(grads) / n_alive``) costs no second collective.

**Reverse-layer order + readiness groups** (DESIGN.md §5): leaves are
ordered by *reverse topological depth* of the grad pytree — output-side
parameters (lm_head, final_norm) first, stacked block parameters next,
input-side embeddings last — because backprop finalizes gradients in
exactly that order. Contiguous runs of leaves with the same readiness
class form **bucket groups**: group 0's buckets hold the gradients that
finalize earliest, so a pipelined executor can start syncing group 0
while the backward pass is still producing the later groups. Each group
is padded to a whole number of buckets independently, which keeps every
group's sub-buffer a standalone ``(g_buckets, bucket_elems)`` collective
operand with no dataflow dependency on the other groups' leaves. Each
group's bucket count is a multiple of 8, the combine kernel's block
height, so the full buffer and every group's sub-buffer tile alike.

**Per-layer scan-slice sub-groups** (``block_groups=K``): the backward
scan over the stacked blocks finalizes the stacked grad ROWS from the
last layer down, so the monolithic "blocks" group can split into K
row-range sub-groups of the scan axis — ordered last-rows-first, the
order the backward scan emits them. Each sub-group covers the same
stacked leaves restricted to its row slice (``group_rows``), padded to
whole buckets like any other group, which deepens the pipelined
executor's overlap past the 3 coarse classes. Row splitting applies
only when every stacked-blocks leaf shares one scan length; anything
else (and non-stacked class-1 leaves, e.g. a hybrid family's shared
attention, whose grads accumulate across the whole backward) keeps its
own unsplit group after the block sub-groups.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..kernels.bucket_combine import MAX_BUCKET_BYTES, SUBLANES

LANES = 128                        # TPU lane width: rows stay tile-aligned
DEFAULT_BUCKET_ELEMS = 1 << 16     # 256 KiB f32 rows

# readiness classes, in the order backprop finalizes gradients:
#   0 = output side (loss head — grads ready first)
#   1 = interior blocks (stacked-layer leaves — ready after the backward
#       scan reaches layer 0)
#   2 = input side (embeddings — accumulated until the very end)
_OUTPUT_NAMES = ("lm_head", "final_norm", "head", "out_norm")
_INPUT_NAMES = ("embed", "patch_proj", "frame_proj")


def _path_names(path: Tuple) -> List[str]:
    return [str(getattr(p, "key", getattr(p, "idx", p))).lower()
            for p in path]


def _leaf_class(path: Tuple) -> int:
    for n in _path_names(path):
        if any(tag in n for tag in _OUTPUT_NAMES):
            return 0
        if any(tag in n for tag in _INPUT_NAMES):
            return 2
    return 1


def _rows_elems(size: int, shape: Tuple[int, ...],
                rows: Optional[Tuple[int, int]]) -> int:
    """Raveled elems a leaf contributes to a group: the whole leaf, or
    its [rlo, rhi) slice of the leading scan axis. The single owner of
    the row-slice accounting (layout derivation and flatten/unflatten
    must agree on it)."""
    if rows is None:
        return size
    rlo, rhi = rows
    return (rhi - rlo) * (size // shape[0])


@dataclass(frozen=True)
class BucketLayout:
    """Static identity of the bucketed buffer: part of the compiled
    program's key (it is derived from the param spec, which only changes
    when the model does).

    ``perm[j]`` is the index (into tree-flatten order) of the j-th leaf
    in buffer order; ``group_leaves`` are [lo, hi) ranges into that
    permuted order, one per readiness group (group 0 finalizes
    earliest); ``group_rows[g]`` restricts group g to a [rlo, rhi) slice
    of its stacked leaves' leading (scan) axis — ``None`` takes whole
    leaves, and row-split groups repeat the same leaf range with
    disjoint row slices (``block_groups``). ``group_buckets`` is each
    group's bucket count, and ``groups`` the derived [start, stop)
    *bucket* ranges. The alive flag occupies ``flag_index`` (flattened
    element index) at the tail of the last group — it is an input, so it
    never delays a group's readiness.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    payload: int                   # raveled grad elems (without the flag)
    n_buckets: int
    bucket_elems: int
    perm: Tuple[int, ...] = ()
    group_leaves: Tuple[Tuple[int, int], ...] = ()
    group_rows: Tuple[Optional[Tuple[int, int]], ...] = ()
    group_buckets: Tuple[int, ...] = ()
    flag_index: int = -1

    def __post_init__(self):
        if not self.perm:
            object.__setattr__(self, "perm",
                               tuple(range(len(self.sizes))))
        if not self.group_leaves:
            object.__setattr__(self, "group_leaves",
                               ((0, len(self.sizes)),))
        if not self.group_rows:
            object.__setattr__(self, "group_rows",
                               (None,) * len(self.group_leaves))
        if not self.group_buckets:
            object.__setattr__(self, "group_buckets", (self.n_buckets,))
        if self.flag_index < 0:
            object.__setattr__(
                self, "flag_index",
                (self.n_buckets - self.group_buckets[-1])
                * self.bucket_elems + self._group_payload(-1) - 1)

    @property
    def total_elems(self) -> int:
        return self.n_buckets * self.bucket_elems

    @property
    def n_groups(self) -> int:
        return len(self.group_buckets)

    @property
    def groups(self) -> Tuple[Tuple[int, int], ...]:
        """Per-group [start, stop) bucket ranges, readiness order."""
        out, off = [], 0
        for nb in self.group_buckets:
            out.append((off, off + nb))
            off += nb
        return tuple(out)

    def _leaf_elems(self, i: int, rows: Optional[Tuple[int, int]]) -> int:
        return _rows_elems(self.sizes[i], self.shapes[i], rows)

    def _group_payload(self, g: int) -> int:
        """Raveled elems in group g, including the flag in the last."""
        if g == -1:
            g = len(self.group_leaves) - 1
        lo, hi = self.group_leaves[g]
        rows = self.group_rows[g]
        base = sum(self._leaf_elems(self.perm[j], rows)
                   for j in range(lo, hi))
        return base + (1 if g == len(self.group_leaves) - 1 else 0)

    # ----------------------------------------------------------- flatten
    def flatten_groups(self, tree, alive) -> List[jax.Array]:
        """tree -> per-group ``(g_buckets, bucket_elems)`` f32 buffers.

        Each group's buffer depends only on its own leaves — or, for a
        row-split group, only on its rows of the stacked leaves (plus
        the alive flag in the last group) — so a consumer can launch
        group 0's collective before the later groups' gradients exist.
        """
        leaves = jax.tree_util.tree_leaves(tree)
        assert len(leaves) == len(self.sizes), \
            (len(leaves), len(self.sizes))
        out = []
        for g, (lo, hi) in enumerate(self.group_leaves):
            rows = self.group_rows[g]
            parts = []
            for j in range(lo, hi):
                leaf = leaves[self.perm[j]]
                if rows is not None:
                    leaf = leaf[rows[0]:rows[1]]
                parts.append(leaf.astype(jnp.float32).reshape(-1))
            if g == self.n_groups - 1:
                parts.append(jnp.asarray(alive, jnp.float32).reshape(1))
            flat = (jnp.concatenate(parts) if parts
                    else jnp.zeros((0,), jnp.float32))
            pad = self.group_buckets[g] * self.bucket_elems - flat.shape[0]
            assert pad >= 0, (g, flat.shape[0])
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), jnp.float32)])
            out.append(flat.reshape(self.group_buckets[g],
                                    self.bucket_elems))
        return out

    def flatten(self, tree, alive) -> jax.Array:
        """tree -> (n_buckets, bucket_elems) f32, alive flag appended at
        the tail of the last readiness group."""
        return jnp.concatenate(self.flatten_groups(tree, alive), axis=0)

    # --------------------------------------------------------- unflatten
    def unflatten_groups(self, bufs: Sequence[jax.Array]
                         ) -> Tuple[Any, jax.Array]:
        """Per-group buffers -> (tree, contributor count)."""
        assert len(bufs) == self.n_groups, (len(bufs), self.n_groups)
        return self.unflatten(jnp.concatenate(list(bufs), axis=0))

    def unflatten(self, buf: jax.Array) -> Tuple[Any, jax.Array]:
        """(n_buckets, bucket_elems) -> (tree, contributor count)."""
        flat = buf.reshape(-1)
        leaves: List[Any] = [None] * len(self.sizes)
        pieces: dict = {}              # leaf idx -> [(rlo, rows array)]
        off = 0
        for g, (lo, hi) in enumerate(self.group_leaves):
            rows = self.group_rows[g]
            pos = off
            for j in range(lo, hi):
                i = self.perm[j]
                size = self._leaf_elems(i, rows)
                seg = flat[pos:pos + size]
                if rows is None:
                    leaves[i] = (seg.reshape(self.shapes[i])
                                 .astype(self.dtypes[i]))
                else:
                    pieces.setdefault(i, []).append(
                        (rows[0], seg.reshape(rows[1] - rows[0],
                                              *self.shapes[i][1:])))
                pos += size
            off += self.group_buckets[g] * self.bucket_elems
        for i, ps in pieces.items():
            stacked = jnp.concatenate(
                [p for _, p in sorted(ps, key=lambda t: t[0])], axis=0)
            leaves[i] = (stacked.reshape(self.shapes[i])
                         .astype(self.dtypes[i]))
        count = flat[self.flag_index]
        return jax.tree_util.tree_unflatten(self.treedef, leaves), count


def make_layout(tree, *, bucket_elems: int = None,
                order: str = "reverse_topo",
                block_groups: int = 1) -> BucketLayout:
    """Derive the bucket layout from a pytree of arrays or
    ShapeDtypeStructs (typically ``api.param_spec()``).

    ``order="reverse_topo"`` (default) sorts leaves by reverse
    topological depth — the order backprop finalizes their gradients —
    and records the readiness groups; ``order="tree"`` keeps the raw
    tree-flatten order in a single group (the pre-overlap layout).
    ``block_groups=K`` additionally splits the stacked-blocks group into
    K scan-row sub-groups, last rows first — the order the backward
    scan emits them — so the pipelined executor's overlap deepens past
    the 3 coarse readiness classes.
    """
    assert order in ("reverse_topo", "tree"), order
    assert block_groups >= 1, block_groups
    flat_with_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    assert flat_with_paths, "empty gradient tree"
    paths = [p for p, _ in flat_with_paths]
    leaves = [l for _, l in flat_with_paths]
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(math.prod(s)) for s in shapes)
    payload = sum(sizes)
    total = payload + 1                       # + alive flag
    if bucket_elems is None:
        bucket_elems = min(DEFAULT_BUCKET_ELEMS,
                           -(-total // LANES) * LANES)
    assert bucket_elems % LANES == 0, bucket_elems
    assert bucket_elems * 4 <= MAX_BUCKET_BYTES, bucket_elems

    if order == "reverse_topo":
        classes = [_leaf_class(p) for p in paths]
    else:
        classes = [1] * len(leaves)

    # stacked-blocks leaves: class 1, under a "blocks" subtree, with one
    # common scan length — the only leaves eligible for row splitting
    stacked = [classes[i] == 1 and "blocks" in _path_names(paths[i])
               and len(shapes[i]) >= 1 and shapes[i][0] > 0
               for i in range(len(leaves))]
    scan_lens = {shapes[i][0] for i in range(len(leaves)) if stacked[i]}
    scan_len = scan_lens.pop() if len(scan_lens) == 1 else 0
    n_row_groups = (min(block_groups, scan_len)
                    if order == "reverse_topo" and scan_len else 1)
    if n_row_groups == 1:
        stacked = [False] * len(leaves)

    if order == "reverse_topo":
        # within class 1, stacked-blocks leaves sort ahead of loose
        # class-1 leaves (whose grads accumulate across the whole
        # backward, like inputs) — a no-op unless rows are split
        sub = [0 if (classes[i] != 1 or stacked[i] or n_row_groups == 1)
               else 1 for i in range(len(leaves))]
        perm = tuple(sorted(range(len(leaves)),
                            key=lambda i: (classes[i], sub[i], i)))
    else:
        sub = [0] * len(leaves)
        perm = tuple(range(len(leaves)))

    # contiguous runs of one (readiness class, stackedness) -> groups;
    # the stacked-blocks run fans out into n_row_groups row slices,
    # ordered last-rows-first (the backward scan's emission order)
    group_leaves: List[Tuple[int, int]] = []
    group_rows: List[Optional[Tuple[int, int]]] = []
    lo = 0
    key_of = lambda i: (classes[i], sub[i], stacked[i])
    for j in range(1, len(perm) + 1):
        if j < len(perm) and key_of(perm[j]) == key_of(perm[lo]):
            continue
        if stacked[perm[lo]] and n_row_groups > 1:
            bounds = [round(k * scan_len / n_row_groups)
                      for k in range(n_row_groups + 1)]
            for k in range(n_row_groups - 1, -1, -1):
                group_leaves.append((lo, j))
                group_rows.append((bounds[k], bounds[k + 1]))
        else:
            group_leaves.append((lo, j))
            group_rows.append(None)
        lo = j

    group_buckets = []
    for g, (glo, ghi) in enumerate(group_leaves):
        elems = sum(_rows_elems(sizes[perm[j]], shapes[perm[j]],
                                group_rows[g])
                    for j in range(glo, ghi))
        if g == len(group_leaves) - 1:
            elems += 1                        # alive flag rides the tail
        # whole kernel blocks: every group and the full buffer tile
        nb = -(-elems // (bucket_elems * SUBLANES)) * SUBLANES
        group_buckets.append(max(SUBLANES, nb))
    # flag_index is derived in __post_init__ (tail of the last group) —
    # one owner for the flag-position invariant
    return BucketLayout(treedef=treedef, shapes=shapes, dtypes=dtypes,
                        sizes=sizes, payload=payload,
                        n_buckets=sum(group_buckets),
                        bucket_elems=bucket_elems, perm=perm,
                        group_leaves=tuple(group_leaves),
                        group_rows=tuple(group_rows),
                        group_buckets=tuple(group_buckets))
