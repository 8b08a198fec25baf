"""Compiled pipeline-parallel train programs on the 2-D (stage, data) mesh.

``build_pipeline_program`` lowers the point-to-point dependency graph of
``core/p2p.py`` — chunks SIG toward their successor, WAIT on their
predecessor — into one ``shard_map`` train step over a 2-D mesh:

* the **stage axis** partitions the stacked-blocks scan. With
  ``interleave = v`` each device owns v NON-contiguous chunks of the
  scan (device s holds chunks s, s+S, …, the looping placement), so
  consecutive chunks sit on neighbouring devices and every wave's
  activation/cotangent handoff stays a single ``lax.ppermute`` hop —
  ring perms (±1 mod S) carry the chunk-group wrap, the open chains of
  the v=1 case are unchanged. Waves are emitted in the interleaved 1F1B
  order ``derive_interleaved`` derives from the phase ordering
  (``schedule.py``); the per-wave (chunk group, microbatch) item is
  data (``wave − axis_index`` arithmetic), not control flow, and each
  backward wave recomputes its chunk slice under ``jax.vjp`` from the
  parked incoming activation. Parked activations live in PER-CHUNK ring
  buffers of ``sched.ring_slots`` slots — live microbatch indices per
  chunk are consecutive (schedule ``check()``), so modular indexing is
  collision-free and the program holds O(ring) activations per chunk
  instead of GPipe's O(M).
* the **data axis** runs the elastic epoch's collective schedule
  unchanged: the stage-local grads flatten into the engine's bucket
  layout (derived from the LOCAL param slice — v·per scan rows) and
  sync through ``execute_flat`` / ``execute_flat_pipelined`` — the same
  ppermute rounds, fused Pallas combine, alive-flag count and overlap
  config as the single-axis engine, now per stage row; with
  ``overlap="pipelined"`` the extra backward waves of the interleaved
  schedule are exactly where the early bucket groups' gradsync rounds
  overlap. Replicated-parameter grads (embed/head/shared) are psum'ed
  over the stage axis first, and the AdamW clip norm is computed
  globally across stages, so the update is mathematically identical to
  the single-axis step (asserted to f32 tolerance against the
  ``xla_psum`` baseline program in ``examples/elastic_train.py``
  through grow/shrink churn, for any interleave).

Carried state is DEVICE-MAJOR: with v > 1 the step takes and returns
the stacked-blocks rows (params and both Adam moments) in the chunk
layout the stage shards actually hold — device s's contiguous shard is
its v chunks in group order. Steady-state training therefore performs
ZERO cross-shard layout permutes: the old design re-gathered params,
mu and nu to the canonical layer order inside every step (6 permutes
per step); now the canonical view exists only at the explicit
``bind_state`` / ``readout_state`` boundaries (program bind,
checkpoint save/restore, final readout). The permutation is a pure
row gather — arithmetic-free — so a device-major run read out at any
step is bitwise identical to the old canonical-surface step, and the
layout depends only on (S, v, rows-per-chunk): epoch swaps under
data-axis churn reuse the carried state as-is.

SPMD uniformity: every wave is kind-uniform (all active stages run the
same instruction), so warmup/cooldown idleness is masked compute — the
same wall-clock shape as a real pipeline bubble. Interleaving makes
each wave 1/v of a stage, cutting the fill/drain cost to 2(S-1) thin
waves (bubble fraction (S-1)/(vM+S-1), down from (S-1)/(M+S-1)).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..collective_exec.buckets import make_layout
from ..collective_exec.executor import execute_flat, execute_flat_pipelined
from ..collective_exec.program import OVERLAP_MODES, reduce_worker_metrics
from ..core.collective import PhaserCollective
from ..obs import timeline as obs_timeline
from ..optim import OptState
from ..sharding.policies import stage_data_mesh
from .schedule import PipelineSchedule, derive_interleaved

STAGE_AXIS = "stage"


def stage_partition(api, n_stages: int,
                    interleave: int = 1) -> Tuple[Tuple[int, int], ...]:
    """The chunk map: contiguous [lo, hi) slices of the stacked-blocks
    scan axis, one per CHUNK (``n_stages * interleave`` virtual stages;
    chunk c belongs to device ``c % n_stages``). The scan length
    (layers, or groups for the grouped families) must divide evenly."""
    assert n_stages >= 1 and interleave >= 1, (n_stages, interleave)
    assert api.pipeline_supported(), \
        f"pipeline: family {api.cfg.family!r} keeps the single-axis path"
    n_chunks = n_stages * interleave
    spec = api.param_spec()
    lens = {l.shape[0] for l in jax.tree_util.tree_leaves(spec["blocks"])}
    assert len(lens) == 1, f"ragged scan axis: {lens}"
    scan_len = lens.pop()
    assert scan_len % n_chunks == 0, \
        f"scan length {scan_len} not divisible by {n_chunks} chunks " \
        f"({n_stages} stages x {interleave} interleave)"
    per = scan_len // n_chunks
    return tuple((c * per, (c + 1) * per) for c in range(n_chunks))


def _spec_tree(param_spec, leaf_spec: P, blocks_spec: P):
    """PartitionSpec tree over the param structure: ``blocks`` leaves
    sharded, everything else replicated."""
    return {k: jax.tree_util.tree_map(
        lambda _: blocks_spec if k == "blocks" else leaf_spec, v)
        for k, v in param_spec.items()}


@dataclass
class PipelineProgram:
    """One epoch's compiled 2-D train step. Mirrors ``GradSyncProgram``'s
    surface (``step``/``reduce_metrics``) so the train loop and example
    drive both interchangeably; ``key`` additionally carries the chunk
    map and pipeline config (interleave included)."""

    key: tuple
    pc: PhaserCollective
    mesh: Mesh
    sched: PipelineSchedule
    stage_map: Tuple[Tuple[int, int], ...]
    interleave: int
    layout: Any
    jitted: Callable
    stacked: bool
    param_sh: Any
    opt_sh: Any
    bind_fn: Callable = None          # canonical -> device-major (jitted)
    readout_fn: Callable = None       # device-major -> canonical (jitted)
    meta: Dict[str, int] = field(default_factory=dict)

    @property
    def n(self) -> int:
        return self.pc.n

    @property
    def n_stages(self) -> int:
        return len(self.stage_map) // self.interleave

    def _commit(self, tree, shardings):
        """Re-commit carried state onto this program's 2-D mesh (stage
        slices for blocks, replicated otherwise) — resharding is a no-op
        within an epoch, an explicit device_put across epoch swaps."""
        return jax.tree_util.tree_map(
            lambda x, sh: x if getattr(x, "sharding", None) == sh
            else jax.device_put(x, sh), tree, shardings)

    def step(self, params, opt_state, batch, alive=None):
        """One step over DEVICE-MAJOR carried state (see module doc);
        ``bind_state`` converts canonical state once, the return value
        feeds the next step directly, and ``readout_state`` recovers
        the canonical order at checkpoint/readout boundaries."""
        if alive is None:
            alive = jnp.ones((self.pc.n,), jnp.float32)
        params = self._commit(params, self.param_sh)
        opt_state = self._commit(opt_state, self.opt_sh)
        return self.jitted(params, opt_state, batch, alive)

    def bind_state(self, params, opt_state):
        """Canonical layer order -> this program's device-major chunk
        layout (identity at v == 1). Pay once at program bind/restore;
        every subsequent step carries the returned layout."""
        if self.bind_fn is None:
            return params, opt_state
        return self.bind_fn(params, opt_state)

    def readout_state(self, params, opt_state):
        """Device-major carried state -> canonical layer order, for
        checkpoints, equality checks and final readout. A pure row
        gather: the round-trip is bitwise exact."""
        if self.readout_fn is None:
            return params, opt_state
        return self.readout_fn(params, opt_state)

    def reduce_metrics(self, pm: Dict[str, jax.Array]) -> Dict[str, Any]:
        return reduce_worker_metrics(pm, self.meta)


def build_pipeline_program(api, opt, pc: PhaserCollective, *,
                           n_stages: int,
                           interleave: int = 1,
                           devices: Optional[Sequence] = None,
                           microbatches: int = 1,
                           stacked: bool = False,
                           remat: bool = False,
                           fused: bool = True,
                           interpret: Optional[bool] = None,
                           overlap: str = "eager",
                           bucket_elems: Optional[int] = None,
                           block_groups: Optional[int] = None
                           ) -> PipelineProgram:
    """Compile the epoch's 2-D program: the (interleaved) 1F1B stage
    pipeline on the stage axis interleaved with the epoch's
    gradient-sync schedule on the data axis. ``microbatches`` is the
    pipeline depth M (the batch splits along its leading dim);
    ``interleave`` is the virtual-stage count v per device (M % S == 0
    required for v > 1); ``overlap``/``block_groups`` select the
    data-axis executor exactly as in ``build_gradsync_program``."""
    assert overlap in OVERLAP_MODES, overlap
    assert microbatches >= 1, microbatches
    S, M, v = n_stages, microbatches, interleave
    mesh = stage_data_mesh(S, pc.n, data_axis=pc.axis_name,
                           stage_axis=STAGE_AXIS, devices=devices)
    stage_map = stage_partition(api, S, v)
    sched = derive_interleaved(S, M, v)
    tl = obs_timeline.current()
    if tl is not None:
        # build-time: the schedule's wave/stage occupancy grid (one
        # event per filled slot, gaps = bubble) for the Chrome trace
        tl.extend(obs_timeline.pipeline_wave_events(
            sched, label=f":S{S}M{M}v{v}"))
    axis = pc.axis_name
    per = stage_map[0][1] - stage_map[0][0]
    Vc = S * v

    spec = api.param_spec()
    local_spec = dict(spec)
    local_spec["blocks"] = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((v * per, *l.shape[1:]), l.dtype),
        spec["blocks"])
    layout = make_layout(local_spec, bucket_elems=bucket_elems,
                         block_groups=block_groups or 1)

    param_ps = _spec_tree(spec, P(), P(STAGE_AXIS))
    opt_ps = OptState(step=P(), mu=param_ps, nu=param_ps)
    if v > 1:
        # ring perms: the chunk-group wrap (chunk jS+S-1 -> (j+1)S)
        # lands on device 0, so every wave's handoff is one hop mod S
        fperm = [(s, (s + 1) % S) for s in range(S)]
        bperm = [(s, (s - 1) % S) for s in range(S)]
        # canonical scan rows -> device-major chunk layout: device s's
        # contiguous stage shard holds its v chunks in group order
        chunk_perm = np.concatenate(
            [np.arange(per) + (j * S + s) * per
             for s in range(S) for j in range(v)])
        chunk_inv = np.argsort(chunk_perm)
    else:
        fperm = [(s, s + 1) for s in range(S - 1)]
        bperm = [(s, s - 1) for s in range(1, S)]
    inv_M = 1.0 / M
    R = sched.ring_slots

    def worker(params, opt_state, batch, alive):
        if stacked:
            batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        a = alive[0]
        sidx = lax.axis_index(STAGE_AXIS)
        is_first = sidx == 0
        is_last = sidx == S - 1
        blocks = params["blocks"]               # local (v*per, ...) slice
        io = {k: v_ for k, v_ in params.items() if k != "blocks"}
        tok_s, tgt_s = (batch[k].reshape(M, batch[k].shape[0] // M,
                                         *batch[k].shape[1:])
                        for k in ("tokens", "targets"))

        def chunk_blocks(blocks, j):
            if v == 1:
                return blocks
            return jax.tree_util.tree_map(
                lambda p: lax.dynamic_slice_in_dim(p, j * per, per, 0),
                blocks)

        def local_fwd(blocks, io, recv, tok, j, want_embed):
            # the chunk input: the embedded microbatch at chunk 0, the
            # ppermuted predecessor activation elsewhere (the `where`
            # also routes the embed gradient to chunk 0 only).
            # ``want_embed`` is STATIC per wave: with v > 1, only the
            # waves where device 0's item is chunk group 0 can consume
            # the embedding — the rest skip it (and its vjp) entirely,
            # which is what keeps the thinner interleaved waves cheap.
            ht = recv.astype(zero_h.dtype)
            if want_embed:
                h0 = api.embed_fn(io, tok)
                use_embed = is_first if v == 1 else is_first & (j == 0)
                ht = jnp.where(use_embed, h0, recv.astype(h0.dtype))
            return api.stage_fn(io, chunk_blocks(blocks, j), ht,
                                remat=remat)

        def local_obj(blocks, io, recv, tok, tgt, j, want_embed,
                      want_head):
            h_out, aux = local_fwd(blocks, io, recv, tok, j, want_embed)
            # ``want_head`` is STATIC per wave: only the waves where
            # device S-1's item is the LAST chunk read the loss head —
            # elsewhere the xent cotangent is zero anyway, so skipping
            # the head (and its vjp) computes the identical gradients
            if want_head:
                logits = api.head_fn(io, h_out)
                xent = api.loss_from_logits(logits, tgt)
            else:
                xent = jnp.zeros((), jnp.float32)
            return h_out, xent, aux

        zero_h = jnp.zeros_like(api.embed_fn(io, tok_s[0]))
        # parked-activation RINGS, one per chunk group: live microbatch
        # indices per chunk are consecutive and capped by the schedule's
        # per-chunk in-flight bound (check()), so ``ring_slots`` slots
        # with modular indexing are collision-free. This is what keeps
        # the compiled program at O(ring) activations per chunk instead
        # of GPipe's O(M).
        acts = jnp.zeros((v, R, *zero_h.shape), zero_h.dtype)
        fwd_reg = zero_h
        bwd_reg = zero_h
        f32z = lambda t: jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), t)
        g_blocks = f32z(blocks)
        g_io = f32z(io)
        loss_acc = jnp.zeros((), jnp.float32)
        aux_acc = jnp.zeros((), jnp.float32)

        for kind, w in sched.waves:
            if kind == "F":
                y = (lax.ppermute(fwd_reg, STAGE_AXIS, perm=fperm)
                     if S > 1 else fwd_reg)
                r = w - sidx
                active = (r >= 0) & (r < v * M)
                rc = jnp.clip(r, 0, v * M - 1)
                j = (rc // S) % v
                m = (rc // Vc) * S + rc % S
                # static: only device 0 consumes the embedding, and
                # only in the waves where ITS item is chunk group 0
                we = (0 <= w < v * M) and (w // S) % v == 0
                h_out, _ = local_fwd(blocks, io, y, tok_s[m], j, we)
                # park the incoming activation for the backward
                # recompute (this chunk's 1F1B in-flight set)
                mr = m % R
                acts = acts.at[j, mr].set(jnp.where(active, y,
                                                    acts[j, mr]))
                fwd_reg = jnp.where(active, h_out,
                                    jnp.zeros_like(h_out))
            else:
                cot = (lax.ppermute(bwd_reg, STAGE_AXIS, perm=bperm)
                       if S > 1 else bwd_reg)
                r = w - (S - 1 - sidx)
                active = (r >= 0) & (r < v * M)
                rc = jnp.clip(r, 0, v * M - 1)
                j = (v - 1) - (rc // S) % v
                m = (rc // Vc) * S + rc % S
                last_chunk = is_last if v == 1 else is_last & (j == v - 1)
                # static per wave: device 0's backward touches the
                # embed grad only when its item is chunk group 0;
                # device S-1 reads the loss head only when its item is
                # the LAST chunk (w is device 0's / S-1's local index)
                r0 = w - (S - 1)
                we = (0 <= r0 < v * M) and \
                    (v - 1) - (r0 // S) % v == 0
                wh = (0 <= w < v * M) and (w // S) % v == 0
                obj = lambda b_, io_, recv, tok, tgt: \
                    local_obj(b_, io_, recv, tok, tgt, j, we, wh)
                primals, pull = jax.vjp(obj, blocks, io,
                                        acts[j, m % R], tok_s[m],
                                        tgt_s[m])
                _, xent_p, aux_p = primals
                cot_h = jnp.where(last_chunk, jnp.zeros_like(cot), cot)
                cot_x = jnp.where(last_chunk, inv_M,
                                  0.0).astype(xent_p.dtype)
                cot_a = jnp.asarray(0.01 * inv_M, aux_p.dtype)
                gb, gio, g_recv, _, _ = pull(
                    (cot_h.astype(zero_h.dtype), cot_x, cot_a))
                gate = active.astype(jnp.float32)
                add = lambda acc, g: acc + gate * g.astype(jnp.float32)
                g_blocks = jax.tree_util.tree_map(add, g_blocks, gb)
                g_io = jax.tree_util.tree_map(add, g_io, gio)
                loss_acc = loss_acc + jnp.where(
                    active & last_chunk, xent_p.astype(jnp.float32), 0.0)
                aux_acc = aux_acc + jnp.where(
                    active, aux_p.astype(jnp.float32), 0.0)
                bwd_reg = jnp.where(active, g_recv,
                                    jnp.zeros_like(g_recv))

        # cross-stage reductions: the loss materializes at the last
        # chunk, replicated-param grads sum their per-stage contributions
        loss = lax.psum(loss_acc, STAGE_AXIS) * inv_M
        aux = lax.psum(aux_acc, STAGE_AXIS) * inv_M
        g_io = jax.tree_util.tree_map(
            lambda g: lax.psum(g, STAGE_AXIS), g_io)
        grads = dict(g_io)
        grads["blocks"] = g_blocks
        grads = jax.tree_util.tree_map(
            lambda g: g * a.astype(g.dtype), grads)

        # ---- data-axis sync: the epoch's collective schedule, per
        # stage row, with the engine's bucket layout over the LOCAL
        # param slice (overlap config identical to the 1-D engine) ----
        if overlap == "pipelined":
            bufs = layout.flatten_groups(grads, a)
            bufs = execute_flat_pipelined(bufs, pc, fused=fused,
                                          interpret=interpret)
            grads, count = layout.unflatten_groups(bufs)
        else:
            flat = execute_flat(layout.flatten(grads, a), pc,
                                fused=fused, interpret=interpret)
            grads, count = layout.unflatten(flat)
        inv = 1.0 / jnp.maximum(count, 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: g * inv.astype(g.dtype), grads)

        # clip on the TRUE global norm: stage-local block slices are
        # disjoint (psum their square sums), replicated grads count once
        sq = lambda t: sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                           for l in jax.tree_util.tree_leaves(t))
        gnorm = jnp.sqrt(lax.psum(sq(grads["blocks"]), STAGE_AXIS)
                         + sq({k: g for k, g in grads.items()
                               if k != "blocks"}))
        new_p, new_o, om = opt.update(grads, opt_state, params,
                                      gnorm=gnorm)
        pm = {"loss": loss * a, "aux": aux * a, "alive": a, **om}
        pm = {k: jnp.asarray(val, jnp.float32).reshape(1)
              for k, val in pm.items()}
        return new_p, new_o, pm

    sm = jax.shard_map(worker, mesh=mesh,
                       in_specs=(param_ps, opt_ps, P(axis), P(axis)),
                       out_specs=(param_ps, opt_ps, P(axis)),
                       check_vma=False)

    # the step is compiled over the device-major layout directly —
    # carried state stays put between steps, so the interleaved program
    # has NO per-step layout permutes (the old canonical-surface design
    # re-gathered params + both Adam moments in and out every step).
    # The canonical view moves behind explicit jitted converters, paid
    # only at bind / checkpoint / readout boundaries.
    jitted = jax.jit(sm)
    bind_fn = readout_fn = None
    if v > 1:
        to_dev = jnp.asarray(chunk_perm)
        to_can = jnp.asarray(chunk_inv)

        def permute_blocks(tree, idx):
            blk = jax.tree_util.tree_map(
                lambda p: jnp.take(p, idx, axis=0), tree["blocks"])
            return {**tree, "blocks": blk}

        def permute_state(params, opt_state, idx):
            return (permute_blocks(params, idx),
                    OptState(step=opt_state.step,
                             mu=permute_blocks(opt_state.mu, idx),
                             nu=permute_blocks(opt_state.nu, idx)))

        bind_fn = jax.jit(lambda p, o: permute_state(p, o, to_dev))
        readout_fn = jax.jit(lambda p, o: permute_state(p, o, to_can))
    named = lambda ps: NamedSharding(mesh, ps)
    is_p = lambda x: isinstance(x, P)
    param_sh = jax.tree_util.tree_map(named, param_ps, is_leaf=is_p)
    opt_sh = OptState(step=named(P()), mu=param_sh, nu=param_sh)
    st = pc.stats()
    meta = {"team": pc.n, "stages": S, "microbatches": M,
            "interleave": v,
            "pipeline_waves": sched.n_waves,
            "ring_slots": R,
            "sync_rounds": st["rounds"],
            "sync_messages": st["messages"],
            "overlap": int(overlap == "pipelined"),
            "bucket_groups": layout.n_groups}
    key = (pc.keys, pc.kind, pc.seed, pc.p, "pipeline", stage_map,
           overlap, M, v)
    return PipelineProgram(key=key, pc=pc, mesh=mesh, sched=sched,
                           stage_map=stage_map, interleave=v,
                           layout=layout, jitted=jitted, stacked=stacked,
                           param_sh=param_sh, opt_sh=opt_sh,
                           bind_fn=bind_fn, readout_fn=readout_fn,
                           meta=meta)
