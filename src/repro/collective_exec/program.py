"""Compiled device-resident programs: phaser schedules inside shard_map.

``build_gradsync_program`` compiles one membership epoch's gradient sync
into an executable ``shard_map`` train step over a real mesh axis:

  1. each mesh rank computes loss + grads on its own batch shard,
  2. the grad pytree is flattened into the bucketed buffer (alive flag
     appended — ``buckets.py``),
  3. the epoch's schedule runs as ``lax.ppermute`` rounds with the fused
     Pallas bucket-combine for the local reduce (``executor.py``),
  4. the buffer is unflattened, the masked mean is taken from the
     reduced alive count, and the optimizer update runs replicated.

Params and optimizer state are replicated (``P()``); batch and alive
mask are sharded over the data axis. ``check_vma=False`` because Pallas
calls carry no replication rule — the schedule itself guarantees every
rank ends with the same reduced buffer (tested against ``xla_psum``).

**Overlap modes** (DESIGN.md §5). ``overlap="pipelined"`` flattens the
grads per readiness group (``flatten_groups``) and runs the schedule
through ``execute_flat_pipelined``: each group's ``ppermute`` chain
depends only on that group's gradients, so the earliest-finalized
buckets (last layers, reverse-topo bucket 0) sync while the backward
pass is still producing the rest. With ``microbatches > 1`` the
grad-accumulation loop is unrolled and each microbatch's bucket stream
is issued as soon as its backward ends — microbatch ``k`` syncs while
microbatch ``k+1``'s backward runs, inside the same ``shard_map``. Both
modes execute the identical per-element combine sequence, so
``overlap="pipelined"`` is bitwise-equal to ``overlap="eager"``.

``build_allreduce_program`` is the raw data-plane program (no model):
it all-reduces a stacked per-rank value through the same bucket path —
what benchmarks and equivalence tests drive.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.collective import PhaserCollective
from .buckets import BucketLayout, make_layout
from .executor import execute_flat, execute_flat_pipelined

OVERLAP_MODES = ("eager", "pipelined")


def reduce_worker_metrics(pm: Dict[str, jax.Array],
                          meta: Dict[str, int]) -> Dict[str, Any]:
    """Per-worker (n,) metric rows -> scalars: masked mean for the
    pre-sync losses, the sum for the alive count, any rank's copy for
    post-sync values (replicated by construction), plus the program's
    static meta. Shared by every compiled program flavour so the
    reported metrics can never drift between the single-axis and
    pipeline paths."""
    n_alive = jnp.maximum(pm["alive"].sum(), 1.0)
    out = {}
    for k, v in pm.items():
        if k in ("loss", "aux"):
            out[k] = v.sum() / n_alive
        elif k == "alive":
            out[k] = v.sum()
        else:
            out[k] = v[0]
    out.update({k: jnp.asarray(v, jnp.float32) for k, v in meta.items()})
    return out


def mesh_for(pc: PhaserCollective,
             devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    assert len(devices) >= pc.n, \
        f"need {pc.n} devices for axis {pc.axis_name!r}, " \
        f"have {len(devices)}"
    return Mesh(np.array(devices[:pc.n]), (pc.axis_name,))


@dataclass
class GradSyncProgram:
    """One epoch's compiled train step. ``key`` is the program-cache
    identity: (member_set, kind, seed, p, overlap, microbatches)."""

    key: tuple
    pc: PhaserCollective
    mesh: Mesh
    layout: BucketLayout
    jitted: Callable          # (params, opt, batch, alive) -> (p, o, pm)
    stacked: bool
    meta: Dict[str, int] = field(default_factory=dict)

    @property
    def n(self) -> int:
        return self.pc.n

    def _replicated(self, tree):
        """Re-commit carried state onto this program's mesh (the epoch
        swap moves params between meshes of different sizes; jit refuses
        mixed committed device sets, so the swap is an explicit
        replicated device_put — a no-op within an epoch)."""
        sh = jax.sharding.NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: x if getattr(x, "sharding", None) == sh
            else jax.device_put(x, sh), tree)

    def step(self, params, opt_state, batch, alive=None):
        """Run one synced step; ``alive`` defaults to the full team."""
        if alive is None:
            alive = jnp.ones((self.pc.n,), jnp.float32)
        params = self._replicated(params)
        opt_state = self._replicated(opt_state)
        return self.jitted(params, opt_state, batch, alive)

    # single-axis programs carry canonical state: the converters exist
    # so loops drive this and the device-major pipeline program alike
    def bind_state(self, params, opt_state):
        return params, opt_state

    def readout_state(self, params, opt_state):
        return params, opt_state

    def reduce_metrics(self, pm: Dict[str, jax.Array]) -> Dict[str, Any]:
        return reduce_worker_metrics(pm, self.meta)


def build_gradsync_program(api, opt, pc: PhaserCollective, *,
                           devices: Optional[Sequence] = None,
                           stacked: bool = False,
                           remat: bool = False,
                           fused: bool = True,
                           interpret: Optional[bool] = None,
                           donate: bool = False,
                           bucket_elems: Optional[int] = None,
                           overlap: str = "eager",
                           microbatches: int = 1,
                           block_groups: Optional[int] = None
                           ) -> GradSyncProgram:
    """Compile the epoch's schedule into a shard_map train step.

    ``stacked=True`` takes per-worker batches stacked on a leading team
    axis (leaves ``(n, B, S)``); ``stacked=False`` shards a global batch
    (leaves ``(B, S)``, ``B % n == 0``) over the data axis.

    ``overlap="pipelined"`` runs the sync per readiness group through
    the double-buffered executor; ``microbatches > 1`` unrolls the
    grad-accumulation loop with one bucket stream per microbatch (each
    microbatch's sync overlaps the next microbatch's backward);
    ``block_groups=K`` splits the stacked-blocks group into K scan-row
    sub-groups (last rows first — the backward scan's emission order) so
    the pipelined overlap deepens past the 3 coarse readiness classes.
    The overlap modes are bitwise-equal at fixed ``microbatches`` for
    any grouping: grouping only partitions the buffer, never the
    per-element combine sequence.
    """
    assert overlap in OVERLAP_MODES, overlap
    assert microbatches >= 1, microbatches
    mesh = mesh_for(pc, devices)
    layout = make_layout(api.param_spec(), bucket_elems=bucket_elems,
                         block_groups=block_groups or 1)
    axis = pc.axis_name

    def sync(grads, flag):
        """One bucket-stream all-reduce; returns per-group buffers."""
        if overlap == "pipelined":
            bufs = layout.flatten_groups(grads, flag)
            return execute_flat_pipelined(bufs, pc, fused=fused,
                                          interpret=interpret)
        flat = execute_flat(layout.flatten(grads, flag), pc,
                            fused=fused, interpret=interpret)
        return [flat]

    def unflatten(bufs):
        if overlap == "pipelined":
            return layout.unflatten_groups(bufs)
        return layout.unflatten(bufs[0])

    def worker(params, opt_state, batch, alive):
        if stacked:
            batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        a = alive[0]

        def mb_grads(b):
            (_, metrics), grads = jax.value_and_grad(
                api.loss_fn, has_aux=True)(params, b, remat=remat)
            grads = jax.tree_util.tree_map(
                lambda g: g * a.astype(g.dtype), grads)
            return metrics, grads

        if microbatches == 1:
            metrics, grads = mb_grads(batch)
            synced = sync(grads, a)
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(microbatches,
                                    x.shape[0] // microbatches,
                                    *x.shape[1:]), batch)
            synced = None
            loss = aux = jnp.zeros((), jnp.float32)
            # unrolled (not scan): microbatch k's collective chain has
            # no dependency on microbatch k+1's backward, so the two
            # overlap inside the compiled step. The flag rides each
            # stream at a/M — the reduced count stays n_alive.
            for k in range(microbatches):
                b = jax.tree_util.tree_map(lambda x: x[k], mbs)
                m, grads = mb_grads(b)
                loss = loss + m["loss"]
                aux = aux + m.get("aux", jnp.zeros(()))
                red = sync(grads, a / microbatches)
                synced = red if synced is None else \
                    [s + r for s, r in zip(synced, red)]
            metrics = {"loss": loss / microbatches,
                       "aux": aux / microbatches}
        grads, count = unflatten(synced)
        inv = 1.0 / jnp.maximum(count, 1.0)
        if microbatches > 1:
            inv = inv / microbatches
        grads = jax.tree_util.tree_map(
            lambda g: g * inv.astype(g.dtype), grads)
        new_p, new_o, om = opt.update(grads, opt_state, params)
        pm = {"loss": metrics["loss"] * a,
              "aux": metrics.get("aux", jnp.zeros(())) * a,
              "alive": a, **om}
        pm = {k: jnp.asarray(v, jnp.float32).reshape(1)
              for k, v in pm.items()}
        return new_p, new_o, pm

    sm = jax.shard_map(worker, mesh=mesh,
                       in_specs=(P(), P(), P(axis), P(axis)),
                       out_specs=(P(), P(), P(axis)),
                       check_vma=False)
    jitted = jax.jit(sm, donate_argnums=(0, 1) if donate else ())
    st = pc.stats()
    meta = {"team": pc.n, "sync_rounds": st["rounds"],
            "sync_messages": st["messages"],
            "overlap": int(overlap == "pipelined"),
            "bucket_groups": layout.n_groups,
            "microbatches": microbatches}
    return GradSyncProgram(key=(pc.keys, pc.kind, pc.seed, pc.p,
                                overlap, microbatches),
                           pc=pc, mesh=mesh,
                           layout=layout, jitted=jitted, stacked=stacked,
                           meta=meta)


@dataclass
class HierSyncProgram:
    """Two-level gradient sync for the multi-host runtime (DESIGN.md
    §11). Level 0 reduces one process's M local device shards inside a
    ``shard_map`` (the local collective); level 1 runs the *process-
    level* schedule — derived from the same skip-list oracle, over the
    live process keys — as real transport messages between processes.
    Only the flat bucket buffer crosses the process boundary; the two
    jitted halves stay device-resident:

      ``local_grads``: (params, opt, batch, alive) -> (flat, pm) — per-
          device grads, flattened with the alive flag, locally reduced
          so every local device (hence the host copy) holds the
          process-partial sum;
      ``apply``: (params, opt, flat) -> (params, opt, pm) — unflatten
          the *globally* reduced buffer, masked-mean by the reduced
          alive count (= live processes x M), optimizer update.

    Identical reduced buffers on every process keep params replicated
    across hosts with zero parameter traffic. ``key`` is keyed by the
    process-level collective: the cache entry a surviving host
    re-commits at each churn epoch boundary."""

    key: tuple
    pc_proc: PhaserCollective     # process-level collective (epoch id)
    pc_local: PhaserCollective    # local M-device collective
    mesh: Mesh
    layout: BucketLayout
    local_grads: Callable
    apply: Callable
    meta: Dict[str, int] = field(default_factory=dict)

    @property
    def proc_schedule(self):
        """The round schedule the owning process executes over the
        transport (add rounds reduce, copy rounds hydrate)."""
        return self.pc_proc.unified_schedule()

    def _replicated(self, tree):
        sh = jax.sharding.NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: x if getattr(x, "sharding", None) == sh
            else jax.device_put(x, sh), tree)

    def bind_state(self, params, opt_state):
        return params, opt_state

    def readout_state(self, params, opt_state):
        return params, opt_state

    def reduce_metrics(self, pm, extra=None):
        return reduce_worker_metrics(pm, {**self.meta, **(extra or {})})


def build_hier_gradsync_program(api, opt, pc_proc: PhaserCollective, *,
                                local_devices: Sequence,
                                local_kind: str = "phaser_scsl",
                                remat: bool = False,
                                fused: bool = True,
                                interpret: Optional[bool] = None,
                                bucket_elems: Optional[int] = None
                                ) -> HierSyncProgram:
    """Compile one churn epoch's hierarchical sync for one process.

    ``pc_proc`` spans the live *process* keys (the epoch identity);
    the local level is a fresh collective over ``range(M)`` for this
    process's ``local_devices`` — identical on every host, so the
    programs only differ by their slice of the batch. ``pc_proc.kind``
    must be a whole-buffer round schedule (``phaser_scsl`` or
    ``recursive_doubling``): the cross-process rounds are executed by
    the transport, not by XLA."""
    assert pc_proc.unified_schedule() is not None, \
        f"process-level kind {pc_proc.kind!r} is not a round schedule"
    m = len(local_devices)
    pc_local = PhaserCollective(m, pc_proc.axis_name, kind=local_kind,
                                seed=pc_proc.seed)
    mesh = mesh_for(pc_local, local_devices)
    layout = make_layout(api.param_spec(), bucket_elems=bucket_elems)
    axis = pc_local.axis_name

    def grads_worker(params, opt_state, batch, alive):
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        a = alive[0]
        (_, metrics), grads = jax.value_and_grad(
            api.loss_fn, has_aux=True)(params, batch, remat=remat)
        grads = jax.tree_util.tree_map(
            lambda g: g * a.astype(g.dtype), grads)
        flat = execute_flat(layout.flatten(grads, a), pc_local,
                            fused=fused, interpret=interpret)
        pm = {"loss": metrics["loss"] * a, "alive": a}
        pm = {k: jnp.asarray(v, jnp.float32).reshape(1)
              for k, v in pm.items()}
        return flat[None], pm

    sm = jax.jit(jax.shard_map(grads_worker, mesh=mesh,
                               in_specs=(P(), P(), P(axis), P(axis)),
                               out_specs=(P(axis), P(axis)),
                               check_vma=False))

    def local_grads(params, opt_state, batch, alive):
        stacked_flat, pm = sm(params, opt_state, batch, alive)
        # every local rank holds the same locally-reduced buffer
        return stacked_flat[0], pm

    def apply_worker(params, opt_state, flat):
        grads, count = layout.unflatten(flat)
        inv = 1.0 / jnp.maximum(count, 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: g * inv.astype(g.dtype), grads)
        new_p, new_o, om = opt.update(grads, opt_state, params)
        om = {k: jnp.asarray(v, jnp.float32) for k, v in om.items()}
        return new_p, new_o, om

    st = pc_proc.stats()
    lst = pc_local.stats()
    meta = {"team": pc_proc.n * m, "processes": pc_proc.n,
            "local_devices": m,
            "sync_rounds": st["rounds"] + lst["rounds"],
            "sync_messages": st["messages"] * m + lst["messages"]}
    return HierSyncProgram(
        key=(pc_proc.keys, pc_proc.kind, pc_proc.seed, pc_proc.p,
             "hier", m, local_kind),
        pc_proc=pc_proc, pc_local=pc_local, mesh=mesh, layout=layout,
        local_grads=local_grads, apply=jax.jit(apply_worker),
        meta=meta)


def build_allreduce_program(pc: PhaserCollective, spec, *,
                            devices: Optional[Sequence] = None,
                            fused: bool = True,
                            interpret: Optional[bool] = None) -> Callable:
    """Compile a bare bucketed all-reduce: ``(n, *spec.shape)`` stacked
    per-rank values -> the same, every rank holding the reduced sum."""
    mesh = mesh_for(pc, devices)
    layout = make_layout({"x": spec})

    def worker(x):
        flat = layout.flatten({"x": x[0].astype(jnp.float32)},
                              jnp.float32(1.0))
        flat = execute_flat(flat, pc, fused=fused, interpret=interpret)
        tree, _ = layout.unflatten(flat)
        return tree["x"][None].astype(x.dtype)

    return jax.jit(jax.shard_map(worker, mesh=mesh,
                                 in_specs=P(pc.axis_name),
                                 out_specs=P(pc.axis_name),
                                 check_vma=False))
