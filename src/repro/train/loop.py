"""Training loop: phaser-coordinated, fault-tolerant, checkpointable.

The control plane is a DistPhaser over the (simulated) worker group: every
step is one phaser phase — workers signal when their step (gradient
contribution) completes; the phase advances when all live signalers have
signaled. Elastic events map onto the paper's protocol exactly
(runtime_elastic.elastic_phaser): joins are eager, schedule re-derivation
lands lazily as a new epoch at the next phase boundary, failures are
deletions. When an ``ElasticPhaserRuntime`` is attached, the loop
re-lowers its compiled step at every epoch boundary (the schedule is part
of the step's static identity) and saves a checkpoint first, so a crash
mid-re-lower resumes into a consistent (params, epoch) pair.

With multiple devices available (``device_collective`` auto/True), the
per-epoch step is the execution engine's compiled shard_map program: the
global batch is sharded over the epoch's mesh axis and gradients sync
through the schedule's ppermute rounds on device. Programs come from an
epoch-aware cache keyed by (member_set, kind) plus the overlap config
(``overlap_sync`` compiles the pipelined programs of DESIGN.md §5 —
reverse-topo bucket groups synced while the backward runs, microbatch
streams interleaved), so a boundary that revisits a team swaps back to
an already-compiled executable. Every checkpoint carries the live
program-cache key, so a resume pre-compiles the exact epoch program
before step 1 instead of paying the first-step compile after restore.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..data import SyntheticLM
from ..models.registry import ModelAPI
from ..obs import timeline as obs_timeline
from ..obs.metrics import MetricsRegistry
from ..optim import AdamW
from ..runtime_elastic.elastic_phaser import ElasticPhaserRuntime
from ..utils import to_device_copy
from .step import build_train_step


@dataclass
class TrainLoop:
    api: ModelAPI
    opt: AdamW
    data: SyntheticLM
    ckpt: Optional[CheckpointManager] = None
    ckpt_every: int = 50
    remat: bool = False
    microbatches: int = 1
    log_every: int = 10
    metrics_log: List[Dict] = field(default_factory=list)
    # --- elastic control plane (optional) --------------------------------
    runtime: Optional[ElasticPhaserRuntime] = None
    # step -> list of ("join", None) | ("leave", wid|None) | ("fail", wid|None)
    elastic_events: Dict[int, List] = field(default_factory=dict)
    epoch_log: List[Dict] = field(default_factory=list)
    # device-collective data plane: None = auto (on when >1 device and the
    # batch divides the team), True = required, False = host/XLA path
    device_collective: Optional[bool] = None
    # overlapped gradient sync (device path): pipeline bucket-group
    # rounds against the backward pass / microbatch streams (DESIGN.md §5)
    overlap_sync: bool = False
    # pipeline parallelism (device path): shard the stacked blocks over
    # a stage axis and run the 1F1B wave schedule on a 2-D (stage, data)
    # mesh; ``microbatches`` is the pipeline depth M (DESIGN.md §6)
    pipeline_stages: int = 1
    # interleaved virtual stages: each device owns ``interleave``
    # non-contiguous model chunks and runs the interleaved 1F1B order —
    # bubble fraction (S-1)/(vM+S-1) instead of (S-1)/(M+S-1); needs
    # microbatches % pipeline_stages == 0 (DESIGN.md §6)
    interleave: int = 1
    # obs plane (optional): an active ``timeline`` receives wall-clock
    # step/relower spans plus the logical schedule grids the executors
    # emit at trace time; ``metrics`` shards relower counts and cache hits
    timeline: Optional[obs_timeline.Timeline] = None
    metrics: Optional[MetricsRegistry] = None
    _progs: Any = field(default=None, init=False, repr=False)

    @property
    def programs(self) -> List:
        """The epoch programs of the device-collective path, least
        recently used first (empty on the plain jit path)."""
        if self._progs is None:
            return []
        return [ts.program for ts in self._progs.programs()]

    @property
    def _overlap_mode(self) -> str:
        return "pipelined" if self.overlap_sync else "eager"

    def _apply_elastic_events(self, step: int) -> None:
        for kind, arg in self.elastic_events.get(step, []):
            if kind == "join":
                self.runtime.request_join(arg, step=step)
                continue
            live = self.runtime.live
            if arg is None:
                if not live:
                    raise ValueError(f"elastic event {kind}@{step}: no "
                                     "live workers left to remove")
                wid = max(live)
            elif arg not in live:
                raise ValueError(f"elastic event {kind}:{arg}@{step}: "
                                 f"worker {arg} is not live "
                                 f"(live={sorted(live)})")
            else:
                wid = arg
            self.runtime.request_leave(wid, fail=(kind == "fail"),
                                       step=step)

    def _replay_elastic_events(self, upto: int) -> None:
        """Resume path: the runtime is reconstructed by replaying the
        churn schedule through the real protocol up to the restored
        step, so the live set and epoch index match the pre-crash run
        (phase counters restart; they are not part of the checkpoint
        contract). Only a fresh runtime is replayed — a pre-churned one
        passed in by the caller is taken as already positioned."""
        if self.runtime.events:
            return
        for s in sorted(k for k in self.elastic_events if k < upto):
            self._apply_elastic_events(s)
            self.runtime.advance(step=s)

    def _collective_devices(self, pc) -> Optional[List]:
        """Devices for the device-collective path, or None for the
        host/XLA path. Auto mode requires >1 device, enough of them for
        the team (x stages on the 2-D pipeline path), and a batch the
        team (and per-rank microbatching) divides."""
        if self.device_collective is False or pc is None:
            if self.pipeline_stages > 1 or self.interleave > 1:
                raise ValueError("pipeline_stages/interleave > 1 "
                                 "require the device-collective path")
            return None
        devs = jax.devices()
        need = pc.n * max(self.pipeline_stages, 1)
        ok = (len(devs) >= need and pc.n >= 1
              and self.data.batch % pc.n == 0
              and (self.data.batch // pc.n) % self.microbatches == 0)
        if (self.device_collective is True or self.pipeline_stages > 1
                or self.interleave > 1):
            assert ok, (f"device_collective requested but team={pc.n}, "
                        f"stages={self.pipeline_stages}, "
                        f"devices={len(devs)}, batch={self.data.batch}, "
                        f"microbatches={self.microbatches}")
            return devs
        return devs if ok and len(devs) > 1 else None

    def _ensure_progs(self):
        """The epoch-aware program cache (device-collective path); the
        overlap/microbatch config rides the cache key."""
        if self._progs is None:
            from ..collective_exec import ProgramCache
            self._progs = ProgramCache(
                lambda c: build_train_step(
                    self.api, self.opt, rules=None, remat=self.remat,
                    microbatches=self.microbatches, donate=False,
                    collective=c, collective_devices=jax.devices(),
                    overlap=self._overlap_mode,
                    pipeline_stages=self.pipeline_stages,
                    interleave=self.interleave),
                extra_key=(self._overlap_mode, self.microbatches,
                           self.pipeline_stages, self.interleave),
                metrics=self.metrics)
        return self._progs

    def _build_step(self):
        pc = (self.runtime.epoch.collective
              if self.runtime is not None else None)
        devs = self._collective_devices(pc)
        if devs is not None:
            return self._ensure_progs().get(pc)
        return build_train_step(self.api, self.opt, rules=None,
                                remat=self.remat,
                                microbatches=self.microbatches,
                                donate=False, collective=pc)

    # ------------------------------------------------- program-key ckpt
    def _program_key(self) -> Optional[Dict]:
        """Checkpointable identity of the current epoch's compiled
        program (member set, kind, seed/p, overlap config) — written
        into every checkpoint manifest so a resume can pre-compile the
        exact program before step 1."""
        if self.runtime is None or self._progs is None:
            return None
        key = self.runtime.epoch_key()
        if key is None:
            return None
        # single-process run: manifest schema matches the multi-host
        # agents, which record the surviving process set (runtime_dist)
        return {"process_set": [0], **key, "overlap": self._overlap_mode,
                "microbatches": self.microbatches,
                "pipeline_stages": self.pipeline_stages,
                "interleave": self.interleave}

    def _precompile_from_key(self, pk: Optional[Dict]) -> None:
        """Resume path: rebuild the checkpointed epoch's collective and
        compile (or cache-hit) its program before the first step."""
        if not pk or self.device_collective is False:
            return
        # config changed since the save (overlap mode, microbatching,
        # sync kind or seed): the replayed epoch would never cache-hit
        # this program, so skip rather than compile a dead executable
        if (pk.get("overlap") != self._overlap_mode
                or pk.get("microbatches") != self.microbatches
                or pk.get("pipeline_stages", 1) != self.pipeline_stages
                or pk.get("interleave", 1) != self.interleave
                or (self.runtime is not None
                    and (pk.get("kind") != self.runtime.kind
                         or pk.get("seed") != self.runtime.seed))):
            return
        from ..core.collective import PhaserCollective
        keys = tuple(pk["member_set"])
        pc = PhaserCollective(len(keys), pk.get("axis", "data"),
                              kind=pk["kind"], seed=pk["seed"],
                              p=pk["p"], keys=keys,
                              leaf_keys=tuple(pk.get("leaf_keys", ())))
        if self._collective_devices(pc) is not None:
            self._ensure_progs().get(pc)

    def _to_canonical(self, ts, params, opt_state):
        """Carried state -> canonical layer order (identity except for
        the interleaved pipeline program's device-major layout)."""
        prog = getattr(ts, "program", None)
        if prog is not None:
            return prog.readout_state(params, opt_state)
        return params, opt_state

    def _to_carried(self, ts, params, opt_state):
        """Canonical state -> the program's carried layout. For the
        interleaved pipeline this is the one permute paid at bind /
        restore; the layout depends only on (stages, interleave, rows
        per chunk), so epoch swaps under data-axis churn reuse the
        carried state without conversion."""
        prog = getattr(ts, "program", None)
        if prog is not None:
            return prog.bind_state(params, opt_state)
        return params, opt_state

    def run(self, steps: int, *, params=None, opt_state=None,
            resume: bool = False, on_step: Optional[Callable] = None):
        if self.timeline is not None:
            # active for the whole run: build-time/trace-time emitters
            # in the executors reach it via the module hook
            obs_timeline.activate(self.timeline)
        ts = self._build_step()
        start = 0
        if params is None:
            params = self.api.init_params(jax.random.key(0))
        if opt_state is None:
            opt_state = self.opt.init(params)
        if resume and self.ckpt is not None and self.ckpt.latest_step():
            # pre-compile the checkpointed epoch's program BEFORE the
            # params restore and event replay: resume reaches step 1
            # with the exact program already executable (cache hit at
            # the re-lower below)
            self._precompile_from_key(self.ckpt.program_key())
            tpl = {"params": params, "opt": opt_state._asdict()}
            start, tree, extra = self.ckpt.restore(tpl)
            params = tree["params"]
            from ..optim import OptState
            opt_state = OptState(**tree["opt"])
            if "data" in extra:
                self.data.load_state_dict(extra["data"])
            if self.runtime is not None:
                self._replay_elastic_events(start)
                ts = self._build_step()     # re-lower for the epoch

        # carried state: the program's own layout (device-major for the
        # interleaved pipeline) — converted once here, carried verbatim
        # through steps and epoch swaps, read out at save/return
        params, opt_state = self._to_carried(ts, params, opt_state)

        for step in range(start, steps):
            if self.runtime is not None:
                self._apply_elastic_events(step)
            batch = next(self.data)
            # snapshot into fresh device buffers: jnp.asarray on a host
            # buffer may alias it and read asynchronously (see utils)
            batch = {k: to_device_copy(v) for k, v in batch.items()}
            t0 = time.time()
            tp0 = (self.timeline.now() if self.timeline is not None
                   else 0.0)
            if ts.program is not None:
                # per-worker alive mask: a worker that left mid-epoch
                # contributes zeros; the program's masked mean re-scales
                ep = self.runtime.epoch
                alive = jnp.asarray([1.0 if w in self.runtime.live else 0.0
                                     for w in ep.live], jnp.float32)
                params, opt_state, metrics = ts.jitted(params, opt_state,
                                                       batch, alive)
            else:
                params, opt_state, metrics = ts.jitted(params, opt_state,
                                                       batch)
            if self.timeline is not None:
                self.timeline.complete("train.step", tp0,
                                       args={"step": step})
            if self.runtime is not None:
                # the step is one phaser phase; churn requested above
                # lands as a new epoch exactly at this boundary
                before = self.runtime.epoch.index
                released = self.runtime.advance(step=step)
                ep = self.runtime.epoch
                if ep.index != before:
                    # checkpoint-consistent swap: persist, then re-lower
                    if self.ckpt is not None:
                        cp, co = self._to_canonical(ts, params, opt_state)
                        self.ckpt.save(step + 1, cp, co,
                                       extra={"data":
                                              self.data.state_dict()},
                                       program_key=self._program_key())
                    tb = (self.timeline.now()
                          if self.timeline is not None else 0.0)
                    ts = self._build_step()
                    if self.timeline is not None:
                        self.timeline.complete("epoch.relower", tb,
                                               args={"epoch": ep.index})
                    if self.metrics is not None:
                        self.metrics.inc("train.relower")
                    self.runtime.verify_epoch()
                    if self.pipeline_stages > 1 or self.interleave > 1:
                        # the stage axis's own proof: the (interleaved)
                        # 1F1B wave order against the real p2p actors
                        from ..pipeline_exec import (derive_interleaved,
                                                     verify_phase_order)
                        verify_phase_order(derive_interleaved(
                            self.pipeline_stages, self.microbatches,
                            self.interleave))
                    self.epoch_log.append({
                        "step": step, "phase": released,
                        "epoch": ep.index, "live": list(ep.live),
                        "kind": ep.kind, **ep.stats()})
            if step % self.log_every == 0 or step == steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["dt"] = time.time() - t0
                if self.runtime is not None:
                    m["epoch"] = self.runtime.epoch.index
                    m["live"] = len(self.runtime.live)
                self.metrics_log.append(m)
            if self.ckpt is not None and (step + 1) % self.ckpt_every == 0:
                cp, co = self._to_canonical(ts, params, opt_state)
                self.ckpt.save(step + 1, cp, co,
                               extra={"data": self.data.state_dict()},
                               program_key=self._program_key())
            if on_step is not None:
                on_step(step, params, metrics)
        # read the carried state out to the canonical layer order — the
        # loop's return contract (and the final checkpoint) never see
        # the device-major placement
        params, opt_state = self._to_canonical(ts, params, opt_state)
        if self.ckpt is not None:
            self.ckpt.save(steps, params, opt_state,
                           extra={"data": self.data.state_dict()},
                           program_key=self._program_key())
            self.ckpt.wait()
        if self.timeline is not None:
            obs_timeline.deactivate()
        return params, opt_state
