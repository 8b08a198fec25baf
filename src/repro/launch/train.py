"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

``--reduced`` trains the family-reduced config on CPU (the end-to-end
example path); full configs target real accelerators with the same code.

Elastic mode attaches the phaser-epoch control plane
(runtime_elastic.elastic_phaser) and drives membership churn from a
schedule of events, e.g.:

  ... --workers 4 --elastic "join@30,join@35,fail@60,leave@80"

Each event is ``kind@step`` (kind: join | leave | fail; leave/fail may
pin a worker with ``kind:wid@step``). The loop re-lowers its compiled
step at every epoch boundary and prints the epoch log.

``--host-devices 8`` splits the host CPU into a simulated 8-device mesh
(must be the first thing to touch jax, so it is applied before any
device use) and ``--device-collective`` forces gradient sync through the
execution engine's compiled shard_map programs; by default the engine is
used automatically whenever more than one device is visible and the
batch divides the team. ``--overlap-sync`` compiles the pipelined
programs (DESIGN.md §5): reverse-topo bucket groups sync while the
backward pass still runs, and with ``--microbatches N`` each
microbatch's bucket stream overlaps the next microbatch's backward.

``--pipeline-stages S`` (DESIGN.md §6) compiles the 2-D program
instead: the stacked blocks shard over a stage axis
(workers x S devices), microbatches flow through the wave-synchronous
1F1B schedule derived from the point-to-point phaser graph, and each
stage row syncs gradients over the data axis through the epoch's
collective schedule — churn re-derives both at the same boundary.
``--interleave v`` runs the INTERLEAVED 1F1B order: each device owns v
non-contiguous model chunks, cutting the pipeline bubble fraction from
(S-1)/(M+S-1) to (S-1)/(vM+S-1); requires the scan length to divide by
S*v and ``--microbatches`` to divide by S.

``--processes N`` (DESIGN.md §11) runs the MULTI-HOST elastic runtime
instead: N logical host processes, each owning a slice of the visible
devices, the phaser skip list partitioned over them (coordinator owns
HEAD), and gradient sync running hierarchically — local shard_map
reduce inside each process, the process-level phaser schedule between
them. Elastic events then churn whole hosts:

  ... --host-devices 4 --processes 2 --elastic "join@4,fail:1@8"

(a joining host needs spare devices: leave ``host-devices`` headroom
or churn down first). Checkpoints record the surviving process set in
the manifest so ``--resume`` pre-compiles the surviving-host program.
"""
from __future__ import annotations

import argparse
import json
import math
import os

import jax

from ..checkpoint import CheckpointManager
from ..data import SyntheticLM
from ..models.registry import get_api, get_config
from ..optim import AdamW
from ..runtime_elastic import ElasticPhaserRuntime
from ..train.loop import TrainLoop
from ..utils import enable_compile_cache


def parse_elastic(spec: str):
    """'join@30,fail@60,leave:2@80' -> {30: [("join", None)], ...}.

    ``kill`` (``--processes`` mode only) is a hard crash: the host is
    SIGKILLed (socket fabric) or dropped without protocol (in-process),
    and the coordinator must *detect* and recover non-cooperatively —
    unlike ``fail``, which still runs the cooperative eviction."""
    events = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if "@" not in item:
            raise ValueError(f"elastic event {item!r}: expected kind@step "
                             "(e.g. join@30, leave:2@80)")
        kind, step = item.split("@", 1)
        wid = None
        if ":" in kind:
            kind, w = kind.split(":", 1)
            wid = int(w)
        if kind not in ("join", "leave", "fail", "kill"):
            raise ValueError(f"elastic event kind {kind!r}: expected "
                             "join | leave | fail | kill")
        events.setdefault(int(step), []).append((kind, wid))
    return events


def run_processes(args, ap):
    """--processes N: the multi-host elastic runtime. With the default
    in-process fabric each logical host owns ndev/N device slices of
    this jax runtime; with ``--fabric socket`` each host is a real OS
    process with its own jax runtime (and the coordinator runs the
    heartbeat failure detector). Churn happens at whole-host
    granularity; ``kill`` events crash hosts non-cooperatively."""
    from ..runtime_dist import (DistCoordinator, InprocCluster,
                                SocketCluster, StepInconsistent)
    n = args.processes
    chaos = None
    if args.chaos is not None:
        from ..runtime_dist import ChaosConfig
        chaos = ChaosConfig(seed=args.chaos, p_reset=args.chaos_reset)
    elif args.chaos_reset > 0:
        # reset storms without the RPC drop/dup/delay chaos: exercises
        # the session layer in isolation
        from ..runtime_dist import ChaosConfig
        chaos = ChaosConfig(seed=13, p_drop=0.0, p_dup=0.0, p_delay=0.0,
                            p_reset=args.chaos_reset)
    link_faults = {}
    if args.chaos_links is not None:
        if args.fabric not in ("socket", "tcp"):
            ap.error("--chaos-links needs --fabric socket|tcp")
        from ..runtime_dist import parse_link_spec
        try:
            for f in parse_link_spec(args.chaos_links):
                link_faults.setdefault(f["step"], []).append(f)
        except ValueError as e:
            ap.error(str(e))
    slot_of = {}
    if args.fabric in ("socket", "tcp"):
        m = max(1, args.host_devices or 1)   # devices per host process
        per_dev_batch = max(1, args.batch // (n * m))

        def data_for(pid):
            return {"arch": args.arch, "reduced": args.reduced,
                    "layers": args.layers, "batch": per_dev_batch,
                    "seq": args.seq, "lr": args.lr,
                    "warmup": min(20, args.steps // 5),
                    "steps": args.steps, "devices": m,
                    "ckpt_dir": args.ckpt_dir,
                    "local_kind": "phaser_scsl"}

        cluster = SocketCluster(hb_interval=args.heartbeat_interval,
                                failure_timeout=args.failure_timeout,
                                chaos=chaos,
                                fabric=("tcp" if args.fabric == "tcp"
                                        else "unix"))
    else:
        ndev = len(jax.devices())
        if ndev < n:
            ap.error(f"--processes {n} needs at least {n} devices "
                     f"(have {ndev}; use --host-devices)")
        m = ndev // n
        slots = ndev // m                   # slice headroom for joins
        per_dev_batch = max(1, args.batch // (n * m))

        def data_for(pid):
            if pid not in slot_of:
                used = set(slot_of.values())
                free = [i for i in range(slots) if i not in used]
                if not free:
                    raise ValueError(f"no free device slice for host "
                                     f"{pid} ({slots} slices of {m} "
                                     "devices)")
                slot_of[pid] = free[0]
            return {"arch": args.arch, "reduced": args.reduced,
                    "layers": args.layers, "batch": per_dev_batch,
                    "seq": args.seq, "lr": args.lr,
                    "warmup": min(20, args.steps // 5),
                    "steps": args.steps,
                    "devices": ndev,
                    "device_slice": [slot_of[pid] * m, m],
                    "ckpt_dir": args.ckpt_dir,
                    "local_kind": "phaser_scsl"}

        cluster = InprocCluster(chaos=chaos)

    events = {}
    if args.elastic is not None:
        try:
            events = parse_elastic(args.elastic)
        except ValueError as e:
            ap.error(str(e))
    obs = bool(args.trace or args.metrics_out or args.live_out)
    rt = DistCoordinator(cluster, n, seed=args.seed,
                         proc_kind=args.sync_kind, data_for=data_for,
                         obs=obs, live_out=args.live_out,
                         flight_dir=args.flight_dir)
    if args.fabric in ("socket", "tcp"):
        for pid, plat in sorted(rt.cluster.platforms.items()):
            print(f"# host process {pid}: jax platform {plat}")
    start = 0
    if args.resume and args.ckpt_dir:
        mk = rt.cluster.call(min(rt.live),
                             {"op": "manifest_key"})["program_key"]
        if mk is not None:
            # the manifest records the process set live at save time;
            # a naive restart boots the original set — shed the rest
            # so resume pre-compiles the surviving-host program
            for pid in sorted(set(rt.live) - set(mk["process_set"])):
                rt.request_leave(pid, step=0)
                slot_of.pop(pid, None)
            out = rt.resume()
            start = out["step"]
            print(f"# resumed at step {start}; manifest process_set="
                  f"{mk['process_set']} compiled={out['compiled']}")
    metrics = []
    for step in range(start, args.steps):
        for f in link_faults.get(step, []):
            # bounded wall-clock window with local auto-heal timers at
            # every endpoint: the heal fires even while the partition
            # stalls this very loop
            rt.cluster.inject_link_fault(
                f["a"], f["b"], duration=f["dur"], oneway=f["oneway"])
            print(f"# step {step}: link fault "
                  f"{f['a']}{'->' if f['oneway'] else '|'}"
                  f"{f['b'] if f['b'] is not None else '*'} "
                  f"for {f['dur']}s")
        for kind, wid in events.get(step, []):
            if kind == "join":
                rt.request_join(step=step)
            elif kind == "kill":
                # hard crash: no protocol, no goodbye — the coordinator
                # must detect the silence and evict non-cooperatively
                victim = wid if wid is not None else max(rt.live)
                if hasattr(rt.cluster, "kill_pid"):
                    rt.cluster.kill_pid(victim)
                else:
                    rt.cluster.kill_host(victim)
                slot_of.pop(victim, None)
            else:
                victim = wid if wid is not None else max(rt.live)
                rt.request_leave(victim, fail=(kind == "fail"),
                                 step=step)
                slot_of.pop(victim, None)   # slice freed for later joins
        t0 = rt.obs.timeline.now() if obs else 0.0
        try:
            out = rt.train_step(step)
        except StepInconsistent as e:
            # params diverged across survivors: only a checkpoint-
            # consistent resume restores the replicated invariant
            if not args.ckpt_dir:
                raise
            rep = rt.resume()
            print(f"# step {step}: {e}; resumed from checkpoint at "
                  f"step {rep['step']}")
            out = rt.train_step(step)
        rt.advance(step=step)
        if obs:
            rt.obs.timeline.complete("train.step", t0,
                                     args={"step": step,
                                           "hosts": len(rt.live)})
        loss = sum(r["loss"] for r in out.values()) / len(out)
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            metrics.append({"step": step, "loss": loss,
                            "hosts": len(rt.live),
                            "epoch": rt.epoch.index})
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            rt.save_checkpoint(step + 1)
    if args.ckpt_dir:
        rt.save_checkpoint(args.steps)
    st = rt.control_stats()
    for mrow in metrics:
        print(json.dumps(mrow))
    print(json.dumps({"control_plane": {
        "live": st["live"], "epochs": rt.epoch.index + 1,
        "remote_frames": st["remote_frames"],
        "critical_path": st["critical_path"],
        "events": [[e.step, e.kind, e.pid] for e in rt.events]}}))
    rt.close()                       # final obs collection rides close()
    if obs:
        rt.export_obs(args.trace, args.metrics_out)
        print(json.dumps({"obs": rt.obs.summary()}))
    if not metrics:
        print("# no steps to run (checkpoint already at --steps)")
        return 0
    first, last = metrics[0]["loss"], metrics[-1]["loss"]
    print(f"# loss {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first else 'NOT DECREASED'})")
    # a short resume tail (a couple of steps after the checkpoint) is
    # loss noise on the reduced configs — gate those on finiteness only
    if len(metrics) < 4:
        return 0 if math.isfinite(last) else 1
    return 0 if last < first else 1


def main(argv=None):
    return run(argv)[0]


def run(argv=None):
    """Parse ``argv`` and train; returns ``(exit code, TrainLoop)`` (the
    loop is None with ``--processes``), so callers can read the metrics
    and the epoch programs of the run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override the config's layer count (e.g. to "
                         "make the scan axis divide stages*interleave)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10,
                    help="record (and print) the metrics of every Nth "
                         "step, and of the last")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=4,
                    help="initial elastic worker-group size")
    ap.add_argument("--elastic", default=None,
                    help='churn schedule, e.g. "join@30,fail@60"')
    ap.add_argument("--sync-kind", default="phaser_scsl",
                    choices=["phaser_scsl", "recursive_doubling",
                             "halving_doubling", "xla_psum"],
                    help="per-epoch gradient-sync schedule (every kind "
                         "now covers non-power-of-two teams)")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="split the host into N simulated devices "
                         "(XLA_FLAGS; must precede first jax device use)")
    ap.add_argument("--device-collective", action="store_true",
                    help="require gradient sync through the compiled "
                         "shard_map engine (default: auto)")
    ap.add_argument("--overlap-sync", action="store_true",
                    help="pipeline gradient sync against the backward "
                         "pass (reverse-topo bucket groups, "
                         "double-buffered rounds; device path only)")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="pipeline parallelism: shard the stacked "
                         "blocks over a stage axis and run the 1F1B "
                         "wave schedule on a 2-D (stage x data) mesh; "
                         "needs workers*stages devices and "
                         "--microbatches as the pipeline depth "
                         "(device path only)")
    ap.add_argument("--processes", type=int, default=1,
                    help="multi-host elastic runtime: N logical host "
                         "processes, each owning ndev/N devices; the "
                         "skip-list control plane partitions over them "
                         "and gradient sync runs hierarchically (local "
                         "shard_map reduce, then the process-level "
                         "schedule). Elastic events churn whole hosts.")
    ap.add_argument("--fabric", default="inproc",
                    choices=["inproc", "socket", "tcp"],
                    help="--processes transport: in-process logical "
                         "hosts (deterministic), real OS processes "
                         "over AF_UNIX sockets, or real processes over "
                         "TCP loopback (host:port registry files; same "
                         "session layer + failure detection)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject seeded transport faults (RPC drop/dup "
                         "+ bounded env delay/reorder; DESIGN.md §13)")
    ap.add_argument("--chaos-links", default=None, metavar="SPEC",
                    help="link-level chaos on the socket fabrics: "
                         "'A|B@STEP+DUR' (symmetric partition between "
                         "pid sets, healing after DUR seconds) or "
                         "'A->B@STEP+DUR' (one-way link kill); "
                         "';'-separated, '-1'/'coord' = coordinator, "
                         "'*' = everyone else. A window shorter than "
                         "--failure-timeout must heal with zero "
                         "evictions (DESIGN.md §15)")
    ap.add_argument("--chaos-reset", type=float, default=0.0,
                    metavar="P",
                    help="socket fabrics: per-frame probability of a "
                         "connection reset injected on cmd/env sends "
                         "(the session layer must reconnect + replay; "
                         "usable without --chaos)")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5,
                    help="socket fabric: coordinator heartbeat period "
                         "(seconds)")
    ap.add_argument("--failure-timeout", type=float, default=10.0,
                    help="socket fabric: hard silence floor before a "
                         "host is declared dead")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(wall-clock step/boundary spans + the compiled "
                         "programs' logical schedule grids); with "
                         "--processes the control plane's span log lands "
                         "in a sibling .spans.jsonl")
    ap.add_argument("--metrics-out", default=None,
                    help="write the merged metrics-registry JSON "
                         "(counters/gauges/histograms across shards)")
    ap.add_argument("--live-out", default=None,
                    help="with --processes: append live heartbeat "
                         "frames (phase watermarks, metric deltas, phi "
                         "scores) to this JSONL file at a bounded "
                         "cadence; tail it mid-run with "
                         "`python -m repro.obs.watch`")
    ap.add_argument("--flight-dir", default=None,
                    help="with --processes: directory where per-process "
                         "flight-recorder rings are flushed on crash, "
                         "orphan exit, eviction, and failure recovery "
                         "(*.flight.jsonl)")
    ap.add_argument("--interleave", type=int, default=1,
                    help="virtual stages per device: run the "
                         "interleaved 1F1B schedule (v non-contiguous "
                         "model chunks per device, bubble fraction "
                         "(S-1)/(vM+S-1)); scan length must divide by "
                         "stages*interleave and --microbatches by "
                         "stages")
    args = ap.parse_args(argv)

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}"
        ).strip()
        if len(jax.devices()) != args.host_devices:
            ap.error(f"--host-devices {args.host_devices}: the jax backend "
                     f"already has {len(jax.devices())} "
                     f"{jax.devices()[0].platform} devices; set XLA_FLAGS "
                     "before launch instead")

    if args.processes > 1:
        return run_processes(args, ap), None
    enable_compile_cache()
    if args.elastic is not None and "kill" in args.elastic:
        try:
            ev = parse_elastic(args.elastic)
        except ValueError as e:
            ap.error(str(e))
        if any(k == "kill" for evs in ev.values() for k, _ in evs):
            ap.error("kill events need --processes > 1 (hard host "
                     "crashes only exist in the multi-host runtime)")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(**({"n_layers": args.layers}
                             if args.layers else {}))
    elif args.layers:
        import dataclasses
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    api = get_api(cfg)
    opt = AdamW(lr=args.lr, warmup=min(20, args.steps // 5),
                total_steps=args.steps)
    data = SyntheticLM(vocab=cfg.vocab_size, batch=args.batch,
                       seq=args.seq, seed=args.seed)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    runtime = events = None
    if (args.elastic is not None or args.device_collective
            or args.overlap_sync or args.pipeline_stages > 1
            or args.interleave > 1):
        # --device-collective/--overlap-sync/--pipeline-stages without
        # churn still need the runtime: the engine's programs are keyed
        # by its epochs (a static team is just a single epoch)
        runtime = ElasticPhaserRuntime(args.workers, seed=args.seed,
                                       kind=args.sync_kind)
    if args.elastic is not None:
        try:
            events = parse_elastic(args.elastic)
        except ValueError as e:
            ap.error(str(e))
    timeline = metrics_reg = None
    if args.trace or args.metrics_out:
        from ..obs import MetricsRegistry, Timeline
        timeline = Timeline()
        metrics_reg = MetricsRegistry()
    loop = TrainLoop(api=api, opt=opt, data=data, ckpt=ckpt,
                     ckpt_every=args.ckpt_every,
                     microbatches=args.microbatches,
                     log_every=args.log_every,
                     timeline=timeline, metrics=metrics_reg,
                     runtime=runtime,
                     elastic_events=events or {},
                     device_collective=(True if args.device_collective
                                        or args.overlap_sync
                                        or args.pipeline_stages > 1
                                        or args.interleave > 1
                                        else None),
                     overlap_sync=args.overlap_sync,
                     pipeline_stages=args.pipeline_stages,
                     interleave=args.interleave)
    try:
        loop.run(args.steps, resume=args.resume)
    except ValueError as e:
        print(f"# elastic schedule error: {e}")
        return 2, loop
    if args.trace:
        timeline.save(args.trace)
    if args.metrics_out:
        from ..obs import MetricsRegistry
        with open(args.metrics_out, "w") as f:
            json.dump({"metrics": MetricsRegistry.merge(
                [metrics_reg.snapshot()])}, f, indent=2)
    for m in loop.metrics_log:
        print(json.dumps(m))
    for e in loop.epoch_log:
        print(json.dumps({"epoch_boundary": e}))
    first = loop.metrics_log[0]["loss"]
    last = loop.metrics_log[-1]["loss"]
    print(f"# loss {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first else 'NOT DECREASED'})")
    return (0 if last < first else 1), loop


if __name__ == "__main__":
    raise SystemExit(main())
