"""Training cells: the program's ``TrainLoop`` at published widths.

The loop is built as the training launcher builds it: AdamW and the
``SyntheticLM`` stream seeded by ``--seed``. On one chip the step is a
plain jitted step with no runtime. On a team of chips it runs as the
launcher's ``--device-collective``: an ``ElasticPhaserRuntime`` of one
replica per chip with the traffic's ``sync_kind``, the step the
execution engine's compiled program, and the traffic's ``batch`` rows
on each chip. Weights come from the seed. Set-up drives the loop
through its first three steps, which the reference then follows over
the whole batch of every chip; the same loop, feed and compiled step
then run for ``--seconds``. The window ends in ``block_until_ready`` on
the parameters, and ``train_tokens_per_s`` is every token of every step
dispatched in it over its wall time.

On a team the gradient and the change compared are the first replica's,
and ``replicas_apart`` is the largest norm of the difference between
any replica's parameters and the first replica's after the checked
steps: every replica is handed the same reduced gradient.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, flops, model_ref, program, trace
from bench.harness import BenchError, Context, Outcome, memory_peak

CHECKED_STEPS = 3


class _WindowClosed(Exception):
    pass


class Feed:
    """The loop's data stream with the first batches kept for the
    reference; the host's time in it, and from it to the step's
    callback, written as trace spans."""

    def __init__(self, inner, keep: int):
        self.inner, self.keep = inner, keep
        self.kept: List[Dict[str, np.ndarray]] = []
        self.dispatch = None

    @property
    def batch(self) -> int:
        return self.inner.batch

    def __iter__(self):
        return self

    def __next__(self):
        self.end_dispatch()
        with jax.profiler.TraceAnnotation("data"):
            b = next(self.inner)
        if len(self.kept) < self.keep:
            self.kept.append({k: np.array(v) for k, v in b.items()})
        self.dispatch = jax.profiler.TraceAnnotation("dispatch")
        self.dispatch.__enter__()
        return b

    def end_dispatch(self):
        if self.dispatch is not None:
            self.dispatch.__exit__(None, None, None)
            self.dispatch = None

    def state_dict(self):
        return self.inner.state_dict()


def _delete(tree) -> None:
    for x in jax.tree_util.tree_leaves(tree):
        x.delete()


def rows_differ(batches: List[Dict[str, np.ndarray]]) -> bool:
    rows = [r.tobytes() for b in batches for r in b["tokens"]]
    return len(set(rows)) == len(rows)


def replicas(x, devices) -> List:
    """The copy of a replicated array on each device, in ``devices``
    order."""
    by = {s.device: s.data for s in x.addressable_shards}
    return [by[d] for d in devices]


def first_replica(tree, devices):
    if len(devices) == 1:
        return tree
    return jax.tree_util.tree_map(lambda x: replicas(x, devices)[0], tree)


@jax.jit
def _sq_diff(a, b):
    return jnp.sum(jnp.square(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))


def replicas_apart(tree, devices) -> float:
    """Largest norm, over the replicas, of the difference between a
    replica's parameters (all leaves together) and the first
    replica's."""
    sq = [0.0] * len(devices)
    for x in jax.tree_util.tree_leaves(tree):
        copies = replicas(x, devices)
        for r in range(1, len(devices)):
            sq[r] += float(_sq_diff(copies[r], jax.device_put(
                copies[0], devices[r])))
    return max(math.sqrt(v) for v in sq)


def team_of(cell) -> Dict:
    """The ``TrainLoop`` arguments of a cell's team. A traffic for a team
    of chips names its ``sync_kind``; one for a single chip names none."""
    tr, chips = cell.traffic, cell.chips
    kind = tr.get("sync_kind")
    if chips == 1:
        if kind:
            raise BenchError(f"traffic {cell.traffic_name} names a "
                             f"sync_kind; the cell has one chip")
        return {}
    if not kind:
        raise BenchError(f"traffic {cell.traffic_name} names no sync_kind; "
                         f"the cell has {chips} chips")
    from repro.runtime_elastic import ElasticPhaserRuntime
    return {"runtime": ElasticPhaserRuntime(chips, seed=tr["phaser_seed"],
                                            kind=kind),
            "device_collective": True}


def run(ctx: Context) -> Outcome:
    from repro.data import SyntheticLM
    from repro.optim import AdamW
    from repro.train.loop import TrainLoop

    cell, tr, cfg = ctx.cell, ctx.cell.traffic, ctx.cell.config
    devs = ctx.devices
    team = team_of(cell)
    api = program.model_api(cfg)
    o = tr["optimizer"]
    opt = AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                warmup=o["warmup"], total_steps=o["total_steps"])
    # the traffic's batch is rows per chip
    batch, seq = tr["batch"] * cell.chips, tr["seq"]
    feed = Feed(SyntheticLM(vocab=cfg["vocab_size"], batch=batch, seq=seq,
                            seed=ctx.seed), CHECKED_STEPS)
    loop = TrainLoop(api=api, opt=opt, data=feed, log_every=10 ** 9,
                     **team)
    p0 = model_ref.make_weights(cfg, ctx.seed, cfg["torch_dtype"],
                                device=devs[0])
    # step 0 on its own: it compiles, and the optimizer state it leaves
    # holds the first gradient as the optimizer got it (m = (1-b1) g)
    p1, o1 = loop.run(1, params=p0, opt_state=opt.init(p0))
    g0 = jax.tree_util.tree_map(lambda m: m / (1 - o["b1"]),
                                first_replica(o1.mu, devs))
    prog = {"loss": [loop.metrics_log[0]["loss"]],
            "grad": model_ref.leaf_norms(g0),
            "grad_tensors": model_ref.host_leaves(g0)}
    del g0
    # the first steps' state, deleted once the loop has moved past it so
    # that the window holds only what the loop itself holds
    hold = {"p0": p0, "p1o1": (p1, o1)}
    del p0
    st = {"steps": 0, "last": None, "losses": [], "t0": None}
    limit = ctx.seconds
    if ctx.traced:
        limit = min(limit, tr["trace_seconds"])

    def on_step(i, params, metrics):
        g = i + 1                                   # global step
        feed.end_dispatch()
        if g < CHECKED_STEPS:
            st["losses"].append(metrics["loss"])
            if g == 1:
                jax.block_until_ready(params)
                _delete(hold.pop("p1o1"))
            if g == CHECKED_STEPS - 1:
                prog["change"] = model_ref.leaf_norms(
                    jax.tree_util.tree_map(
                        lambda a, b: a.astype(jnp.float32)
                        - jax.device_put(b, a.sharding).astype(jnp.float32),
                        first_replica(params, devs), hold["p0"]))
                if team:
                    prog["replicas_apart"] = replicas_apart(params, devs)
                _delete(hold.pop("p0"))
                prog["loss"] += [float(x) for x in st["losses"]]
                jax.block_until_ready(params)
                st["setup_s"] = time.time() - ctx.t_start
                st["compiles0"] = ctx.compiles.count
                if ctx.capture is not None:
                    ctx.capture.start()
                st["ann"] = jax.profiler.TraceAnnotation("window")
                st["ann"].__enter__()
                st["t0"] = time.perf_counter()
            return
        st["steps"] += 1
        st["last"] = params
        if time.perf_counter() - st["t0"] >= limit:
            raise _WindowClosed

    try:
        loop.run(10 ** 9, params=p1, opt_state=o1, on_step=on_step)
        raise BenchError("the loop ended before the window closed")
    except _WindowClosed:
        pass
    feed.end_dispatch()
    with jax.profiler.TraceAnnotation("readback"):
        jax.block_until_ready(st["last"])
    t_end = time.perf_counter()
    st["ann"].__exit__(None, None, None)
    compiles = ctx.compiles.count - st["compiles0"]
    window_s = t_end - st["t0"]
    tokens = st["steps"] * batch * seq
    red = None
    if ctx.capture is not None:
        ctx.capture.stop()
    peak = memory_peak(devs)
    facts = {"chips": cell.chips}
    if team:
        # one combine launch a round over the whole bucket buffer
        lay = loop.programs[0].layout
        facts["bucket_combine"] = {"rows": lay.n_buckets,
                                   "bucket_elems": lay.bucket_elems}
    # free the program's state before the reference runs
    del loop, p1, o1, st["last"], feed.inner
    gc.collect()
    if ctx.capture is not None:
        red = trace.reduce(ctx.capture.record())
    ctx.log(f"# window: {st['steps']} steps, {tokens} tokens in "
            f"{window_s:.3f} s; compiles in window {compiles}; set-up "
            f"{st['setup_s']:.2f} s")
    ref = model_ref.train_readings(
        cfg, o, model_ref.make_weights(cfg, ctx.seed, cfg["torch_dtype"],
                                       device=devs[0]),
        feed.kept, block_rows=tr["ref_block_rows"],
        devices=devs if team else None)
    checks = compare.train_checks(prog, ref)
    ctx.log(f"# losses program {prog['loss']} reference {ref['loss']}")
    limits = ctx.cell.limits
    ctx.log("# readings not compared: " + ", ".join(
        f"{k} {v!r}" for k, v in checks.items() if k not in limits))
    out_checks = [(k, v, limits[k]) for k, v in checks.items()
                  if k in limits]
    if team:
        out_checks.append(("replicas_apart", prog["replicas_apart"],
                           limits["replicas_apart"]))
    out_checks.append(("rows_repeated", 0.0 if rows_differ(feed.kept)
                       else 1.0, 0.0))
    out_checks.append(("compiles_in_window", float(compiles), 0.0))
    tps = tokens / window_s
    return Outcome(
        attempted=st["steps"], failed=0,
        metrics={"train_tokens_per_s": tps, "setup_s": st["setup_s"]},
        checks=out_checks, memory_peak_bytes=peak, trace=red,
        facts={**facts, "tokens_per_s": tps, "steps": st["steps"],
               "window_s": window_s,
               "flops_per_token": flops.train_flops_per_token(cfg, seq),
               "compiles_in_window": compiles,
               "program": prog, "reference": ref, "batches": feed.kept})
