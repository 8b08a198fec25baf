"""Data-plane schedules derived from the phaser topology: rounds/messages
per all-reduce schedule — including the non-power-of-two elimination
derivations — plus numeric equivalence of BOTH executors on a multi-device
mesh (8 host devices; the benchmark runner sets the flag): the plain
schedule executor and the execution engine's bucketed shard_map program
with the fused Pallas combine.

The overlap section times full gradient-sync train steps — overlapped
(pipelined bucket groups + microbatch streams) vs eager vs the xla_psum
baseline — asserts the overlapped step is bitwise-equal to the eager
one, and emits ``BENCH_collective.json`` so CI tracks the perf
trajectory across PRs."""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core.collective import ALLREDUCE_KINDS, PhaserCollective


def _bytes_factor(kind: str, n: int) -> float:
    """x|grad| moved per device (receive side, whole-buffer terms; the
    elimination pre/post phases add 2 half-buffers + 1 full buffer
    amortized over the team)."""
    k = 1 << (n.bit_length() - 1)
    r = n - k
    lg = int(np.log2(k)) if k > 1 else 0
    if kind == "phaser_scsl":
        return 2.0
    if kind == "recursive_doubling":
        return lg + (2.0 if r else 0.0)
    if kind == "halving_doubling":
        return 2 * (k - 1) / k + (2.5 * r / n if r else 0.0)
    return 1.0


def run(report):
    rows = []
    for n in (3, 6, 8, 16, 100, 256):
        for kind in ALLREDUCE_KINDS:
            if kind == "xla_psum":
                continue
            pc = PhaserCollective(n, "data", kind=kind)
            st = pc.stats()
            rows.append({"n": n, "schedule": kind,
                         "rounds": st["rounds"],
                         "messages": st["messages"],
                         "bytes_factor": round(_bytes_factor(kind, n), 2)})
    report.table(
        "collective schedules from the phaser topology "
        "(bytes_factor = x|grad| moved per device; non-pow2 teams use "
        "the elimination derivations)", rows,
        note="phaser_scsl reduces up the SCSL then broadcasts down the "
             "SNSL (latency ~2·log n rounds, bandwidth 2x); "
             "halving_doubling is the bandwidth-optimal variant; at "
             "non-pow2 n the extras fold in via elimination pre-phases "
             "instead of forcing a fallback.")

    # numeric equivalence on the host mesh — plain executor
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.collective_exec import build_allreduce_program

    ndev = jax.device_count()
    if ndev < 2:
        return
    rows = []
    for n in sorted({3, 5, 6, min(8, ndev)}):
        if n > ndev:
            continue
        mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
        x = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4) * 0.5 + 1
        want = jnp.broadcast_to(x.sum(0), (n, 4))
        for kind in ALLREDUCE_KINDS:
            pc = PhaserCollective(n, "data", kind=kind)
            f = jax.shard_map(pc.all_reduce, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data"),
                              check_vma=False)
            got = f(x)
            rows.append({"schedule": kind, "devices": n,
                         "allclose_vs_psum": bool(jnp.allclose(got,
                                                               want))})
    report.table("schedule equivalence (plain shard_map executor, "
                 "host devices, incl. non-pow2 teams)", rows)

    # execution-engine path: bucketed buffer + fused Pallas combine
    rows = []
    spec = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
    rng = np.random.default_rng(0)
    for n in sorted({3, 6, min(8, ndev)}):
        if n > ndev:
            continue
        x = jnp.asarray(rng.normal(size=(n, 8, 1024)).astype(np.float32))
        want = np.asarray(x).sum(0)
        for kind in ALLREDUCE_KINDS:
            pc = PhaserCollective(n, "data", kind=kind)
            prog = build_allreduce_program(pc, spec)
            got = prog(x)
            jax.block_until_ready(got)
            t0 = time.perf_counter()
            for _ in range(3):
                got = prog(x)
            jax.block_until_ready(got)
            dt = (time.perf_counter() - t0) / 3
            ok = all(np.allclose(np.asarray(got[i]), want, rtol=1e-4,
                                 atol=1e-4) for i in range(n))
            rows.append({"schedule": kind, "devices": n,
                         "allclose_vs_sum": ok,
                         "ms_per_sync": round(dt * 1e3, 2)})
    report.table(
        "execution engine equivalence (bucketed shard_map program, "
        "fused Pallas bucket-combine)", rows,
        note="CPU-mesh timings are structural (Pallas runs interpreted "
             "off-TPU); the table proves the compiled programs, not "
             "hardware speed.")

    # overlapped gradient sync: pipelined bucket groups + microbatch
    # streams vs eager vs xla_psum — full train steps, wall-clock
    _overlap_bench(report, ndev)


def _overlap_bench(report, ndev: int) -> None:
    import time

    import jax
    import jax.numpy as jnp

    from repro.collective_exec import build_gradsync_program
    from repro.data.synthetic import make_batch
    from repro.models.registry import get_api, get_config
    from repro.optim import AdamW

    n = min(6, ndev)                        # non-pow2: elimination path
    if n < 2:
        return
    cfg = get_config("smollm-135m").reduced()
    api = get_api(cfg)
    opt = AdamW(lr=1e-3, warmup=2, total_steps=100)
    params = api.init_params(jax.random.key(0))
    opt_state = opt.init(params)
    M = 2                                   # microbatch streams
    bs = [make_batch(cfg.vocab_size, 4, 32, seed=w, step=0)
          for w in range(n)]
    batch = {k: jnp.asarray(np.stack([b[k] for b in bs]))
             for k in bs[0]}
    alive = jnp.ones((n,), jnp.float32)

    def timed(prog, reps=5):
        p, o, m = prog.step(params, opt_state, batch, alive)   # warmup
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for _ in range(reps):
            p, o, m = prog.step(params, opt_state, batch, alive)
        jax.block_until_ready(p)
        return (time.perf_counter() - t0) / reps, (p, o)

    modes = [("xla_psum", "eager", "xla_psum"),
             ("eager", "eager", "recursive_doubling"),
             ("overlapped", "pipelined", "recursive_doubling")]
    rows, results, outs = [], {}, {}
    groups = 0
    for label, overlap, kind in modes:
        prog = build_gradsync_program(
            api, opt, PhaserCollective(n, "data", kind=kind, seed=0),
            stacked=True, overlap=overlap, microbatches=M,
            bucket_elems=1024)
        dt, out = timed(prog)
        outs[label] = out
        groups = max(groups, prog.meta["bucket_groups"])
        rows.append({"mode": label, "kind": kind, "devices": n,
                     "microbatches": M,
                     "bucket_groups": prog.meta["bucket_groups"],
                     "ms_per_step": round(dt * 1e3, 2)})
        results[label] = dt * 1e3
    # correctness gate: overlapped == eager bitwise (hard-fails the
    # bench run — the CI smoke must go red if equivalence ever breaks)
    bitwise = all(
        bool((np.asarray(a) == np.asarray(b)).all())
        for a, b in zip(jax.tree_util.tree_leaves(outs["overlapped"][0]),
                        jax.tree_util.tree_leaves(outs["eager"][0])))
    assert bitwise, \
        "overlapped gradient-sync params diverged from the eager program"
    speedup = results["eager"] / results["overlapped"] \
        if results.get("overlapped") else float("nan")
    report.table(
        "overlapped gradient sync (pipelined bucket groups + microbatch "
        "streams) vs eager vs xla_psum — full train-step wall clock",
        rows,
        note=f"overlapped==eager bitwise: {bitwise}; "
             f"eager/overlapped speedup {speedup:.2f}x "
             f"({groups} bucket groups; host-CPU mesh — structural, "
             "the overlap win is hardware-dependent)")
    payload = {
        "bench": "collective_overlap",
        "devices": n, "microbatches": M, "bucket_groups": groups,
        "model": "smollm-135m.reduced",
        "ms_per_step": {k: round(v, 3) for k, v in results.items()},
        "eager_over_overlapped": round(speedup, 4),
        "overlapped_bitwise_equals_eager": bitwise,
    }
    path = os.path.join(report.outdir, "BENCH_collective.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"  -> wrote {path}")
