"""Readings that set a cell's correctness limits, and the serving knee.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--seconds 20] [--sweep 1.5,2,3] [--plant FAULT] [--out FILE]

Not part of a benchmark run. For each seed it reads, in one process:

* the program: the numbers the cell compares, as a run reports them;
* the control: the plain reference computed one precision step below
  the configuration's bfloat16 (float8 e4m3 matmul operands), put in
  the program's place;
* training only, the faults a training cell can have beside a state
  left unchanged, planted in the reference: half the batch left out
  with the mean over the rest, and on a team one replica's rows left
  out of the sum (``replica_left_out``) and the exchange left out, the
  first replica's gradient its own rows' alone (``no_exchange``).

``--plant FAULT`` (training) instead reads the program with one of
``FAULTS`` planted under its timed path, and nothing else.

Serving builds its engine once and swaps in each seed's weights; the
program's readings come from a window of ``--seconds`` at the cell's
rate. ``--sweep`` first offers each listed rate (requests per second)
for ``--seconds`` and reports how the tails grow, to find the knee.
Each reading is one JSON line on standard output.
"""
import argparse
import contextlib
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the team's mesh axis, as the program's elastic runtime names it
AXIS = "data"


# ---------------------------------------------------------------------------
# faults planted under a training cell's timed path
# ---------------------------------------------------------------------------
def _rank():
    import jax
    return jax.lax.axis_index(AXIS)


def _state_unchanged(orig):
    def update(self, grads, state, params, **_):
        import jax.numpy as jnp
        return params, state, {"lr": jnp.zeros(()),
                               "grad_norm": jnp.zeros(())}
    return update


def _half_batch(orig):
    def loss_fn(self, params, batch, **kw):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, params, half, **kw)
    return loss_fn


def _no_exchange(orig):
    def execute_flat(flat, pc, **kw):
        return flat
    return execute_flat


def _replica_left_out(orig):
    """Replica 1's buffer, its contributor flag with it, enters the sum
    as zeros: the others' mean."""
    def execute_flat(flat, pc, **kw):
        import jax.numpy as jnp
        flat = jnp.where(_rank() == 1, jnp.zeros_like(flat), flat)
        return orig(flat, pc, **kw)
    return execute_flat


def _replica_step_skipped(orig):
    """Replica 1 keeps its parameters and optimizer state."""
    def update(self, grads, state, params, **kw):
        import jax
        import jax.numpy as jnp
        new_p, new_s, om = orig(self, grads, state, params, **kw)
        keep = _rank() == 1
        pick = lambda new, old: jnp.where(keep, old, new)
        return (jax.tree_util.tree_map(pick, new_p, params),
                jax.tree_util.tree_map(pick, new_s, state), om)
    return update


FAULTS = ("state_unchanged", "half_batch", "no_exchange",
          "replica_left_out", "replica_step_skipped")


def _fault_site(fault):
    from repro.collective_exec import program
    from repro.models.registry import ModelAPI
    from repro.optim import AdamW
    return {"state_unchanged": (AdamW, "update", _state_unchanged),
            "half_batch": (ModelAPI, "loss_fn", _half_batch),
            "no_exchange": (program, "execute_flat", _no_exchange),
            "replica_left_out": (program, "execute_flat",
                                 _replica_left_out),
            "replica_step_skipped": (AdamW, "update",
                                     _replica_step_skipped)}[fault]


@contextlib.contextmanager
def planted(fault=None):
    """The program with ``fault`` planted (none for ``None``)."""
    if fault is None:
        yield
        return
    owner, attr, make = _fault_site(fault)
    orig = getattr(owner, attr)
    setattr(owner, attr, make(orig))
    try:
        yield
    finally:
        setattr(owner, attr, orig)


def train_readings(cell, seeds, emit, require_tpu=True, plant=None):
    from bench import compare, harness, model_ref
    tr, cfg = cell.traffic, cell.config
    team = cell.chips > 1
    rows = tr["batch"] * cell.chips
    for seed in seeds:
        ctx = harness.context(cell, seed=seed, seconds=0.5, traced=False,
                              t_start=time.time(), require_tpu=require_tpu)
        with planted(plant):
            out = cell.driver().run(ctx)
        f = out.facts
        ref, batches = f["reference"], f["batches"]
        row = {"seed": seed,
               "program": compare.train_checks(f["program"], ref)}
        if team:
            row["program"]["replicas_apart"] = f["program"]["replicas_apart"]
        if plant:
            emit({"plant": plant, **row})
            continue
        devs = ctx.devices if team else None
        p0 = lambda: model_ref.make_weights(cfg, seed, cfg["torch_dtype"],
                                            device=ctx.devices[0])
        o = tr["optimizer"]
        ref_on = lambda **kw: model_ref.train_readings(
            cfg, o, p0(), batches, block_rows=tr["ref_block_rows"],
            devices=devs, **kw)
        ctl = ref_on(quant="fp8")
        row["control"] = compare.train_checks(ctl, ref)
        row["half_batch"] = compare.train_checks(
            ref_on(rows=range(max(1, rows // 2))), ref)
        if team:
            b = tr["batch"]
            row["replica_left_out"] = compare.train_checks(
                ref_on(rows=[r for r in range(rows)
                             if not b <= r < 2 * b]), ref)
            row["no_exchange"] = compare.train_checks(
                ref_on(rows=range(b)), ref)
        row["losses"] = {"program": f["program"]["loss"],
                         "reference": ref["loss"], "control": ctl["loss"]}
        emit(row)


def serve_readings(cell, seeds, seconds, sweep, emit, require_tpu=True):
    from bench import harness, model_ref
    drv = cell.driver()
    mix, cfg = dict(cell.traffic), cell.config
    ctx = harness.context(cell, seed=seeds[0], seconds=seconds,
                          traced=False, t_start=time.time(),
                          require_tpu=require_tpu)
    engine = drv.build(ctx)
    emit({"setup_s": time.time() - ctx.t_start})
    for rate in sweep:
        r = drv.open_loop(ctx, engine, dict(mix, rate=rate), seeds[0],
                          seconds)
        ttft = r["ttft"]
        third = max(1, len(ttft) // 3)
        emit({"rate": rate, "requests": len(ttft), "failed": r["failed"],
              "ttft_p90_ms": r["ttft_p90_ms"],
              "tpot_p90_ms": r["tpot_p90_ms"],
              "ttft_p50_first_third_ms": 1e3 * sorted(ttft[:third])[
                  third // 2],
              "ttft_p50_last_third_ms": 1e3 * sorted(ttft[-third:])[
                  third // 2],
              "served_s": r["served_s"], "steps": len(r["steps"]),
              "compiles": r["compiles"]})
    for seed in seeds:
        params = model_ref.make_weights(cfg, seed, cfg["torch_dtype"],
                                        device=ctx.devices[0])
        engine.params = params
        r = drv.open_loop(ctx, engine, mix, seed, seconds)
        seqs = drv.reference_sample(r["tracks"], seed, mix["ref_sample"])
        emit({"seed": seed, "requests": len(r["ttft"]),
              "failed": r["failed"], "ttft_p90_ms": r["ttft_p90_ms"],
              "tpot_p90_ms": r["tpot_p90_ms"],
              "served_tokens": sum(len(o) for _, o in seqs),
              "program": {"logit_gap": drv.widest_gap(cfg, mix, params,
                                                      seqs)},
              "control": {"logit_gap": drv.widest_gap(cfg, mix, params,
                                                      seqs, "fp8")}})
        del params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--plant", default=None, choices=FAULTS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                           ".jax_cache")
    sys.path.insert(0, CHECKOUT)
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from bench import harness
    cell = harness.find_cell(os.path.join(CHECKOUT, "BENCHMARK.json"),
                             args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    sink = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps({"cell": cell.name, **row})
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    if cell.traffic["driver"] == "train":
        train_readings(cell, seeds, emit, plant=args.plant)
    else:
        sweep = [float(x) for x in args.sweep.split(",") if x]
        serve_readings(cell, seeds, args.seconds, sweep, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
