"""Bring-up smoke run of the main path on TPU.

    python chip_smoke.py             # one chip: train and serve
    python chip_smoke.py --chips 4   # four chips: the phaser gradient sync

Every phase runs smollm-135m at its published width (30 layers, d_model
576, 9 heads / 3 KV heads, d_ff 1536, vocab 49152, bf16) with random
weights from seed 0, through the launchers a user calls.

One chip:
  * train: ``repro.launch.train`` takes 8 steps at 4 x 1024 tokens; the
    loss must stay finite and fall (the launcher's own exit code);
  * serve: ``repro.launch.serve`` answers 16 requests of 128 prompt
    tokens and 32 new tokens each from 8 slots with a 2048 window;
  * serve reference: the engine's greedy tokens (KV-cache decode) must be
    the argmax of a full forward pass over the same sequence, in float32
    at the highest matmul precision.

Four chips (``--chips 4``): the elastic trainer runs with its workers
churning 4 -> 3 -> 4, once with the phaser schedule (``ppermute`` rounds
and the Pallas bucket combine) and once with XLA's ``psum``. Loss and
gradient norm must agree step by step, each epoch's mesh must hold
distinct devices, and the compiled phaser program must hold the kernel
as a ``tpu_custom_call``.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase. A failed phase raises, so the exit code is non-zero. Lines
before the last are bring-up readings (compile and step seconds, tok/s,
peak device memory), not benchmark results. The last line of standard
output is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-135m"
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 8, 4, 1024
# the launcher's default 3e-3 has a one-step warmup over 8 steps; at full
# width from random weights Adam's first sign-like steps then overshoot
# (on v5e the loss rose from step 3 on), so take a smaller peak
TRAIN_LR = "1e-3"
SERVE_ARGS = {"requests": 16, "batch": 8, "window": 2048, "prompt-len": 128,
              "max-new": 32}
# the global batch divides both teams of the churn (4 and 3 workers)
SYNC_BATCH, SYNC_SEQ, SYNC_CHURN = 12, 1024, "leave@3,join@6"
SYNC_TEAMS = [4, 4, 4, 4, 3, 3, 3, 4]          # per step, from SYNC_CHURN
# phaser vs psum, per step: both sum the same f32 gradient buckets, in a
# different order, so they differ only in the last bits of each sum
SYNC_RTOL = 1e-3
# engine token vs full forward: the chosen token's logit may sit this far
# below the row's max (float32 noise between the two attention paths)
LOGIT_ATOL = 1e-3


class _Tee(io.TextIOBase):
    """Echo writes to ``out`` and keep a copy."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        return self.buf.write(s)

    def flush(self):
        self.out.flush()


def cli(main, argv):
    """Run a launcher's ``main(argv)``; returns (exit code, its stdout)."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    return rc, tee.buf.getvalue()


def peak_memory(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def train_phase(jax):
    from repro.launch import train
    rc, out = cli(train.main, [
        "--arch", ARCH, "--steps", str(TRAIN_STEPS),
        "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
        "--lr", TRAIN_LR, "--log-every", "1"])
    rows = [json.loads(line) for line in out.splitlines()
            if line.startswith("{") and '"loss"' in line]
    losses = [r["loss"] for r in rows]
    assert len(losses) == TRAIN_STEPS, losses
    assert all(math.isfinite(x) for x in losses), losses
    assert rc == 0, f"train launcher exited {rc}"
    dts = [r["dt"] for r in rows]
    steady = statistics.median(dts[2:])
    print(f"# train: first step {dts[0]:.2f} s (compile included); "
          f"steady step {steady:.4f} s (median of steps 2-{TRAIN_STEPS - 1},"
          f" each ending in a host read of its loss), "
          f"{TRAIN_BATCH * TRAIN_SEQ / steady:.0f} tok/s; "
          f"peak device memory {peak_memory(jax)}")


def serve_phase(jax):
    from repro.launch import serve
    argv = ["--arch", ARCH]
    for k, v in SERVE_ARGS.items():
        argv += [f"--{k}", str(v)]
    rc, out = cli(serve.main, argv)
    assert rc == 0, f"serve launcher exited {rc}"
    m = re.search(r"served (\d+)/(\d+) requests, (\d+) tokens", out)
    n = SERVE_ARGS["requests"]
    assert m and int(m[1]) == int(m[2]) == n, out[-500:]
    assert int(m[3]) == n * SERVE_ARGS["max-new"], out[-500:]
    print(f"# serve: tok/s above includes compilation; "
          f"peak device memory {peak_memory(jax)}")


def serve_reference_phase(jax):
    import jax.numpy as jnp
    import numpy as np
    from repro.models.registry import get_api, get_config
    from repro.serve.engine import Request, ServeEngine
    api = get_api(dataclasses.replace(get_config(ARCH), dtype="float32"))
    with jax.default_matmul_precision("highest"):
        params = api.init_params(jax.random.key(0))
        eng = ServeEngine(api, params, batch=2, window=64)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i, max_new=8, prompt=rng.integers(
                    0, api.cfg.vocab_size, n).astype(np.int32))
                for i, n in enumerate((5, 12))]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        forward = jax.jit(api.prefill_full_fn)
        worst = 0.0
        for r in reqs:
            assert len(r.out) == r.max_new, (r.rid, r.out)
            seq = np.concatenate([r.prompt, r.out[:-1]]).astype(np.int32)
            logits, _ = forward(params, {"tokens": jnp.asarray(seq[None])})
            # the logits that chose out[j] sit at position len(prompt)-1+j
            rows = np.asarray(logits[0, len(r.prompt) - 1:], np.float64)
            gap = rows.max(-1) - rows[np.arange(len(r.out)), r.out]
            worst = max(worst, float(gap.max()))
            assert np.all(gap <= LOGIT_ATOL), (r.rid, gap.tolist())
    print(f"# serve reference: largest logit gap {worst:.2e} "
          f"(limit {LOGIT_ATOL})")


def compiled_text(jax, loop, prog) -> str:
    """HLO of the program as compiled for its mesh (shapes only)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(prog.mesh, P())
    dat = NamedSharding(prog.mesh, P(prog.pc.axis_name))
    pspec = loop.api.param_spec()
    ospec = jax.eval_shape(loop.opt.init, pspec)
    on = lambda tree, sh: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)
    batch = {k: jax.ShapeDtypeStruct((SYNC_BATCH, SYNC_SEQ), jnp.int32,
                                     sharding=dat)
             for k in ("tokens", "targets")}
    alive = jax.ShapeDtypeStruct((prog.n,), jnp.float32, sharding=dat)
    return prog.jitted.lower(on(pspec, rep), on(ospec, rep), batch,
                             alive).compile().as_text()


def gradsync_phase(jax):
    from repro.launch import train
    assert len(jax.devices()) >= 4, jax.devices()
    loops = {}
    for kind in ("phaser_scsl", "xla_psum"):
        t0 = time.time()
        rc, loop = train.run([
            "--arch", ARCH, "--steps", str(len(SYNC_TEAMS)),
            "--batch", str(SYNC_BATCH), "--seq", str(SYNC_SEQ),
            "--workers", "4", "--device-collective",
            "--elastic", SYNC_CHURN, "--sync-kind", kind,
            "--lr", TRAIN_LR, "--log-every", "1"])
        assert rc == 0, f"{kind}: train launcher exited {rc}"
        log = loop.metrics_log
        assert [int(m["team"]) for m in log] == SYNC_TEAMS, log
        assert all(math.isfinite(m["loss"]) for m in log), log
        steady = statistics.median(m["dt"] for m in log[1:])
        print(f"# {kind}: {time.time() - t0:.1f} s with compiles; median "
              f"step {steady:.4f} s; peak device memory {peak_memory(jax)}")
        loops[kind] = loop
    ph, ps = loops["phaser_scsl"], loops["xla_psum"]
    for a, b in zip(ph.metrics_log, ps.metrics_log):
        for k in ("loss", "grad_norm"):
            assert math.isclose(a[k], b[k], rel_tol=SYNC_RTOL), \
                (a["step"], k, a[k], b[k])
        print(f"# step {int(a['step'])} team {int(a['team'])}: loss "
              f"{a['loss']:.6f} vs psum {b['loss']:.6f}; grad_norm "
              f"{a['grad_norm']:.6f} vs {b['grad_norm']:.6f}")
    progs = ph.programs
    meshes = [[d.id for d in p.mesh.devices.flat] for p in progs]
    print(f"# phaser epoch meshes (device ids): {meshes}")
    assert [len(m) for m in meshes] == [4, 3, 4], meshes
    assert all(len(set(m)) == len(m) for m in meshes), meshes
    assert "tpu_custom_call" in compiled_text(jax, ph, progs[-1])
    print("# compiled phaser program holds tpu_custom_call")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and serve on one chip; 4: only the "
                         "phaser gradient sync against psum on four")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.utils import enable_compile_cache
    print(f"# device {dev.device_kind} x{len(jax.devices())}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    phases = ([gradsync_phase] if args.chips == 4 else
              [train_phase, serve_phase, serve_reference_phase])
    for phase in phases:
        t0 = time.time()
        print(f"# == {phase.__name__}", flush=True)
        phase(jax)
        print(f"# == {phase.__name__} passed in {time.time() - t0:.1f} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
