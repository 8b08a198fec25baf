import os
# host-mesh rehearsals: every bench here runs on the host CPU, never on
# an accelerator; its timings are XLA:CPU numbers, not device numbers
os.environ["JAX_PLATFORMS"] = "cpu"
if "XLA_FLAGS" not in os.environ:
    # collective_bench checks schedule equivalence on the host mesh;
    # pipeline_bench needs 12 devices for the 2-stage x 6-wide
    # interleaved-vs-wave-sync comparison
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"

"""Benchmark runner: one table per paper claim, on a host-CPU mesh.

  PYTHONPATH=src python -m benchmarks.run [--only complexity,...]
"""
import argparse
import csv
import json
import sys
import time


class Report:
    def __init__(self, outdir="results/bench"):
        self.outdir = outdir
        os.makedirs(outdir, exist_ok=True)
        self.n = 0

    def table(self, title, rows, note=None):
        self.n += 1
        print(f"\n== [{self.n}] {title} (host CPU rehearsal, not device "
              "numbers) ==")
        if not rows:
            print("  (empty)")
            return
        cols = list(rows[0])
        widths = {c: max(len(str(c)), *(len(str(r.get(c, ""))) for r in rows))
                  for c in cols}
        print("  " + "  ".join(str(c).ljust(widths[c]) for c in cols))
        for r in rows:
            print("  " + "  ".join(str(r.get(c, "")).ljust(widths[c])
                                   for c in cols))
        if note:
            print(f"  -> {note}")
        slug = "".join(ch if ch.isalnum() else "_" for ch in title)[:60]
        with open(os.path.join(self.outdir, f"{self.n:02d}_{slug}.csv"),
                  "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=cols)
            w.writeheader()
            w.writerows(rows)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: complexity,modelcheck,collective,"
                         "pipeline,kernel,roofline,obs,chaos,tcp")
    ap.add_argument("--quick", action="store_true",
                    help="smoke path: schedule-derivation benches only "
                         "(complexity + collective + pipeline + obs "
                         "tables; skips the model-check sweep, kernel "
                         "timing and roofline)")
    args = ap.parse_args(argv)
    want = set(args.only.split(",")) if args.only else None
    if args.quick and want is None:
        want = {"complexity", "collective", "pipeline", "obs", "chaos",
                "tcp"}

    from benchmarks import (chaos_bench, collective_bench,
                            complexity_bench, kernel_bench,
                            modelcheck_bench, obs_bench, pipeline_bench,
                            roofline_bench, tcp_bench)
    benches = {
        "complexity": complexity_bench,
        "modelcheck": modelcheck_bench,
        "collective": collective_bench,
        "pipeline": pipeline_bench,
        "kernel": kernel_bench,
        "roofline": roofline_bench,
        "obs": obs_bench,
        "chaos": chaos_bench,
        "tcp": tcp_bench,
    }
    rep = Report()
    t0 = time.time()
    for name, mod in benches.items():
        if want and name not in want:
            continue
        print(f"\n#### {name} " + "#" * 50)
        try:
            mod.run(rep)
        except Exception as e:  # noqa: BLE001
            print(f"  !! {name} failed: {type(e).__name__}: {e}")
            raise
    # persist the machine-readable summaries where CI (and the repo
    # history) can diff them: BENCH_*.json land in the repo root
    import glob
    import shutil
    for src in sorted(glob.glob(os.path.join(rep.outdir, "BENCH_*.json"))):
        dst = os.path.basename(src)
        shutil.copyfile(src, dst)
        print(f"persisted {src} -> ./{dst}")
    if args.quick:
        # everything the benches routed through the process-default
        # metrics registry (strike policy, serve engines, ...) plus the
        # obs bench's exported per-case shards, in one merged table —
        # the smoke path's obs summary
        from repro.obs.metrics import MetricsRegistry, default_registry
        snaps = [default_registry().snapshot()]
        obs_json = os.path.join(rep.outdir, "BENCH_obs.json")
        if os.path.exists(obs_json):
            with open(obs_json) as f:
                snaps.append(json.load(f).get("metrics", {}))
        mrows = MetricsRegistry.summary_rows(MetricsRegistry.merge(snaps))
        if mrows:
            rep.table("metrics summary (process shards, merged)", mrows)
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s; CSVs in "
          f"{rep.outdir}/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
