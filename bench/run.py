"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the root of the checkout.
The run loads, warms up every shape the cell uses (``setup_s``), then
measures for ``--seconds``. With ``--trace 0`` the last line of standard
output carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window. Each run
also compares what the timed path produced with a plain float32
reference; the numbers compared, each beside its limit, are the last
lines of standard error and the ``checks`` key of the result. JAX's
persistent compilation cache lives at ``<checkout>/.jax_cache``. Without
a TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                           ".jax_cache")
    sys.path.insert(0, CHECKOUT)
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from bench import harness
    try:
        cell = harness.find_cell(os.path.join(CHECKOUT, "BENCHMARK.json"),
                                 args.workload)
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        line, checks = harness.run_cell(cell, seed=args.seed,
                                        seconds=args.seconds,
                                        traced=bool(args.trace),
                                        t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    print(checks, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
