"""Runs of the four-chip training cell on four host CPU devices, at the
tiny cut, each with one fault planted under the timed path or none.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python bench/tests/team_runs.py sound no_exchange ...

The device count has to be set before JAX starts, so the tests run this
in a process of its own. Each case prints one JSON line: its name,
``correct`` and the numbers compared.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smollm-135m.dp4-phaser"
SEED = 2 ** 31 + 5


def readings():
    """The program, the float8 control and the faults planted in the
    reference, as ``bench/calibrate.py`` reads them on the chip."""
    from bench import calibrate
    from bench_tiny import tiny_cell
    rows = []
    calibrate.train_readings(tiny_cell(CELL), [SEED], rows.append,
                             require_tpu=False)
    return {"case": "readings", **rows[0]}


def run(case):
    from bench import harness
    from bench.calibrate import planted
    from bench_tiny import tiny_cell
    if case == "readings":
        return readings()
    with planted(None if case == "sound" else case):
        line, _ = harness.run_cell(tiny_cell(CELL), seed=SEED, seconds=1.0,
                                   traced=False, t_start=0.0,
                                   require_tpu=False)
    res = json.loads(line)
    return {"case": case, "correct": res["correct"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "limits": {k: c["limit"] for k, c in res["checks"].items()}}


if __name__ == "__main__":
    sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]
    for c in sys.argv[1:]:
        print(json.dumps(run(c)), flush=True)
