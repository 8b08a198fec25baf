"""The four-chip training cell's team path on four host CPU devices at
the tiny cut: a sound run reads its gradient and change under the
cell's limits with every replica equal, and each fault planted under
the timed path fails a check, as do the control and the faults planted
in the reference. A traffic whose team does not match the
cell is refused before anything runs."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench_tiny import ROOT

RUNS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "team_runs.py")
CELL = "smollm-135m.dp4-phaser"
# fault -> the check that has to fail
FAULTS = {"state_unchanged": "update_gap", "half_batch": "grad_err",
          "no_exchange": "grad_err", "replica_left_out": "grad_err",
          "replica_step_skipped": "replicas_apart"}


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, RUNS, "sound", "readings",
                        *FAULTS], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    rows = [json.loads(x) for x in p.stdout.splitlines()
            if x.startswith("{")]
    return {r["case"]: r for r in rows}


def test_sound_team_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r
    assert r["checks"]["replicas_apart"] == 0.0
    for k in ("grad_err", "update_gap"):
        assert r["checks"][k] < r["limits"][k], (k, r)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_its_check(runs, fault):
    r = runs[fault]
    k = FAULTS[fault]
    assert not r["correct"], r
    assert r["checks"][k] > r["limits"][k], (k, r)


def test_control_and_reference_faults_fail_and_program_passes(runs):
    """The readings ``bench/calibrate.py`` takes on the chip: the
    program passes the cell's limits; the float8 control and each fault
    planted in the reference fail one."""
    r = runs["readings"]
    limits = _cell().limits
    fails = lambda reading: [k for k in ("grad_err", "update_gap")
                             if reading[k] > limits[k]]
    assert not fails(r["program"]) and r["program"]["replicas_apart"] == 0
    for case in ("control", "half_batch", "replica_left_out",
                 "no_exchange"):
        assert fails(r[case]), (case, r[case])


def _cell(traffic_edit=None, chips=None):
    cell = harness.find_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    if traffic_edit:
        cell.traffic = traffic_edit(dict(cell.traffic))
    if chips is not None:
        cell.chips = chips
    return cell


def _drop_sync(tr):
    tr.pop("sync_kind")
    return tr


@pytest.mark.parametrize("case,match", [
    ("one_chip_traffic_on_four", "names no sync_kind"),
    ("team_traffic_without_sync_kind", "names no sync_kind"),
    ("team_traffic_on_one_chip", "names a sync_kind"),
])
def test_a_team_that_does_not_fit_the_cell_is_refused(case, match):
    if case == "one_chip_traffic_on_four":
        cell = harness.find_cell(os.path.join(ROOT, "BENCHMARK.json"),
                                 "smollm-135m.train")
        cell.chips = 4
    elif case == "team_traffic_without_sync_kind":
        cell = _cell(_drop_sync)
    else:
        cell = _cell(chips=1)
    with pytest.raises(harness.BenchError, match=match):
        cell.driver().team_of(cell)
