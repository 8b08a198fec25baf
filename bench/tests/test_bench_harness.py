"""The harness finds cells, configurations, traffic, limits and metrics
by name from their files, refuses what it cannot run, and never falls
back from the TPU to another platform."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
RUN = os.path.join(ROOT, "bench", "run.py")


def _bench():
    with open(BENCHMARK) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_is_found_with_its_files(name):
    cell = harness.find_cell(BENCHMARK, name)
    assert cell.config["name"] == cell.config_name
    assert callable(cell.driver().run)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)
        assert m["moves"] in reported
    assert cell.limits


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.find_cell(BENCHMARK, "no-such-cell")


def _run(args, cwd=ROOT, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, RUN if cwd == ROOT else
                           os.path.join(cwd, "bench", "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_unknown_workload_exits_without_a_result():
    p = _run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
              "1", "--trace", "0"])
    assert p.returncode == 2 and p.stdout == "", (p.returncode, p.stdout)
    assert "unknown workload" in p.stderr


def test_no_tpu_exits_without_a_result():
    p = _run(["--workload", "smollm-135m.train", "--seed", str(2 ** 33),
              "--seconds", "1", "--trace", "0"])
    assert p.returncode == 3 and p.stdout == "", (p.returncode, p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    shutil.copy(BENCHMARK, tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "smollm-135m.train", "--seed", "1",
              "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""


def test_tpu_required_without_fallback():
    with pytest.raises(harness.NoChip):
        harness.tpu_devices(1)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks_for("TPU v0 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


DUMMY_DRIVER = '''
from bench.harness import Outcome


def run(ctx):
    n = ctx.cell.traffic["n"]
    return Outcome(attempted=n, failed=0,
                   metrics={"ops_per_s": 2.0 * n, "setup_s": 0.5},
                   checks=[("gap", 0.0, ctx.cell.limits["gap"])],
                   memory_peak_bytes=7, facts={"n": n},
                   trace={"busy_ns": 3e9, "window_ns": 4e9, "by_op": {},
                          "idle_gaps": {}})
'''


def test_a_new_cell_config_and_metric_are_found_by_name(tmp_path):
    """Everything a later change adds is files plus entries."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "drivers", "metrics"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "bench", "peaks.json"), bench)
    (bench / "configs" / "dummy.json").write_text(json.dumps(
        {"name": "dummy"}))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {"driver": "dummy", "n": 21}))
    (bench / "limits" / "dummy.tiny.json").write_text(json.dumps(
        {"gap": 0.0}))
    (bench / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (bench / "metrics" / "dummy.busy.py").write_text(
        "def read(m):\n    return m.facts['n'] + m.trace['busy_ns'] / 1e9\n")
    (bench / "metrics" / "dummy.silent.py").write_text(
        "def read(m):\n    return None\n")
    b = _bench()
    b["configs"].append({"name": "dummy", "source": "https://example.org",
                         "file": "bench/configs/dummy.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "dummy.tiny", "config": "dummy",
                           "traffic": "tiny", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "ops_per_s", "unit": "ops/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["dummy.tiny"]})
    for name in ("dummy.busy", "dummy.silent"):
        b["per_layer"].append({"name": name, "unit": "s", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "ops_per_s",
                               "workloads": ["dummy.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.find_cell(str(tmp_path / "BENCHMARK.json"), "dummy.tiny",
                             bench_dir=str(bench))
    assert sorted(m["name"] for m in cell.end_to_end) == ["ops_per_s",
                                                         "setup_s"]
    line, checks = harness.run_cell(cell, seed=1, seconds=1.0, traced=False,
                                    t_start=0.0, require_tpu=False)
    res = json.loads(line)
    assert res["metrics"] == {"ops_per_s": {"value": 42.0, "unit": "ops/s"},
                              "setup_s": {"value": 0.5, "unit": "s"}}
    assert res["correct"] and list(res)[-1] == "checks"
    assert checks == "check gap: 0.0 (limit 0.0)"
    line, _ = harness.run_cell(cell, seed=1, seconds=1.0, traced=True,
                               t_start=0.0, require_tpu=False)
    res = json.loads(line)
    # a reader that finds nothing is left out of the line
    assert res["metrics"] == {"dummy.busy": {"value": 24.0, "unit": "s"}}
    assert res["device"]["busy_s"] == 3.0 and res["device"]["window_s"] == 4.0
