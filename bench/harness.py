"""Finds a cell's files by name and runs it once.

A cell names a configuration and a traffic mix in ``BENCHMARK.json``.
The configuration is ``configs/<config>.json``; the traffic is
``traffic/<traffic>.json``, whose ``driver`` names the module under
``drivers/`` that runs that kind of work; each per-layer metric is
``metrics/<metric>.py``; the correctness limits of a cell are
``limits/<cell>.json``. Adding any of them adds files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class BenchError(Exception):
    """A cell that cannot be run as asked; nothing is printed as a
    result."""


class NoChip(BenchError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _read_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str

    def driver(self):
        d = self.traffic.get("driver")
        if not d:
            raise BenchError(f"traffic {self.traffic_name} names no driver")
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        f"{d}.py"), f"bench_driver_{d}")

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        f"{name}.py"),
                           "bench_metric_" + name.replace(".", "_"))


def _listed(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(benchmark_path: str, name: str,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = _read_json(benchmark_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config "
                         f"{w['config']!r}")
    root = os.path.dirname(os.path.abspath(benchmark_path))
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _read_json(os.path.join(bench_dir, "limits", f"{name}.json"))
    e2e = [m for m in bench["end_to_end"] if _listed(m, name)]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def peaks_for(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict:
    table = _read_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def tpu_devices(chips: int, *, require_tpu: bool = True):
    """The first ``chips`` devices; no fallback to another platform."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# what a driver reports through
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts the executables made, compiled or read from the persistent
    cache, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _all: List["CompileCounter"] = []

    def __init__(self):
        import jax
        self.count = 0
        if not CompileCounter._all:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on)
        CompileCounter._all.append(self)

    @staticmethod
    def _on(event: str, duration: float, **_) -> None:
        if event == CompileCounter.EVENT:
            for c in CompileCounter._all:
                c.count += 1


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    devices: list
    t_start: float
    compiles: Optional[CompileCounter] = None
    capture: Optional[object] = None
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                 flush=True)


@dataclass
class Outcome:
    """What a driver hands back: counts, end-to-end metrics, the numbers
    compared beside their limits, and whatever the per-layer readers
    read (``facts``: counts and shapes; ``trace``: the reduced trace)."""

    attempted: int
    failed: int
    metrics: Dict[str, float]
    checks: List[Tuple[str, float, float]]
    memory_peak_bytes: int
    facts: Dict = field(default_factory=dict)
    trace: Optional[Dict] = None

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim
                   for _, v, lim in self.checks) and bool(self.checks)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclass
class MetricInput:
    """What a per-layer metric's reader gets."""

    cell: Cell
    facts: Dict
    trace: Optional[Dict]
    peaks: Dict


def per_layer_values(cell: Cell, out: Outcome,
                     peaks: Dict) -> Dict[str, Dict]:
    vals = {}
    mi = MetricInput(cell=cell, facts=out.facts, trace=out.trace,
                     peaks=peaks)
    for m in cell.per_layer:
        v = cell.metric_reader(m["name"]).read(mi)
        if v is not None:
            vals[m["name"]] = {"value": v, "unit": m["unit"]}
    return vals


def make_capture(traced: bool):
    if not traced:
        return None
    from . import trace
    return trace.Capture(tempfile.mkdtemp(prefix="bench_trace_"))


def result_line(cell: Cell, out: Outcome, metrics: Dict, device: Dict,
                breakdown: Optional[Dict]) -> str:
    res = {"correct": out.correct, "attempted": out.attempted,
           "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in out.checks}
    return json.dumps(res)


def context(cell: Cell, *, seed: int, seconds: float, traced: bool,
            t_start: float, require_tpu: bool = True) -> Context:
    devices = tpu_devices(cell.chips, require_tpu=require_tpu)
    return Context(cell=cell, seed=seed, seconds=seconds, traced=traced,
                   devices=devices, t_start=t_start,
                   compiles=CompileCounter(), capture=make_capture(traced))


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             t_start: float, require_tpu: bool = True) -> Tuple[str, str]:
    """Run one cell once; returns (the result line, the check lines)."""
    ctx = context(cell, seed=seed, seconds=seconds, traced=traced,
                  t_start=t_start, require_tpu=require_tpu)
    devices = ctx.devices
    kind = devices[0].device_kind
    peaks = peaks_for(kind, cell.bench_dir) if require_tpu else {}
    out = cell.driver().run(ctx)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    bd = None
    if traced:
        from . import trace
        red = out.trace
        if red is None:
            raise BenchError("the traced run captured no trace")
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        metrics = per_layer_values(cell, out, peaks)
        bd = trace.breakdown(red)
    else:
        metrics = {m["name"]: {"value": out.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = "\n".join(f"check {n}: {v!r} (limit {lim!r})"
                       for n, v, lim in out.checks)
    return result_line(cell, out, metrics, device, bd), checks
