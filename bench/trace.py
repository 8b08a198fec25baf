"""Profiler capture and the reduction from a device trace to numbers.

``capture`` wraps ``jax.profiler`` around part of a run. ``extract``
turns the written ``.xplane.pb`` into a compact record: the op and
module events of each device plane and the harness's own host spans,
all on the profiler's clock in nanoseconds. ``reduce`` computes from
that record what the per-layer metrics read: busy and idle time, device
time per module and per op, and the idle gaps named by the host span
that covered them. The reduction is plain Python over lists,
so a test can run it on a small recorded trace.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans the harness writes with jax.profiler.TraceAnnotation
HOST_SPANS = ("window", "data", "dispatch", "submit", "engine.step",
              "readback", "idle")


class Capture:
    """Start and stop the profiler around a slice of a run."""

    def __init__(self, directory: str):
        self.dir = directory
        self.on = False

    def start(self) -> None:
        import jax
        # no Python tracer: it records every Python call, and the host
        # loops of these cells would run at a fraction of their speed
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def record(self) -> Dict:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no trace under {self.dir}")
        rec = extract(max(files, key=os.path.getmtime))
        shutil.rmtree(self.dir, ignore_errors=True)
        return rec


def extract(path: str) -> Dict:
    """Compact record of a trace file: ``devices`` maps a device id to
    its ``ops`` and ``modules`` as ``[name, start_ns, dur_ns]``;
    ``host`` lists the harness spans as ``[name, start_ns, dur_ns]``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
            devices[m.group(1)] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(x: Sequence[Interval], y: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def op_group(name: str) -> str:
    """An op's instruction name without its instance number: ``fusion.12``
    and ``%fusion.12 = bf16[16]{0} fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def window_of(rec: Dict) -> Optional[Interval]:
    spans = [(s, s + d) for n, s, d in rec["host"] if n == "window"]
    if not spans:
        return None
    return (min(a for a, _ in spans), max(b for _, b in spans))


def reduce(rec: Dict, window: Optional[Interval] = None,
           module_match: Optional[Dict[str, str]] = None) -> Dict:
    """Numbers of one traced window, averaged over the devices.

    ``module_match`` maps a label to a regular expression; the device's
    module executions whose name matches it count towards that label's
    ``module_matched`` time and count."""
    mpats = {k: re.compile(v) for k, v in (module_match or {}).items()}
    window = window or window_of(rec)
    if window is None:
        raise ValueError("the trace holds no window span")
    lo, hi = window
    per_dev = []
    for dev_id, dev in sorted(rec["devices"].items()):
        segs = [(op_group(n), clip([(s, s + d)], lo, hi))
                for n, s, d in dev["ops"]]
        segs = [(g, seg[0]) for g, seg in segs if seg]
        busy = union(seg for _, seg in segs)
        by_op: Dict[str, float] = {}
        for g, (a, b) in segs:
            by_op[g] = by_op.get(g, 0.0) + (b - a)
        modules: Dict[str, Dict[str, float]] = {}
        for n, s, d in dev["modules"]:
            seg = clip([(s, s + d)], lo, hi)
            if seg:
                m = modules.setdefault(n, {"ns": 0.0, "count": 0})
                m["ns"] += length(seg)
                m["count"] += 1
        per_dev.append({"id": dev_id, "busy_ns": length(busy),
                        "busy": busy, "by_op": by_op, "modules": modules})
    if not per_dev:
        raise ValueError("the trace holds no TPU device plane")
    n = len(per_dev)
    out = {"devices": n, "window_ns": hi - lo,
           "busy_ns": sum(d["busy_ns"] for d in per_dev) / n,
           "modules": {}, "by_op": {}}
    for d in per_dev:
        for name, m in d["modules"].items():
            o = out["modules"].setdefault(name, {"ns": 0.0, "count": 0})
            o["ns"] += m["ns"] / n
            o["count"] += m["count"] / n
        for g, t in d["by_op"].items():
            out["by_op"][g] = out["by_op"].get(g, 0.0) + t / n
    out["module_matched"] = {
        k: {"ns": sum(m["ns"] for n, m in out["modules"].items()
                      if p.search(n)),
            "count": sum(m["count"] for n, m in out["modules"].items()
                         if p.search(n))}
        for k, p in mpats.items()}
    out["idle_gaps"] = idle_by_host(per_dev[0]["busy"], rec["host"], lo, hi)
    return out


def idle_by_host(busy: Sequence[Interval], host: Sequence, lo: float,
                 hi: float) -> Dict[str, float]:
    """Idle nanoseconds of one device, each gap split among the host
    spans that cover it, the innermost (shortest) span first; idle time
    no span covers goes to ``other``."""
    spans = sorted(((s, s + d, n) for n, s, d in host if n != "window"),
                   key=lambda x: x[1] - x[0])
    out: Dict[str, float] = {}
    for a, b in gaps(busy, lo, hi):
        left = [(a, b)]
        for s, e, name in spans:
            cut = intersect(left, [(s, e)])
            t = length(cut)
            if t:
                out[name] = out.get(name, 0.0) + t
                left = _subtract(left, (s, e))
        rest = length(left)
        if rest:
            out["other"] = out.get("other", 0.0) + rest
    return out


def _subtract(segs: List[Interval], cut: Interval) -> List[Interval]:
    out = []
    for a, b in segs:
        if cut[1] <= a or cut[0] >= b:
            out.append((a, b))
            continue
        if a < cut[0]:
            out.append((a, cut[0]))
        if cut[1] < b:
            out.append((cut[1], b))
    return out


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The device ops that took most time and the idle time by host
    span, in seconds per device, as the result line carries them."""
    ops = sorted(red["by_op"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}
