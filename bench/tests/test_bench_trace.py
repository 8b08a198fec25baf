"""The reduction from a device trace to numbers, on traces small
enough to count by hand, and on a slice of a trace recorded on the chip."""
import json
import os

import pytest

from bench import trace

# one device; the window runs from 100 to 200 ns. Ops: a fusion
# 100-130, a collective 120-150 (10 ns of it under the fusion), a
# custom call 150-160, another fusion 170-190 and one op that starts
# before the window. Host spans: "data" 130-175 and "dispatch" 160-200.
HAND = {
    "devices": {"0": {
        "ops": [["fusion.1", 100, 30],
                ["collective-permute-done.3", 120, 30],
                ["shard_map.9", 150, 10],
                ["fusion.2", 170, 20],
                ["copy.4", 80, 30]],
        "modules": [["jit_step(1)", 95, 100],
                    ["jit__pf(2)", 190, 30]]}},
    "host": [["window", 100, 100], ["data", 130, 45],
             ["dispatch", 160, 40]],
}


def test_busy_idle_and_matched_by_hand():
    r = trace.reduce(HAND, module_match={"prefill": "_pf"})
    assert r["window_ns"] == 100
    # busy: 100-160 and 170-190
    assert r["busy_ns"] == 80
    # clipped to the window
    assert r["by_op"] == {"fusion": 50, "collective-permute-done": 30,
                          "shard_map": 10, "copy": 10}
    assert r["modules"]["jit_step(1)"] == {"ns": 95, "count": 1}
    assert r["module_matched"]["prefill"] == {"ns": 10, "count": 1}
    # idle 160-170 under "dispatch" (the shorter span wins where both
    # cover), and 190-200 under "dispatch"
    assert r["idle_gaps"] == {"dispatch": 20}


def test_idle_outside_every_span_is_other():
    rec = {"devices": {"0": {"ops": [["a", 0, 10]], "modules": []}},
           "host": [["window", 0, 40], ["data", 20, 5]]}
    r = trace.reduce(rec)
    assert r["busy_ns"] == 10
    assert r["idle_gaps"] == {"data": 5, "other": 25}


def test_devices_are_averaged():
    rec = {"devices": {"0": {"ops": [["a", 0, 10]], "modules": []},
                       "1": {"ops": [["a", 0, 30]], "modules": []}},
           "host": [["window", 0, 40]]}
    r = trace.reduce(rec)
    assert r["devices"] == 2 and r["busy_ns"] == 20


def test_breakdown_is_in_seconds_and_capped():
    red = {"by_op": {f"op{i}": float(i) for i in range(15)},
           "idle_gaps": {"data": 2e9, "other": 1e9}}
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0] == ["op14", 14e-9]
    assert b["idle_gaps"] == [["data", 2.0], ["other", 1.0]]


def test_no_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="window"):
        trace.reduce({"devices": {}, "host": []})
    with pytest.raises(ValueError, match="device"):
        trace.reduce({"devices": {}, "host": [["window", 0, 1]]})


# 27.8 ms of the serve cell's traced window on a TPU v5e: the end of an
# admission prefill (the ``_pf`` module) and the eager splice ops after
# it, under one engine step. Op names are cut to the instruction name,
# module names to the jitted function's; times are in ns from the trace.
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_serve_v5e.json")


def _sweep_busy(intervals, lo, hi):
    """Busy time by counting the intervals open at each boundary: a
    second method beside the reduction's merge."""
    pts = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(pts):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    r = trace.reduce(rec, module_match={"prefill": "_pf"})
    lo, hi = trace.window_of(rec)
    ops = rec["devices"]["0"]["ops"]
    assert r["window_ns"] == hi - lo == 27775412
    busy = _sweep_busy([(s, s + d) for _, s, d in ops], lo, hi)
    assert r["busy_ns"] == busy == 25549544
    # every idle gap lies under the engine step's span
    assert r["idle_gaps"] == {"engine.step": 27775412 - 25549544}
    # the prefill began 20 ms before the slice and ended inside it
    assert r["module_matched"]["prefill"] == {"ns": 15775412, "count": 1}
    by_op = {}
    for n, s, d in ops:
        t = min(s + d, hi) - max(s, lo)
        if t > 0:
            g = trace.op_group(n)
            by_op[g] = by_op.get(g, 0) + t
    assert r["by_op"] == pytest.approx(by_op)
    # a layer scan's loop holds the ops it runs, so op times overlap
    assert r["by_op"]["while"] == 16596985
    assert sum(r["by_op"].values()) > r["busy_ns"]
    bd = trace.breakdown(r)
    assert bd["device_ops"][0] == ["while", 16596985 / 1e9]
