"""The reduction on records that hold the program's own host spans
(``serve.admit``, ``serve.decode``, ``phaser.*``) nested inside the
harness's ``engine.step``: idle time goes to the innermost span, and
every other number is the one the same record gives without them."""
import json
import os

from bench import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "trace_serve_v5e.json")

PROGRAM = ("serve.admit", "phaser.join", "serve.decode", "phaser.advance",
           "phaser.leave")

# one device, window 0-100 ns, ops 10-20 and 40-55 (busy 25). One
# engine step covers the window; inside it an admission 5-30 holding a
# join 20-28, a decode 35-60 and a gate advance 62-95.
NESTED = {
    "devices": {"0": {
        "ops": [["fusion.1", 10, 10], ["while.2", 40, 15]],
        "modules": [["jit__pf(1)", 10, 10], ["jit_decode(2)", 40, 15]]}},
    "host": [["window", 0, 100], ["engine.step", 0, 100],
             ["serve.admit", 5, 25], ["phaser.join", 20, 8],
             ["serve.decode", 35, 25], ["phaser.advance", 62, 33]],
}


def _without_program(rec):
    return {**rec, "host": [h for h in rec["host"] if h[0] not in PROGRAM]}


def _same_but_idle(a, b):
    for key in ("devices", "window_ns", "busy_ns", "modules",
                "module_matched", "by_op"):
        assert a[key] == b[key], key
    assert sum(a["idle_gaps"].values()) == sum(b["idle_gaps"].values())


def test_program_spans_take_idle_from_engine_step_by_hand():
    r = trace.reduce(NESTED, module_match={"prefill": "_pf"})
    bare = trace.reduce(_without_program(NESTED),
                        module_match={"prefill": "_pf"})
    _same_but_idle(r, bare)
    assert bare["idle_gaps"] == {"engine.step": 75}
    # idle 0-10, 20-40, 55-100: the join's 20-28 is its own, not the
    # admission's; what no program span covers stays with the step
    assert r["idle_gaps"] == {"engine.step": 17, "serve.admit": 7,
                              "phaser.join": 8, "serve.decode": 10,
                              "phaser.advance": 33}


def test_program_spans_on_recorded_chip_trace():
    """Program spans laid over the recorded slice's one engine step
    split its idle time and change nothing else. The slice's idle time
    lies in its last tenth, after the splices, and a little at 50-80%."""
    with open(RECORDED) as f:
        rec = json.load(f)
    lo, hi = trace.window_of(rec)
    at = lambda share: lo + share * (hi - lo)
    spans = [["serve.admit", lo - 1000, at(0.8) - lo + 1000],
             ["phaser.join", at(0.6), at(0.8) - at(0.6)],
             ["serve.decode", at(0.8), at(0.93) - at(0.8)],
             ["phaser.advance", at(0.95), at(0.99) - at(0.95)]]
    nested = {**rec, "host": rec["host"] + spans}
    r = trace.reduce(nested, module_match={"prefill": "_pf"})
    bare = trace.reduce(rec, module_match={"prefill": "_pf"})
    _same_but_idle(r, bare)
    assert bare["idle_gaps"] == {"engine.step": 27775412 - 25549544}
    assert set(r["idle_gaps"]) == {"engine.step", *(s[0] for s in spans)}
    busy = trace.union((s, s + d) for _, s, d in rec["devices"]["0"]["ops"])
    idle = trace.gaps(trace.clip(busy, lo, hi), lo, hi)
    covered = trace.union((s, s + d) for _, s, d in spans)
    assert r["idle_gaps"]["engine.step"] == \
        trace.length(idle) - trace.length(trace.intersect(idle, covered))
