"""Observability plane (``repro.obs``): causal span traces across the
partitioned control plane, metrics shard merging, and the step
timeline.

Tier-1 drives the ``InprocCluster`` fabric for churn tracing and a
2-host ``SocketCluster`` (control-only, so the worker processes never
import jax) to prove span contexts survive pickling across real
AF_UNIX process boundaries — and that the per-signal span-tree depth
the runtime hop check measures agrees with the committed
``BENCH_dist.json`` figure for the same membership.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from repro.core.runtime import FifoScheduler
from repro.obs import (MetricsRegistry, Timeline, TraceStore,
                       check_signal_hops, pipeline_wave_events)
from repro.runtime_dist import COORD, DistCoordinator, InprocCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def coordinator(n, **kw):
    return DistCoordinator(InprocCluster(), n, seed=kw.pop("seed", 0),
                           obs=True, **kw)


# ------------------------------------------------------------------ metrics
def test_metrics_merge_rules():
    """Counters sum, gauges max, histograms fold moments + reservoir."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("ops", 3)
    b.inc("ops", 4)
    b.inc("only_b")
    a.set("occupancy", 0.25)
    b.set("occupancy", 0.75)
    for v in (1.0, 2.0, 3.0):
        a.observe("lat", v)
    b.observe("lat", 10.0)
    m = MetricsRegistry.merge([a.snapshot(), b.snapshot()])
    assert m["counters"] == {"ops": 7, "only_b": 1}
    assert m["gauges"]["occupancy"] == 0.75
    h = m["hists"]["lat"]
    assert h["count"] == 4 and h["total"] == 16.0
    assert h["min"] == 1.0 and h["max"] == 10.0
    assert sorted(h["recent"]) == [1.0, 2.0, 3.0, 10.0]
    # empty shards are inert, merge is associative over them
    assert MetricsRegistry.merge([{}, m, {}])["counters"]["ops"] == 7
    rows = MetricsRegistry.summary_rows(m)
    assert {r["metric"] for r in rows} == {"ops", "only_b", "occupancy",
                                           "lat"}


# ----------------------------------------------------- inproc churn tracing
def test_traced_churn_reconstructs_complete_span_trees():
    """join -> demote -> repromote -> evict under tracing: every causal
    tree (signal release chains, join splices, the eviction fan-out,
    epoch fingerprint rounds) reconstructs complete — every span has a
    known parent and a close — including spans recorded on the evicted
    host itself (salvaged before the process is dropped)."""
    rt = coordinator(4)
    rt.advance(step=0)
    pid = rt.request_join(step=1)
    rt.advance(step=1)
    rt.request_demote(pid, step=2)
    rt.advance(step=2)
    rt.request_repromote(pid, step=3)
    rt.advance(step=3)
    rt.request_leave(1, fail=True, step=4)
    rt.advance(step=4)
    rt.close()

    store = rt.obs.store
    for op in ("signal", "join", "evict", "demote", "repromote", "epoch"):
        assert store.trace_ids(op), f"no {op} traces recorded"
    problems = [p for t in store.traces() for p in store.problems(t)]
    assert problems == [], problems[:10]
    # signal chains actually crossed processes and did causal work
    sig = max(store.trace_ids("signal"), key=store.critical_path)
    assert store.critical_path(sig) > 0
    tree = store.tree(sig)
    assert tree["span"]["parent"] is None and tree["children"]


def test_blackholed_notifications_close_their_spans():
    """Stale notifications swallowed at the network edge after an
    eviction must close their spans with status ``blackholed`` — the
    causal tree stays complete, and the count agrees with the fabric's
    black-hole counters."""
    rt = coordinator(4)
    rt.advance(step=0)
    rt.request_leave(1, fail=True, step=1)
    rt.advance(step=1)
    rt.request_join(step=2)          # churn on top drives late frames
    rt.advance(step=2)
    nets = [rt.shard.net] + [a.shard.net
                             for a in rt.cluster.agents.values()]
    swallowed = sum(n.black_holed for n in nets)
    rt.close()
    store = rt.obs.store
    assert len(store.blackholed()) == swallowed
    problems = [p for t in store.traces() for p in store.problems(t)]
    assert problems == [], problems[:10]


def test_hop_invariant_checked_at_every_advance():
    """The O(log P) per-signal assertion runs on every quiescent phase
    advance (epoch boundaries included), and each checked window's
    measured depth is within the bound it asserted."""
    rt = coordinator(3)
    rt.advance(step=0)
    rt.request_join(step=1)
    rt.advance(step=1)               # epoch boundary
    rt.advance(step=2)
    rt.close()
    assert rt.obs.hop_checks == 3
    assert len(rt.obs.hop_check_log) == 3
    for h in rt.obs.hop_check_log:
        assert h["traces"] > 0
        assert 0 < h["max_depth"] <= h["bound"]
    assert rt.obs.metrics.counter("obs.hop_checks").value == 3


def test_check_signal_hops_rejects_deep_chains():
    tr = TraceStore()  # noqa: F841  (constructed for parity; raw recs)
    recs = [{"ev": "span", "trace": "signal:0:0:1", "span": (0, 1),
             "parent": None, "name": "signal", "src": 0, "dst": 0,
             "pid": 0, "hop": 0, "depth": 0}]
    prev = (0, 1)
    for i in range(2, 40):           # 38-deep chain >> bound at n=4
        recs.append({"ev": "span", "trace": "signal:0:0:1",
                     "span": (0, i), "parent": prev, "name": "SIG",
                     "src": 0, "dst": 1, "pid": 0, "hop": i - 1,
                     "depth": i - 1})
        prev = (0, i)
    with pytest.raises(AssertionError, match="exceeds the O\\(log P\\)"):
        check_signal_hops(recs, 4)


# ------------------------------------------------------- coordinator obs IO
def test_export_and_summary(tmp_path):
    rt = coordinator(3)
    rt.advance(step=0)
    s = rt.control_stats()["obs"]
    assert s["spans"] > 0 and s["hop_checks"] == 1
    rt.close()
    trace = str(tmp_path / "trace.json")
    metrics = str(tmp_path / "metrics.json")
    rt.export_obs(trace, metrics)
    with open(trace) as f:
        chrome = json.load(f)
    assert any(e["name"] == "epoch.derive"
               for e in chrome["traceEvents"])
    spans = [json.loads(line)
             for line in open(trace[:-5] + ".spans.jsonl")]
    assert any(r["ev"] == "span" and r["name"] == "signal"
               for r in spans)
    with open(metrics) as f:
        mj = json.load(f)
    assert mj["hop_checks"] and "rpc.obs.seconds" in \
        mj["metrics"]["hists"]


# -------------------------------------------------------------- strike obs
def test_compile_step_exempt_from_strikes():
    """The first step after a (re)compile is tagged: recorded in the
    metrics but exempt from strike accounting — warmup skew must never
    strike a healthy host."""
    from repro.runtime_elastic.strikes import StrikeEscalation
    reg = MetricsRegistry()
    esc = StrikeEscalation(slack=3.0, demote_after=2, evict_after=3,
                           metrics=reg)
    times = {0: 1.0, 1: 1.0, 2: 50.0}        # 2 looks straggly...
    assert esc.observe([0, 1, 2], times, compile_step=True) == []
    assert esc.strikes.get(2, 0) == 0        # ...but compile is exempt
    assert reg.counter("strikes.compile_steps").value == 1
    acts = esc.observe([0, 1, 2], times)     # steady state DOES strike
    assert [a.action for a in acts] == ["straggle"]
    assert reg.counter("strikes.straggle").value == 1
    assert reg.histogram("strikes.step_seconds").count == 6
    assert reg.gauge("strikes.step_median_s").value == 1.0


def test_elastic_boundary_arms_compile_exemption():
    """An elastic runtime with a re-lower hook (the data plane's
    boundary trigger) tags the first step after every epoch boundary:
    that step's skew is exempt, the next one strikes as usual."""
    from repro.runtime_elastic import ElasticPhaserRuntime
    rt = ElasticPhaserRuntime(4, seed=0)
    rt.on_epoch(lambda old, new: None)     # a data plane would re-lower
    assert rt._compile_pending is False    # boot: nothing compiled yet
    rt.request_leave(3, step=0)
    rt.advance(step=0)                     # boundary fires the hook
    assert rt._compile_pending is True
    times = {0: 1.0, 1: 1.0, 2: 50.0}
    assert rt.record_step_times(1, times) == []
    assert rt._strikes.get(2, 0) == 0      # exempt warmup step
    rt.record_step_times(2, times)
    assert rt._strikes.get(2, 0) == 1      # steady state strikes again
    assert [e.kind for e in rt.events if e.kind == "straggle"]


def test_control_only_coordinator_never_tags_compile_steps():
    """A coordinator with no data plane has nothing to re-lower, so the
    exemption must never swallow a real first-step strike (the strike
    escalation tests rely on these exact semantics)."""
    rt = coordinator(3)
    assert rt._compile_pending is False
    evicted = []
    for step in range(4):
        times = {p: (10.0 if p == 2 else 1.0) for p in rt.live}
        evicted += rt.record_step_times(step, times, slack=3.0,
                                        demote_after=2, evict_after=3)
        rt.advance(step=step)
        if evicted:
            break
    assert evicted == [2]
    rt.close()
    m = rt.obs.merged_metrics()["counters"]
    assert m.get("strikes.compile_steps", 0) == 0
    assert m["strikes.straggle"] == 3
    assert m["strikes.demote"] == 1 and m["strikes.evict"] == 1


# --------------------------------------------------------------- timeline
def test_timeline_chrome_export_and_wave_grid(tmp_path):
    from repro.pipeline_exec.schedule import derive_interleaved
    tl = Timeline()
    t0 = tl.now()
    tl.complete("train.step", t0, args={"step": 0})
    with tl.span("epoch.relower"):
        pass
    S, M, v = 2, 4, 2
    sched = derive_interleaved(S, M, v)
    waves = pipeline_wave_events(sched, label=f":S{S}M{M}v{v}")
    occupied = sum(1 for t, (kind, w) in enumerate(sched.waves)
                   for s in range(S)
                   if (sched.fwd_item(w, s) if kind == "F"
                       else sched.bwd_item(w, s)) is not None)
    assert len(waves) == occupied > 0
    tl.extend(waves)
    path = str(tmp_path / "tl.json")
    tl.save(path)
    with open(path) as f:
        chrome = json.load(f)
    names = [e["name"] for e in chrome["traceEvents"]]
    assert "train.step" in names and "epoch.relower" in names
    stages = {e["tid"] for e in chrome["traceEvents"]
              if e["cat"].startswith("pipeline")}
    assert stages == set(range(S))
    tl.save_jsonl(str(tmp_path / "tl.jsonl"))
    assert len(open(str(tmp_path / "tl.jsonl")).readlines()) == \
        len(chrome["traceEvents"])


# ------------------------------------------------------------ program spans
def test_span_times_into_histogram_and_active_timeline():
    """``span`` adds its elapsed seconds to ``<name>.seconds`` and, while
    a timeline is active, records the same interval there with its
    arguments; an exception inside still closes the span."""
    from repro.obs import activate, deactivate, span
    reg = MetricsRegistry()
    with span("unit.work", reg):
        pass
    tl = Timeline(pid=3)
    activate(tl)
    try:
        with span("unit.work", reg, rids=[7, 8]):
            pass
        with pytest.raises(KeyError):
            with span("unit.fail", reg):
                raise KeyError("x")
    finally:
        deactivate()
    h = reg.histogram("unit.work.seconds")
    assert h.count == 2 and h.total >= 0.0
    assert reg.histogram("unit.fail.seconds").count == 1
    got = [(e["name"], e["args"]) for e in tl.events]
    assert got == [("unit.work", {"rids": [7, 8]}), ("unit.fail", {})]


def test_span_keeps_jax_free_processes_jax_free():
    """The control plane's processes never import jax: ``span`` only
    annotates for the profiler when jax is already loaded."""
    code = ("import sys\n"
            "from repro.obs import MetricsRegistry, span\n"
            "reg = MetricsRegistry()\n"
            "with span('x', reg):\n"
            "    pass\n"
            "assert reg.histogram('x.seconds').count == 1\n"
            "assert 'jax' not in sys.modules, 'span imported jax'\n")
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


class _CountingFifo(FifoScheduler):
    """FifoScheduler that remembers what every ``run`` returned, and the
    most nonempty channels any of its steps chose from."""

    returned = []
    widest = 0

    def run(self, net, max_steps=10_000_000):
        n = super().run(net, max_steps)
        _CountingFifo.returned.append(n)
        return n

    def step(self, net):
        _CountingFifo.widest = max(_CountingFifo.widest,
                                   len(net.nonempty_channels()))
        return super().step(net)


def test_gate_counts_deliveries_and_sets_gauges():
    """``phaser.deliveries`` is the sum of what the protocol's scheduler
    runs returned; each join, leave and advance is one span; the gauges
    set at an advance equal the sizes they name; ``phaser.ready_channels``
    is the most nonempty channels the scheduler chose from since the
    previous advance, at most ``phaser.channels``."""
    from repro.runtime_elastic import ElasticPhaserRuntime
    _CountingFifo.returned = []
    _CountingFifo.widest = 0
    reg = MetricsRegistry()
    rt = ElasticPhaserRuntime(0, seed=1, axis_name="slots",
                              scheduler=_CountingFifo, metrics=reg)
    keys = []
    for step in range(6):
        keys.append(rt.request_join())
        if step % 2:
            rt.request_leave(keys.pop(0))
        rt.advance()
        assert reg.gauge("phaser.channels").value == len(rt.ph.net.channels)
        assert reg.gauge("phaser.actors").value == len(rt.ph.actors)
        assert reg.gauge("phaser.epochs").value == len(rt.epochs)
        ready = reg.gauge("phaser.ready_channels").value
        assert ready == _CountingFifo.widest
        assert 0 < ready <= reg.gauge("phaser.channels").value
        _CountingFifo.widest = 0
    rt.request_demote(keys[-1])
    rt.advance()
    assert reg.counter("phaser.deliveries").value == \
        sum(_CountingFifo.returned) > 0
    assert reg.histogram("phaser.join.seconds").count == 6
    assert reg.histogram("phaser.leave.seconds").count == 3
    assert reg.histogram("phaser.advance.seconds").count == 7
    # no registry given: the runtime keeps a private one
    own = ElasticPhaserRuntime(2, seed=0)
    own.advance()
    assert own.metrics.histogram("phaser.advance.seconds").count == 1
    assert own.metrics.counter("phaser.deliveries").value > 0


# ------------------------------------------- real process boundaries (fast:
# control-only workers never import jax, so spawn is cheap)
def test_socket_spans_survive_pickling_and_match_bench():
    """2 worker OS processes over AF_UNIX: span contexts ride pickled
    envelopes and the merged store still reconstructs complete trees.
    The runtime hop check's first-phase signal depth must agree with
    the committed BENCH_dist.json n=2 row — same protocol, same seed,
    same membership."""
    from repro.runtime_dist import SocketCluster
    rt = DistCoordinator(SocketCluster(control_only=True), 2, seed=0,
                         obs=True)
    rt.advance(step=0)
    phase0 = rt.obs.hop_check_log[0]["max_depth"]
    pid = rt.request_join(step=1)
    rt.advance(step=1)
    rt.request_leave(pid, step=2)
    rt.advance(step=2)
    rt.close()

    store = rt.obs.store
    for op in ("signal", "join", "evict", "epoch"):
        assert store.trace_ids(op), f"no {op} traces over sockets"
    problems = [p for t in store.traces() for p in store.problems(t)]
    assert problems == [], problems[:10]
    # spans from BOTH worker processes made it back across the wire
    pids = {r["pid"] for r in store.spans.values()}
    assert {0, 1} <= pids and COORD in pids

    bench = os.path.join(REPO, "BENCH_dist.json")
    if not os.path.exists(bench):
        pytest.skip("BENCH_dist.json not generated yet")
    with open(bench) as f:
        payload = json.load(f)
    if payload.get("schema_version", 1) < 2:
        pytest.skip("BENCH_dist.json predates trace_sig_depth")
    row = next(r for r in payload["rows"] if r["n"] == 2)
    assert phase0 == row["trace_sig_depth"], \
        (phase0, row["trace_sig_depth"])
