"""Always-on flight recorder plane (DESIGN.md §14).

Tier-1 drives the three always-on layers end to end over the inproc
fabric: per-host phase watermarks with the wait-time decomposition
(monotone across churn, chaos, and generation bumps; a dead host's
watermark frozen then retired), the bounded flight ring flushed at the
failure edges, the live heartbeat frame stream plus the ``obs.watch``
dashboard that renders from it, and the ``obs.regress`` perf sentry
(synthetic +20% latency regression flagged; the committed baseline
passes against the committed artifacts).

The slow tier crosses real process boundaries: an orphaned socket
worker flushes its flight ring before its code-2 exit, a SIGKILLed
worker's survivors leave a coherent post-kill flight record on disk,
and ``obs.watch --once`` renders a 2-process socket run's ``--live-out``
stream mid-run, from the file alone.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from repro.obs import (ClusterWatermarks, FlightRecorder, LiveStreamer,
                       MetricsRegistry, TraceStore, WatermarkRegression,
                       WatermarkTracker, check_flight_file, flight_path,
                       read_frames)
from repro.runtime_dist import (COORD, ChaosConfig, DistCoordinator,
                                InprocCluster)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def coordinator(n, *, chaos=None, **kw):
    return DistCoordinator(InprocCluster(chaos=chaos), n,
                           seed=kw.pop("seed", 0), obs=True, **kw)


# -------------------------------------------------------- tracker unit
def test_watermark_tracker_decomposes_wait_time():
    """signal -> release gap accumulates into wait_s; signal/compute
    buckets are separate; snapshots are plain JSON-able dicts."""
    wm = WatermarkTracker(0)
    wm.set_mode(3, "SIG_WAIT")
    wm.on_signal(3, 0)
    time.sleep(0.01)
    wm.on_wait_advance(3, 0)
    wm.on_signal(3, 1)
    wm.on_wait_advance(3, 1)
    wm.add_signal_time(3, 0.002)
    wm.add_compute_time(3, 0.5)
    snap = json.loads(json.dumps(wm.snapshot()))
    h = snap["hosts"]["3"]
    assert h["signal"] == 1 and h["wait"] == 1
    assert h["mode"] == "SIG_WAIT"
    assert h["wait_s"] >= 0.01                 # the slept gap was seen
    assert h["signal_s"] == pytest.approx(0.002)
    assert h["compute_s"] == pytest.approx(0.5)
    assert h["outstanding"] == 0               # every signal released
    assert "0" in h["phase_waits"] or 0 in h["phase_waits"]
    # a release without a signal (replayed presig) is monotone-safe
    wm.on_wait_advance(3, 5)
    assert wm.snapshot()["hosts"][3]["wait"] == 5


def test_watermark_tracker_outstanding_is_bounded():
    """A signaler that never waits (SIG mode) must not leak timestamp
    entries without bound."""
    from repro.obs.live import _MAX_OUTSTANDING
    wm = WatermarkTracker(0)
    for p in range(_MAX_OUTSTANDING + 50):
        wm.on_signal(1, p)
    h = wm.snapshot()["hosts"][1]
    assert h["outstanding"] == _MAX_OUTSTANDING
    assert wm.dropped_outstanding == 50


def test_cluster_watermarks_monotone_retire_and_deltas():
    cw = ClusterWatermarks()

    def snap(rank, sig, wait, wait_s=0.0):
        # str keys: the snapshot crossed a JSON round-trip on the wire
        return {"pid": 0, "gen": 0, "hosts": {str(rank): {
            "signal": sig, "wait": wait, "mode": "SIG_WAIT",
            "wait_s": wait_s, "signal_s": 0.0, "compute_s": 0.0}}}

    cw.update(0, snap(1, 3, 2, wait_s=1.0), gen=0)
    cw.update(0, snap(1, 4, 3, wait_s=1.5), gen=1)   # gen bump, forward
    assert cw.view[1]["signal"] == 4
    with pytest.raises(WatermarkRegression, match="rank 1"):
        cw.update(0, snap(1, 2, 2), gen=1)           # rewind: corruption
    # strike attribution deltas: since-last-call, floor at zero
    d1 = cw.take_wait_deltas()
    assert d1 == {1: pytest.approx(1.5)}
    assert cw.take_wait_deltas() == {1: 0.0}
    cw.update(0, snap(1, 5, 4, wait_s=2.0), gen=1)
    assert cw.take_wait_deltas() == {1: pytest.approx(0.5)}
    # retirement freezes the corpse; its stale snapshots fold to nothing
    frozen = cw.retire(1)
    assert frozen["signal"] == 5 and 1 not in cw.view
    cw.update(0, snap(1, 0, 0), gen=2)               # late stale frame
    assert 1 not in cw.view and cw.retired[1]["signal"] == 5
    s = cw.summary()
    assert s["retired"][1]["wait"] == 4 and s["live"] == {}


# ------------------------------------------- inproc: churn, chaos, kill
def test_inproc_watermarks_monotone_under_chaos_and_kill():
    """The acceptance path: chaos delays + a join + a SIGKILL-style
    crash. Merged watermarks stay monotone through the generation bump
    (update() would raise WatermarkRegression otherwise), the dead
    host is frozen-then-retired, and survivors advance past the
    corpse's frozen phases."""
    rt = coordinator(4, chaos=ChaosConfig(seed=3, p_drop=0.0, p_dup=0.0,
                                          p_delay=0.4, delay_ticks=3))
    rt.advance(step=0)
    rt.request_join(step=1)
    rt.advance(step=1)
    view1 = {r: dict(h) for r, h in rt.obs.watermarks.view.items()}
    assert sorted(view1) == [0, 1, 2, 3, 4]
    rt.cluster.kill_host(2)
    for s in range(2, 6):
        rt.advance(step=s)                 # recover (gen bump) + phases
    assert rt.gen >= 1
    cw = rt.obs.watermarks
    assert 2 in cw.retired and 2 not in cw.view
    for r, h in cw.view.items():
        if r in view1:
            assert h["signal"] >= view1[r]["signal"], (r, h, view1[r])
            assert h["wait"] >= view1[r]["wait"], (r, h, view1[r])
    # survivors advanced past the frozen corpse
    assert all(h["signal"] > cw.retired[2]["signal"]
               for h in cw.view.values())
    assert all(h["wait_s"] > 0.0 for h in cw.view.values())
    s = rt.control_stats()["obs"]["watermarks"]
    assert set(s["live"]) == set(rt.live) and 2 in s["retired"]
    rt.close()


def test_inproc_cooperative_leave_retires_watermark():
    rt = coordinator(3)
    rt.advance(step=0)
    rt.request_leave(1, step=1)
    rt.advance(step=1)
    rt.advance(step=2)
    cw = rt.obs.watermarks
    assert 1 in cw.retired and sorted(cw.view) == [0, 2]
    rt.close()


def test_strikes_wait_attribution_spares_the_victim():
    """A host slow because it was *blocked on peers* is a victim, not
    a culprit: the watermark layer's wait seconds are subtracted before
    the slack test."""
    from repro.runtime_elastic.strikes import StrikeEscalation
    reg = MetricsRegistry()
    esc = StrikeEscalation(slack=3.0, metrics=reg)
    times = {0: 1.0, 1: 1.0, 2: 10.0}
    # without attribution, host 2 straggles
    assert [a.action for a in esc.observe([0, 1, 2], dict(times))] \
        == ["straggle"]
    esc.strikes.clear()
    # with 9.5s of its 10s attributed to waiting, it is exonerated
    acts = esc.observe([0, 1, 2], dict(times), waits={2: 9.5})
    assert acts == [] and esc.strikes.get(2, 0) == 0
    # but a genuinely slow host is NOT excused by someone else's waits
    acts = esc.observe([0, 1, 2], dict(times), waits={0: 0.5})
    assert [a.action for a in acts] == ["straggle"]


def test_coordinator_wait_deltas_feed_strike_observation():
    """record_step_times pulls take_wait_deltas() from the merged view;
    after a few advances the deltas drain to ~0 between calls."""
    rt = coordinator(3)
    for s in range(3):
        rt.advance(step=s)
        rt.record_step_times(s, {p: 1.0 for p in rt.live})
    # the escalation saw every step with no false strikes
    rt.close()
    m = rt.obs.merged_metrics()["counters"]
    assert m.get("strikes.straggle", 0) == 0
    assert rt.obs.watermarks.take_wait_deltas() == \
        {r: 0.0 for r in rt.obs.watermarks.view}


# ----------------------------------------------------- span retention
def test_trace_store_evicts_whole_traces_under_cap():
    def mk(trace, seq, n):
        recs = [{"ev": "span", "trace": trace, "span": [0, seq * 100 + 1],
                 "parent": None, "name": "signal",
                 "src": 0, "dst": 0, "pid": 0, "hop": 0, "depth": 0}]
        root = recs[0]["span"]
        for i in range(1, n):
            recs.append({"ev": "span", "trace": trace,
                         "span": [0, root[1] + i], "parent": list(root),
                         "name": "SIG", "src": 0, "dst": 1, "pid": 0,
                         "hop": i, "depth": i})
            recs.append({"ev": "close", "span": [0, root[1] + i],
                         "status": "delivered", "pid": 0})
        return recs

    st = TraceStore(max_spans=10)
    for t in range(6):
        st.add(mk(f"signal:0:0:{t}", t, 4))    # 24 spans through a cap
    assert len(st.spans) <= 10 + 4             # at most one trace over
    assert st.dropped_spans > 0 and st.evicted_traces > 0
    # whole-trace eviction: every retained tree is still complete
    for trace in st.trace_ids():
        assert st.problems(trace) == []
    # and a downstream exact store accepts the retention accounting
    down = TraceStore(max_spans=None)
    down.add([{"ev": "retention", "dropped_spans": st.dropped_spans,
               "evicted_traces": st.evicted_traces}])
    assert down.dropped_spans == st.dropped_spans


def test_hub_export_reflects_retention_and_survives_reload(tmp_path):
    """A capped hub store still exports a span log offline checks agree
    with: retention marker first, then complete per-trace records."""
    rt = coordinator(3)
    rt.obs.store.max_spans = 20                # force eviction pressure
    for s in range(5):
        rt.advance(step=s)
    rt.close()
    assert rt.obs.store.dropped_spans > 0
    trace = str(tmp_path / "capped.json")
    rt.export_obs(trace, None)
    recs = [json.loads(line)
            for line in open(str(tmp_path / "capped.spans.jsonl"))]
    assert recs[0]["ev"] == "retention"
    assert recs[0]["dropped_spans"] == rt.obs.store.dropped_spans
    st = TraceStore(max_spans=None)
    st.add(recs)
    assert st.dropped_spans == rt.obs.store.dropped_spans
    assert len(st.spans) == len(rt.obs.store.spans)
    for t in st.trace_ids():
        assert st.problems(t) == []
    assert rt.obs.summary()["dropped_spans"] > 0


def test_check_cli_summary_and_exit_codes(tmp_path, capsys):
    from repro.obs import check

    # 0: a clean traced run, --summary prints the one-liner
    rt = coordinator(3)
    rt.advance(step=0)
    rt.advance(step=1)
    rt.close()
    trace = str(tmp_path / "t.json")
    rt.export_obs(trace, None)
    spans = str(tmp_path / "t.spans.jsonl")
    assert check.main([spans, "--hosts", "3", "--summary",
                       "--require-ops", "signal"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("OK ") and "sig_depth=" in out

    # interleaved lost marker mid-file: tolerated, still 0
    recs = [json.loads(line) for line in open(spans)]
    mid = len(recs) // 2
    recs.insert(mid, {"ev": "lost", "pid": 99})
    lost = str(tmp_path / "lost.spans.jsonl")
    with open(lost, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert check.main([lost, "--hosts", "3"]) == 0
    assert json.loads(capsys.readouterr().out)["lost_pids"] == [99]

    # 1: an invariant violation (unclosed non-root span, live pid)
    bad = str(tmp_path / "bad.spans.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"ev": "span", "trace": "signal:0:0:1",
                            "span": [0, 1], "parent": None,
                            "name": "signal", "src": 0, "dst": 0,
                            "pid": 0, "hop": 0, "depth": 0}) + "\n")
        f.write(json.dumps({"ev": "span", "trace": "signal:0:0:1",
                            "span": [0, 2], "parent": [0, 1],
                            "name": "SIG", "src": 0, "dst": 1,
                            "pid": 0, "hop": 1, "depth": 1}) + "\n")
    assert check.main([bad, "--hosts", "2", "--summary"]) == 1
    assert "FAIL" in capsys.readouterr().out

    # 2: unreadable input is distinct from a protocol violation
    assert check.main([str(tmp_path / "absent.jsonl"),
                       "--hosts", "2"]) == 2
    garbled = str(tmp_path / "garbled.jsonl")
    with open(garbled, "w") as f:
        f.write("not json at all\n")
    assert check.main([garbled, "--hosts", "2"]) == 2


# ------------------------------------------------------- flight ring
def test_flight_ring_bounds_and_coherent_flush(tmp_path):
    fr = FlightRecorder(3, cap=8)
    for i in range(20):
        fr.event("step", step=i)
    assert len(fr) == 8 and fr.dropped == 12
    path = flight_path(str(tmp_path), 3)
    assert path.endswith("worker3.flight.jsonl")
    assert fr.flush(path, "test") == 8
    s = check_flight_file(path)
    assert s["problems"] == [] and s["records"] == 8
    assert s["pid"] == 3 and s["reason"] == "test" and s["dropped"] == 12
    # the ring keeps the LATEST window
    recs = [json.loads(line) for line in open(path)][1:]
    assert [r["step"] for r in recs] == list(range(12, 20))
    assert flight_path(str(tmp_path), COORD).endswith(
        "coord.flight.jsonl")


def test_flight_checker_cli_verdicts(tmp_path, capsys):
    from repro.obs import recorder

    # empty dir fails the min-files floor
    assert recorder.main([str(tmp_path)]) == 1
    capsys.readouterr()
    fr = FlightRecorder(0)
    fr.event("release", phase=0)
    fr.event("release", phase=1)
    fr.flush(flight_path(str(tmp_path), 0), "test")
    assert recorder.main([str(tmp_path), "--min-files", "1"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] and rep["records"] == 2
    # an incoherent file (headerless) flips the verdict
    with open(flight_path(str(tmp_path), 1), "w") as f:
        f.write(json.dumps({"ev": "event", "kind": "step", "pid": 1,
                            "t": 1.0}) + "\n")
    assert recorder.main([str(tmp_path)]) == 1


def test_inproc_kill_flushes_survivor_flight_records(tmp_path):
    """Non-cooperative eviction: the corpse wrote nothing, but recovery
    flushes the coordinator's ring and every survivor's — the window
    around the death is on disk, and the checker calls it coherent."""
    from repro.obs import recorder
    fdir = str(tmp_path / "flight")
    rt = coordinator(4, flight_dir=fdir)
    rt.advance(step=0)
    rt.cluster.kill_host(2)
    rt.advance(step=1)                     # recover + flush + advance
    files = sorted(os.listdir(fdir))
    assert files == ["coord.flight.jsonl", "worker0.flight.jsonl",
                     "worker1.flight.jsonl", "worker3.flight.jsonl"]
    for name in files:
        s = check_flight_file(os.path.join(fdir, name))
        assert s["problems"] == [], (name, s["problems"])
        assert s["reason"] == "peer-dead" and s["records"] > 0
    # survivor rings recorded the rebuild edge (gen bump) bracketed by
    # teed span records; the coordinator's ring has the phase releases
    # (on_release fires on the HEAD owner)
    recs = [json.loads(line) for line in
            open(os.path.join(fdir, "worker0.flight.jsonl"))]
    kinds = {r.get("kind") for r in recs if r.get("ev") == "event"}
    assert {"rebuild", "membership"} <= kinds
    assert any(r.get("ev") == "span" for r in recs)
    coord_recs = [json.loads(line) for line in
                  open(os.path.join(fdir, "coord.flight.jsonl"))]
    assert any(r.get("ev") == "event" and r.get("kind") == "release"
               for r in coord_recs)
    assert recorder.main([fdir, "--min-files", "4"]) == 0
    # cooperative leave flushes the departing host's ring too
    rt.request_leave(1, step=2)
    rt.advance(step=2)
    s = check_flight_file(os.path.join(fdir, "worker1.flight.jsonl"))
    assert s["reason"] == "leave" and s["problems"] == []
    rt.close()


# ---------------------------------------------------- live stream + watch
def test_live_streamer_cadence_deltas_and_torn_tail(tmp_path):
    path = str(tmp_path / "live.jsonl")
    ls = LiveStreamer(path, min_interval=60.0)
    reg = MetricsRegistry()
    reg.inc("adv", 3)
    reg.observe("rpc.obs.seconds", 0.004)
    m = {"counters": dict(reg.snapshot()["counters"]),
         "hists": reg.snapshot()["hists"]}
    assert ls.frame(step=0, phase=1, epoch=0, gen=0, live=[0, 1],
                    merged_metrics=m, events=[[0, "join", 1]],
                    force=True)      # pin the cadence window start
    # cadence: a second frame inside the interval is suppressed...
    assert not ls.frame(step=1, phase=2, epoch=0, gen=0, live=[0, 1])
    assert ls.suppressed == 1
    # ...unless forced (failure edges must not be rate-limited away)
    m2 = {"counters": {"adv": 5}, "hists": {}}
    assert ls.frame(step=2, phase=3, epoch=0, gen=1, live=[0],
                    merged_metrics=m2, events=[[0, "join", 1],
                                               [2, "dead", 1]],
                    force=True)
    ls.close()
    frames = read_frames(path)
    assert [f["phase"] for f in frames] == [1, 3]
    assert frames[0]["deltas"] == {"adv": 3}
    assert frames[1]["deltas"] == {"adv": 2}         # delta, not total
    assert frames[0]["rpc"]["obs"]["p50"] > 0
    assert frames[0]["events"] == [[0, "join", 1]]
    assert frames[1]["events"] == [[2, "dead", 1]]   # only the new one
    # a torn tail (writer mid-append) parses up to the tear
    with open(path, "a") as f:
        f.write('{"v":1,"step":3,"pha')
    assert [f["step"] for f in read_frames(path)] == [0, 2]


def test_inproc_live_frames_and_watch_render(tmp_path, capsys):
    from repro.obs import watch
    out = str(tmp_path / "run.live.jsonl")
    rt = coordinator(3, live_out=out)
    for s in range(3):
        rt.advance(step=s)
    rt.cluster.kill_host(1)
    rt.advance(step=3)
    rt.close()
    frames = read_frames(out)
    assert frames, "no live frames written"
    # phases never rewind across the frame stream, gen bump included
    phases = [f["phase"] for f in frames]
    assert phases == sorted(phases)
    assert frames[-1]["gen"] >= 1 and frames[-1]["live"] == [0, 2]
    last_wm = frames[-1]["wm"]
    assert sorted(last_wm) == ["0", "2"] and "1" in frames[-1]["retired"]
    assert all("wait_s" in h for h in last_wm.values())
    # the dashboard renders the same file standalone
    assert watch.main([out, "--once"]) == 0
    text = capsys.readouterr().out
    assert "live phaser run" in text and "dead" in text
    assert f"gen {frames[-1]['gen']}" in text
    # exit codes: empty stream -> 1, missing file -> 2
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert watch.main([empty, "--once"]) == 1
    assert watch.main([str(tmp_path / "gone.jsonl"), "--once"]) == 2
    # --json dumps the raw last frame
    assert watch.main([out, "--once", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["gen"] == \
        frames[-1]["gen"]


# ------------------------------------------------------ regression sentry
def test_regress_flags_synthetic_latency_regression(tmp_path, capsys):
    from repro.obs import regress
    fresh = tmp_path / "fresh"
    fresh.mkdir()
    bench = {"schema_version": 1,
             "ms_per_step": {"eager": 100.0, "overlapped": 80.0},
             "eager_over_overlapped": 1.25,
             "overlapped_bitwise_equals_eager": True}
    (fresh / "BENCH_collective.json").write_text(json.dumps(bench))
    base = str(tmp_path / "BENCH_BASELINE.json")
    assert regress.main(["--fresh", str(fresh), "--baseline", base,
                         "--seed"]) == 0
    assert regress.main(["--fresh", str(fresh),
                         "--baseline", base]) == 0   # self-compare clean
    capsys.readouterr()

    # +20% latency: beyond the 15% band, flagged in the bad direction
    bench["ms_per_step"]["overlapped"] = 96.0
    (fresh / "BENCH_collective.json").write_text(json.dumps(bench))
    rc = regress.main(["--fresh", str(fresh), "--baseline", base,
                       "--json", str(tmp_path / "diff.json")])
    assert rc == 1
    assert "REGRESSION" in capsys.readouterr().out
    rep = json.load(open(str(tmp_path / "diff.json")))
    assert [r["metric"] for r in rep["regressions"]] == \
        ["ms_per_step.overlapped"]
    assert rep["regressions"][0]["delta_pct"] == pytest.approx(20.0)
    # --warn-only reports but exits clean (CI smoke on shared machines)
    assert regress.main(["--fresh", str(fresh), "--baseline", base,
                         "--warn-only"]) == 0

    # a -20% (faster) move in the same band is an improvement, not a
    # regression — direction-aware, not magnitude-aware
    bench["ms_per_step"]["overlapped"] = 64.0
    (fresh / "BENCH_collective.json").write_text(json.dumps(bench))
    assert regress.main(["--fresh", str(fresh), "--baseline", base]) == 0

    # boolean flip is always a regression, tolerance be damned
    bench["ms_per_step"]["overlapped"] = 80.0
    bench["overlapped_bitwise_equals_eager"] = False
    (fresh / "BENCH_collective.json").write_text(json.dumps(bench))
    assert regress.main(["--fresh", str(fresh), "--baseline", base]) == 1

    # a schema bump sidesteps comparison with a warning, never a failure
    bench["overlapped_bitwise_equals_eager"] = True
    bench["schema_version"] = 2
    (fresh / "BENCH_collective.json").write_text(json.dumps(bench))
    assert regress.main(["--fresh", str(fresh), "--baseline", base]) == 0
    assert "schema_version" in capsys.readouterr().out

    # unreadable baseline is its own exit code
    assert regress.main(["--fresh", str(fresh),
                         "--baseline", str(tmp_path / "nope.json")]) == 2


def test_regress_committed_baseline_passes_committed_artifacts():
    """The acceptance gate CI runs: the committed BENCH_*.json compared
    against the committed BENCH_BASELINE.json must be clean (the
    baseline was seeded from those exact artifacts)."""
    from repro.obs import regress
    base = os.path.join(REPO, "BENCH_BASELINE.json")
    if not os.path.exists(base):
        pytest.skip("BENCH_BASELINE.json not seeded yet")
    baseline = json.load(open(base))
    report = regress.compare(baseline, REPO)
    assert report["ok"], report["regressions"]
    assert report["compared"] > 20
    # no schema drift between the committed pair
    assert not [w for w in report["warnings"]
                if "schema_version" in w], report["warnings"]


# --------------------------------------------------- serve latency hists
def _serve_engine(batch: int = 2, window: int = 32):
    """The reduced smollm-135m engine the serve tests drive."""
    jax = pytest.importorskip("jax")
    from repro.models.registry import get_api, get_config
    from repro.serve.engine import ServeEngine

    cfg = get_config("smollm-135m").reduced()
    api = get_api(cfg)
    params = api.init_params(jax.random.key(0))
    return ServeEngine(api, params, batch=batch, window=window)


def _requests(n: int):
    import numpy as np
    from repro.serve.engine import Request
    return [Request(rid=i, prompt=np.array([1 + i, 2, 3, 4][:2 + i % 3],
                                           np.int32), max_new=2 + i % 3)
            for i in range(n)]


def test_serve_engine_latency_histograms():
    """Admission queue-wait and per-token decode latency land in the
    engine's metrics shard as histograms with readable quantiles."""
    eng = _serve_engine()
    import numpy as np
    from repro.serve.engine import Request
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.array([1 + i, 2, 3],
                                                  np.int32), max_new=2))
    eng.run_until_drained()
    snap = eng.metrics.snapshot()["hists"]
    qw = snap["serve.admit.queue_wait_seconds"]
    tok = snap["serve.decode.seconds"]
    assert qw["count"] == 3                    # one wait per admission
    assert tok["count"] >= 2                   # one observation per step
    for h in (qw, tok):
        p50 = MetricsRegistry.hist_quantile(h, 0.5)
        p99 = MetricsRegistry.hist_quantile(h, 0.99)
        assert p50 is not None and p99 is not None and p99 >= p50 > 0
    # bucket counts carry the mass (quantiles work on merged shards)
    merged = MetricsRegistry.merge([eng.metrics.snapshot()])
    assert sum(merged["hists"]["serve.decode.seconds"]
               ["buckets"]) == tok["count"]


def test_serve_spans_count_admissions_decodes_and_gate():
    """One ``serve.admit`` per step that admits, one ``serve.decode`` per
    step that decodes, one ``phaser.advance`` per gate advance, one
    ``phaser.join`` and one ``phaser.leave`` per request; the engine's
    shard holds the gate's counters, and the spans of a drained run fit
    in its wall time. Each advance sets ``phaser.ready_channels``, at
    most ``phaser.channels``."""
    eng = _serve_engine()
    reqs = _requests(5)
    for r in reqs:
        eng.submit(r)
    advances = [0]
    ready = []                   # (ready_channels, channels) per advance
    advance = eng.gate.advance

    def counted(**kw):
        advances[0] += 1
        out = advance(**kw)
        ready.append((eng.metrics.gauge("phaser.ready_channels").value,
                      eng.metrics.gauge("phaser.channels").value))
        return out

    eng.gate.advance = counted
    admitting = decoding = 0
    t0 = time.perf_counter()
    while True:
        admitting += bool(eng.queue) and None in eng.slot_req
        n = eng.step()
        decoding += n > 0
        if n == 0 and not eng.queue:
            break
    wall = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    reg = eng.metrics
    hist = lambda name: reg.histogram(name + ".seconds")
    assert hist("serve.admit").count == admitting >= 3
    assert hist("serve.decode").count == decoding
    assert hist("phaser.advance").count == advances[0] >= decoding
    assert hist("phaser.join").count == len(reqs)
    assert hist("phaser.leave").count == len(reqs)
    # joins nest inside admissions; every leave here follows a decode,
    # outside the other spans, so the top-level spans are disjoint
    assert hist("phaser.join").total <= hist("serve.admit").total
    top = sum(hist(n).total for n in ("serve.admit", "serve.decode",
                                      "phaser.advance", "phaser.leave"))
    assert 0 < top <= wall
    assert reg.counter("phaser.deliveries").value > 0
    ph = eng.gate.ph
    assert reg.gauge("phaser.channels").value == len(ph.net.channels)
    assert reg.gauge("phaser.actors").value == len(ph.actors)
    assert reg.gauge("phaser.epochs").value == len(eng.gate.epochs)
    assert len(ready) == advances[0]
    assert all(0 <= r <= c for r, c in ready)
    assert max(r for r, _ in ready) > 0


def test_serve_engine_unchanged_under_profiler(tmp_path):
    """With ``jax.profiler`` tracing around it the engine answers as it
    does with no profiler, and the trace's host plane holds the
    program's spans, the admissions with their request ids."""
    jax = pytest.importorskip("jax")
    import glob
    plain = _serve_engine()
    for r in _requests(4):
        plain.submit(r)
    want = [(r.rid, r.out) for r in plain.run_until_drained()]
    traced = _serve_engine()
    for r in _requests(4):
        traced.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        got = [(r.rid, r.out) for r in traced.run_until_drained()]
    assert got == want
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = ("serve.admit", "serve.decode", "phaser.advance",
             "phaser.join", "phaser.leave")
    seen = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert sorted(seen) == sorted(names)
    rids = "".join(st.get("rids", "") for st in seen["serve.admit"])
    assert all(str(i) in rids for i in range(4)), rids


# ------------------------------------------- slow: real process boundaries
@pytest.mark.slow
def test_socket_orphan_exit_flushes_flight_ring():
    """An orphaned worker (coordinator gone silent) flushes its flight
    ring next to its span shard before the code-2 exit."""
    code = """
import os
os.chdir({root!r})
from repro.runtime_dist import SocketCluster
from repro.obs.recorder import check_flight_file

cl = SocketCluster(control_only=True, hb_interval=0.1, failure_timeout=1.0,
                   orphan_timeout=2.0)
cl.add_host(0, {{"pid": 0, "n": 1, "seed": 0, "control_only": True}})
p = cl.procs[0]
cl._hb_stop.set()                   # simulate coordinator crash: silence
cl._hb_thread.join(timeout=5)
cl.ep.close()
rc = p.wait(timeout=30)
assert rc == 2, rc
path = os.path.join(cl.dir, "worker0.flight.jsonl")
assert os.path.exists(path), path
s = check_flight_file(path)
assert s["problems"] == [], s["problems"]
assert s["reason"] == "orphan" and s["records"] > 0
import json
recs = [json.loads(l) for l in open(path)]
exits = [r for r in recs if r.get("ev") == "event"
         and r.get("kind") == "exit"]
assert exits and exits[-1]["reason"] == "orphan"
print("OK")
""".format(root=REPO)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH":
                              os.path.join(REPO, "src")},
                         cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_socket_kill9_leaves_coherent_flight_record(tmp_path):
    """The chaos-smoke acceptance: SIGKILL a worker OS process, let the
    survivors recover, and find a coherent non-empty flight record on
    disk — coordinator plus every survivor (the corpse wrote nothing,
    its final phases live in the survivors' rings)."""
    fdir = str(tmp_path / "flight")
    code = """
import os
os.chdir({root!r})
from repro.runtime_dist import DistCoordinator, SocketCluster

cl = SocketCluster(control_only=True, hb_interval=0.1, failure_timeout=2.0)
rt = DistCoordinator(cl, 3, seed=0, flight_dir={fdir!r})
rt.advance(step=0)
cl.kill_pid(1)                             # SIGKILL, no cleanup
for s in range(1, 4):
    rt.advance(step=s)                     # detect + evict + keep going
assert sorted(rt.live) == [0, 2], rt.live
rt.close()
print("OK")
""".format(root=REPO, fdir=fdir)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH":
                              os.path.join(REPO, "src")},
                         cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
    files = sorted(os.listdir(fdir))
    assert "coord.flight.jsonl" in files
    assert "worker0.flight.jsonl" in files
    assert "worker2.flight.jsonl" in files
    assert "worker1.flight.jsonl" not in files     # the corpse: nothing
    for name in files:
        s = check_flight_file(os.path.join(fdir, name))
        assert s["problems"] == [], (name, s["problems"])
        assert s["records"] > 0 and s["reason"] == "peer-dead"
    # the checker CLI agrees (what chaos-smoke runs in CI)
    from repro.obs import recorder
    assert recorder.main([fdir, "--min-files", "3"]) == 0


@pytest.mark.slow
def test_socket_live_out_renders_midrun(tmp_path):
    """A 2-process socket run streaming --live-out: `obs.watch --once`
    renders mid-run from the file alone (the watcher never talks to the
    run), and the stream stays monotone through churn."""
    live = str(tmp_path / "run.live.jsonl")
    code = """
import os, subprocess, sys
os.chdir({root!r})
from repro.runtime_dist import DistCoordinator, SocketCluster

rt = DistCoordinator(SocketCluster(control_only=True), 2, seed=0,
                     live_out={live!r})
for s in range(3):
    rt.advance(step=s)
# mid-run: the coordinator is alive, the watcher reads the file only
w = subprocess.run([sys.executable, "-m", "repro.obs.watch",
                    {live!r}, "--once"],
                   capture_output=True, text=True,
                   env={{**os.environ,
                        "PYTHONPATH": os.path.join({root!r}, "src")}},
                   timeout=60)
assert w.returncode == 0, w.stderr[-2000:]
assert "live phaser run" in w.stdout, w.stdout
assert "wait_s" in w.stdout or "blocked(s)" in w.stdout, w.stdout
pid = rt.request_join(step=3)
rt.advance(step=3)
rt.request_leave(pid, step=4)
rt.advance(step=4)
rt.close()
print("OK")
""".format(root=REPO, live=live)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH":
                              os.path.join(REPO, "src")},
                         cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
    frames = read_frames(live)
    assert frames
    phases = [f["phase"] for f in frames]
    assert phases == sorted(phases)
    assert any("wm" in f for f in frames)
