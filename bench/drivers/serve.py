"""Serving cells: open-loop arrivals through the program's ServeEngine.

Requests arrive on a schedule fixed by the mix and the seed (Poisson
gaps, lognormal prompt and output lengths) and go to
``ServeEngine.submit``; the loop calls ``ServeEngine.step`` while any
request is queued or running and sleeps until the next arrival
otherwise. A token has reached the host when the step that made it
returns. Time to first token runs from when a request was due; a
request that never finishes counts as infinitely late. Set-up makes the
weights on the device and admits groups of every size at every prompt
bucket, so the window compiles nothing. After the window closes and the
engine is freed, a sample of the finished requests, the longest among
them, is run through the plain reference.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass
from typing import Dict, List

import jax
import numpy as np

from bench import model_ref, program, traffic_gen
from bench.harness import Context, Outcome, memory_peak


@dataclass
class Track:
    plan: traffic_gen.Planned
    req: object
    submitted: float = math.nan
    first: float = math.nan
    last: float = math.nan
    seen: int = 0


@dataclass
class StepRecord:
    t0: float
    t1: float
    live_slots: int
    live_positions: int
    admitted: int


def warm_up(engine, Request, mix: Dict, slots: int, vocab: int) -> int:
    """Admit every group size at every prompt bucket the mix can reach,
    between decodes: each executable the window can use is made here,
    for a cache that decode steps have already written. Returns the
    number of requests used."""
    rng = np.random.default_rng(0)
    n = 0

    def one(length: int, max_new: int) -> None:
        nonlocal n
        engine.submit(Request(rid=-1 - n, max_new=max_new, prompt=rng.integers(
            0, vocab, length).astype(np.int32)))
        n += 1

    one(mix["prompt"]["min"], 4)
    engine.run_until_drained()
    for b in traffic_gen.buckets(mix["prompt"]["min"],
                                 mix["prompt"]["max"]):
        for g in range(1, slots + 1):
            for _ in range(g):
                one(b, 1)
            engine.step()
    one(mix["prompt"]["min"], 4)
    engine.run_until_drained()
    return n


def build(ctx: Context):
    """The engine with seeded weights, warmed up."""
    from repro.serve.engine import Request, ServeEngine
    mix, cfg = ctx.cell.traffic, ctx.cell.config
    api = program.model_api(cfg)
    params = model_ref.make_weights(cfg, ctx.seed, cfg["torch_dtype"],
                                    device=ctx.devices[0])
    engine = ServeEngine(api, params, batch=mix["slots"],
                         window=mix["window"])
    warm_up(engine, Request, mix, mix["slots"], cfg["vocab_size"])
    return engine


def open_loop(ctx: Context, engine, mix: Dict, seed: int,
              seconds: float, trace_seconds: float = 0.0) -> Dict:
    """Offer the mix's requests for ``seconds`` and serve until every one
    has finished or the drain time is up. With ``trace_seconds`` the
    profiler records that long a slice in the middle of the window."""
    from repro.serve.engine import Request
    cfg = ctx.cell.config
    planned = traffic_gen.plan(mix, seed, seconds, cfg["vocab_size"])
    tracks = [Track(plan=p, req=Request(rid=p.rid, prompt=p.prompt,
                                        max_new=p.max_new))
              for p in planned]
    steps: List[StepRecord] = []
    trace_at = (max(0.0, (seconds - trace_seconds) / 2)
                if trace_seconds else math.inf)
    slice_ = [math.inf, -math.inf]
    ann = None
    compiles0 = ctx.compiles.count
    t0 = time.perf_counter()
    close = t0 + seconds
    give_up = close + mix["drain_seconds"]
    nxt, open_ = 0, []
    while True:
        now = time.perf_counter()
        if ann is None and now - t0 >= trace_at and slice_[1] < 0:
            ctx.capture.start()
            ann = jax.profiler.TraceAnnotation("window")
            ann.__enter__()
            slice_[0] = time.perf_counter()
        elif ann is not None and (now - slice_[0] >= trace_seconds
                                  or now >= close):
            ann.__exit__(None, None, None)
            slice_[1] = time.perf_counter()
            ann = None
            ctx.capture.stop()
        if nxt < len(tracks) and t0 + tracks[nxt].plan.due <= now:
            with jax.profiler.TraceAnnotation("submit"):
                while nxt < len(tracks) and \
                        t0 + tracks[nxt].plan.due <= now:
                    tr = tracks[nxt]
                    tr.submitted = time.perf_counter()
                    engine.submit(tr.req)
                    open_.append(tr)
                    nxt += 1
        if open_:
            s0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.step"):
                engine.step()
            s1 = time.perf_counter()
            live = pos = admitted = 0
            still = []
            for tr in open_:
                n = len(tr.req.out)
                if n > tr.seen:
                    if tr.seen == 0:
                        tr.first = s1
                        admitted += 1
                    if n > 1:
                        live += 1
                        pos += len(tr.plan.prompt) + n - 2
                    tr.last = s1
                    tr.seen = n
                if not tr.req.done:
                    still.append(tr)
            open_ = still
            steps.append(StepRecord(s0, s1, live, pos, admitted))
        elif nxt < len(tracks):
            with jax.profiler.TraceAnnotation("idle"):
                time.sleep(max(0.0, t0 + tracks[nxt].plan.due
                               - time.perf_counter()))
        else:
            break
        if time.perf_counter() > give_up:
            break
    if ann is not None:
        ann.__exit__(None, None, None)
        slice_[1] = time.perf_counter()
        ctx.capture.stop()
    end = time.perf_counter()
    ttft, tpot, failed = [], [], 0
    for tr in tracks:
        if not (tr.req.done and len(tr.req.out) == tr.plan.max_new):
            failed += 1
            ttft.append(math.inf)
            tpot.append(math.inf)
            continue
        ttft.append(tr.first - (t0 + tr.plan.due))
        tpot.append((tr.last - tr.first) / (tr.seen - 1))
    late = [tr.submitted - (t0 + tr.plan.due) for tr in tracks
            if not math.isnan(tr.submitted)]
    bound = (end - t0) * 1e3
    cap = lambda v: v if math.isfinite(v) else bound
    return {"tracks": tracks, "steps": steps, "slice": slice_,
            "failed": failed, "ttft": ttft, "tpot": tpot,
            "ttft_p90_ms": cap(traffic_gen.percentile(ttft, 90) * 1e3),
            "tpot_p90_ms": cap(traffic_gen.percentile(tpot, 90) * 1e3),
            "ttft_p50_ms": cap(traffic_gen.percentile(ttft, 50) * 1e3),
            "tpot_p50_ms": cap(traffic_gen.percentile(tpot, 50) * 1e3),
            "late_max_s": max(late, default=0.0),
            "compiles": ctx.compiles.count - compiles0,
            "served_s": end - t0, "close": close}


def step_median(steps: List[StepRecord]) -> float:
    return float(np.median([s.t1 - s.t0 for s in steps])) if steps else 0.0


def reference_sample(tracks, seed: int, want: Dict):
    """Finished requests for the reference: the longest, then others in
    an order drawn from the seed, until enough served tokens."""
    done = [tr for tr in tracks if tr.req.done]
    if not done:
        return []
    rng = np.random.default_rng(seed)
    longest = max(done, key=lambda t: len(t.plan.prompt) + t.seen)
    sample = [longest]
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    while (rest and len(sample) < want["max_requests"]
           and sum(t.seen for t in sample) < want["min_tokens"]):
        sample.append(rest.pop())
    return [(np.asarray(t.plan.prompt), list(t.req.out)) for t in sample]


def widest_gap(cfg: Dict, mix: Dict, params, seqs, quant=None) -> float:
    pad = mix["prompt"]["max"] + mix["output"]["max"]
    widest = -math.inf
    for prompt, out in seqs:
        g = model_ref.served_gaps(cfg, params, prompt, out, pad_to=pad,
                                  quant=quant)
        widest = max(widest, float(g.max()))
    return widest


def run(ctx: Context) -> Outcome:
    cell, mix, cfg = ctx.cell, ctx.cell.traffic, ctx.cell.config
    engine = build(ctx)
    setup_s = time.time() - ctx.t_start
    r = open_loop(ctx, engine, mix, ctx.seed, ctx.seconds,
                  mix["trace_seconds"] if ctx.traced else 0.0)
    peak = memory_peak(ctx.devices)
    tracks = r["tracks"]
    ctx.log(f"# window: {len(tracks)} requests due in {ctx.seconds} s, "
            f"{len(tracks) - r['failed']} finished after "
            f"{r['served_s']:.2f} s; {len(r['steps'])} engine steps, "
            f"median {1e3 * step_median(r['steps']):.1f} ms; ttft p50 "
            f"{r['ttft_p50_ms']:.1f} ms, tpot p50 {r['tpot_p50_ms']:.1f} ms; "
            f"compiles in window {r['compiles']}; generator late by at "
            f"most {r['late_max_s'] * 1e3:.2f} ms; set-up {setup_s:.2f} s")
    seqs = reference_sample(tracks, ctx.seed, mix["ref_sample"])
    facts = serve_facts(cfg, mix, r["steps"], r["slice"], r["close"])
    facts.update(compiles_in_window=r["compiles"], sample=seqs,
                 failed=r["failed"], attempted=len(tracks),
                 ttft_p90_ms=r["ttft_p90_ms"], tpot_p90_ms=r["tpot_p90_ms"])
    del engine, tracks
    r.clear()
    gc.collect()
    red = None
    if ctx.capture is not None:
        from bench import trace
        red = trace.reduce(ctx.capture.record(),
                           module_match=mix["trace_modules"])
    ref_params = model_ref.make_weights(cfg, ctx.seed, cfg["torch_dtype"],
                                        device=ctx.devices[0])
    widest = widest_gap(cfg, mix, ref_params, seqs) if seqs else math.inf
    ctx.log(f"# reference over {len(seqs)} requests, "
            f"{sum(len(o) for _, o in seqs)} served tokens: widest gap "
            f"{widest!r}")
    checks = [("logit_gap", widest, cell.limits["logit_gap"]),
              ("unanswered", float(facts["failed"]), 0.0),
              ("compiles_in_window", float(facts["compiles_in_window"]),
               0.0)]
    return Outcome(
        attempted=facts["attempted"], failed=facts["failed"],
        metrics={"ttft_p90_ms": facts["ttft_p90_ms"],
                 "tpot_p90_ms": facts["tpot_p90_ms"], "setup_s": setup_s},
        checks=checks, memory_peak_bytes=peak, trace=red, facts=facts)


def serve_facts(cfg: Dict, mix: Dict, steps: List[StepRecord], slice_,
                close: float) -> Dict:
    """For the per-layer readers: the host time of every engine step
    begun in the window outside the traced slice, where the profiler
    slows the host; for each of those steps that decoded its live slots,
    live cache positions and host time; and the requests admitted inside
    the traced slice, whose device time the trace holds."""
    lo, hi = slice_
    inside = [s for s in steps if s.t0 >= lo and s.t1 <= hi]
    clear = [s for s in steps if s.t0 < close and (s.t1 < lo or s.t0 > hi)]
    return {"config": cfg,
            "step_s": [s.t1 - s.t0 for s in clear],
            "decode_steps": [[s.live_slots, s.live_positions, s.t1 - s.t0]
                             for s in clear if s.live_slots],
            "admitted_in_slice": sum(s.admitted for s in inside)}
