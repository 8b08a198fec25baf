"""ElasticPhaserRuntime: epoch/schedule-swap lifecycle (DESIGN.md §3).

Three layers of evidence that the elastic control plane is sound:

1. deterministic scripted churn — every epoch's compiled schedule matches
   the deterministic skip-list oracle AND the converged protocol actors;
2. a hypothesis property sweep over arbitrary join/leave/step sequences
   (skipped where the dev-only dependency is missing);
3. numeric: the per-epoch ``phaser_scsl`` all-reduce equals ``xla_psum``
   on a real 8-device host mesh as the team grows and shrinks
   (subprocess: device count is init-locked).
"""
import subprocess
import sys

import numpy as np
import pytest

from repro.core.collective import ALLREDUCE_KINDS, PhaserCollective
from repro.runtime_elastic import ElasticPhaserRuntime


# ------------------------------------------------------ epoch semantics
def test_epoch_boundary_semantics():
    rt = ElasticPhaserRuntime(4, seed=0)
    assert rt.epoch.index == 0 and rt.epoch.live == (0, 1, 2, 3)
    w = rt.request_join()
    # eager on the control plane, lazy on the data plane:
    assert w in rt.live and rt.epoch.live == (0, 1, 2, 3)
    assert rt.advance() == 0
    assert rt.epoch.index == 1 and rt.epoch.live == (0, 1, 2, 3, 4)
    rt.verify_epoch()
    rt.request_leave(w, fail=True)
    rt.request_leave(1)
    assert rt.epoch.live == (0, 1, 2, 3, 4)      # still the old epoch
    assert rt.advance() == 1
    assert rt.epoch.index == 2 and rt.epoch.live == (0, 2, 3)
    rt.verify_epoch()
    # no churn -> no new epoch
    assert rt.advance() == 2
    assert rt.epoch.index == 2
    kinds = [e.kind for e in rt.events]
    assert kinds == ["join", "fail", "leave"]


def test_epoch_phase_starts_are_monotone_and_gapless():
    rt = ElasticPhaserRuntime(3, seed=1)
    rt.advance()
    rt.request_join()
    rt.advance()
    rt.advance()
    rt.request_leave(0)
    rt.advance()
    starts = [e.phase_start for e in rt.epochs]
    assert starts == sorted(starts)
    assert all(b > a for a, b in zip(starts, starts[1:]))


def test_on_epoch_hook_fires_with_old_and_new():
    rt = ElasticPhaserRuntime(4, seed=0)
    seen = []
    rt.on_epoch(lambda old, new: seen.append((old.index, new.index,
                                              old.live, new.live)))
    rt.request_join()
    rt.advance()
    rt.advance()                      # no churn: hook must not fire
    assert seen == [(0, 1, (0, 1, 2, 3), (0, 1, 2, 3, 4))]


def test_kind_kept_for_non_pow2_teams():
    """Since the elimination derivations every kind covers every team
    size: a non-pow2 epoch keeps the preferred schedule (the historical
    fallback to phaser_scsl is gone)."""
    rt = ElasticPhaserRuntime(4, seed=0, kind="recursive_doubling")
    assert rt.epoch.kind == "recursive_doubling"
    rt.request_join()
    rt.advance()
    assert rt.epoch.n == 5 and rt.epoch.kind == "recursive_doubling"
    assert rt.epoch.collective.rd.ops[-1] == "copy"   # elimination form
    for _ in range(3):
        rt.request_join()
    rt.advance()
    assert rt.epoch.n == 8 and rt.epoch.kind == "recursive_doubling"
    assert rt.epoch.collective.rd.ops == ("add",) * 3  # pure hypercube
    rt.verify_epoch()


def test_scripted_churn_epochs_match_oracle():
    """Deterministic mini-sweep (runs everywhere; the hypothesis version
    below explores the same space adversarially)."""
    for seed in range(8):
        rng = np.random.default_rng(seed)
        rt = ElasticPhaserRuntime(int(rng.integers(2, 6)), seed=seed % 3)
        for _ in range(12):
            op = rng.integers(0, 3)
            if op == 0:
                parent = (int(rng.choice(sorted(rt.live)))
                          if rt.live and rng.integers(0, 2) else None)
                rt.request_join(parent)
            elif op == 1 and len(rt.live) > 1:
                rt.request_leave(int(rng.choice(sorted(rt.live))),
                                 fail=bool(rng.integers(0, 2)))
            else:
                rt.advance()
        rt.advance()
        rt.verify_epoch()
        for ep in rt.epochs:
            if ep.collective is not None:
                assert ep.collective.matches_oracle(), (seed, ep.index)


# ------------------------------------------------- hypothesis property
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False


if HAVE_HYP:
    @given(st.integers(2, 6), st.integers(0, 10_000),
           st.lists(st.sampled_from(["join", "leave", "step"]),
                    max_size=16))
    @settings(max_examples=40, deadline=None)
    def test_any_churn_sequence_epochs_match_oracle(n, seed, ops):
        rng = np.random.default_rng(seed)
        rt = ElasticPhaserRuntime(n, seed=seed % 5)
        for op in ops:
            if op == "join":
                rt.request_join()
            elif op == "leave" and len(rt.live) > 1:
                rt.request_leave(int(rng.choice(sorted(rt.live))))
            else:
                rt.advance()
        rt.advance()
        rt.verify_epoch()
        for ep in rt.epochs:
            if ep.collective is not None:
                assert ep.collective.matches_oracle(), ep.index
        starts = [e.phase_start for e in rt.epochs]
        assert starts == sorted(starts)


# --------------------------------------------------- schedule numerics
def test_simulate_allreduce_matches_direct_sum():
    rng = np.random.default_rng(0)
    for kind in ALLREDUCE_KINDS:
        for keys in [(0, 1, 2, 3), (1, 3, 5, 9), (0, 2, 3, 5, 7, 11),
                     (4, 7, 9)]:
            n = len(keys)
            pc = PhaserCollective(n, "data", kind=kind, keys=keys, seed=3)
            xs = [rng.normal(size=17).astype(np.float32) for _ in range(n)]
            out = pc.simulate_allreduce(xs)
            want = np.sum(np.stack(xs, 0), axis=0)
            for o in out:
                np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)


def test_collective_keys_change_schedule():
    a = PhaserCollective(4, "data", kind="phaser_scsl", seed=0)
    b = PhaserCollective(4, "data", kind="phaser_scsl", seed=0,
                         keys=(0, 1, 2, 5))
    assert a.schedule_fingerprint() != b.schedule_fingerprint()
    assert a.matches_oracle() and b.matches_oracle()


@pytest.mark.slow
def test_phaser_allreduce_matches_psum_under_churn_subprocess():
    """Grow 4 -> 6, shrink 6 -> 3: each epoch's compiled schedule computes
    the same all-reduce as XLA's psum on a real host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.runtime_elastic import ElasticPhaserRuntime

rt = ElasticPhaserRuntime(4, seed=0, kind="phaser_scsl")
rt.request_join(); rt.request_join()
rt.advance()
ep_grow = rt.epoch
for w in sorted(rt.live)[-3:]:
    rt.request_leave(w)
rt.advance()
ep_shrink = rt.epoch
assert ep_grow.n == 6 and ep_shrink.n == 3, (ep_grow.n, ep_shrink.n)
for ep in (rt.epochs[0], ep_grow, ep_shrink):
    rtN = ep.n
    pc = ep.collective
    mesh = Mesh(np.array(jax.devices()[:rtN]), ("data",))
    x = jnp.arange(rtN * 5, dtype=jnp.float32).reshape(rtN, 5) * 0.25 + 1
    f = jax.shard_map(pc.all_reduce, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
    want = jnp.broadcast_to(x.sum(0), (rtN, 5))
    assert jnp.allclose(f(x), want), ep.index
    # and the host simulation agrees with the mesh execution
    sim = pc.simulate_allreduce([np.asarray(x[i]) for i in range(rtN)])
    for i in range(rtN):
        np.testing.assert_allclose(sim[i], np.asarray(want[i]), rtol=1e-6)
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                          "PYTHONPATH": "src"},
                         cwd=__import__("os").path.dirname(
                             __import__("os").path.dirname(__file__)),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ----------------------------------------------------- serve phase gate
def test_serve_engine_phase_gated_refill():
    jax = pytest.importorskip("jax")
    import numpy as np
    from repro.models.registry import get_api, get_config
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("smollm-135m").reduced()
    api = get_api(cfg)
    params = api.init_params(jax.random.key(0))
    eng = ServeEngine(api, params, batch=2, window=32)
    reqs = [Request(rid=i, prompt=np.array([1 + i, 2, 3], np.int32),
                    max_new=3) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    assert eng.epoch == 0
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [0, 1, 2, 3]
    assert all(r.done and len(r.out) == 3 for r in reqs)
    # every admit and retire landed as an epoch at a phase boundary:
    # 4 joins + 4 leaves, batched per boundary -> at least 4 epochs
    assert eng.epoch >= 4
    kinds = [e.kind for e in eng.gate.events]
    assert kinds.count("join") == 4 and kinds.count("leave") == 4
    eng.gate.verify_epoch()
    assert eng.gate.epoch.live == ()         # drained team is empty


def test_serve_engine_one_token_requests_still_land_epochs():
    """A request that finishes during its own admission (max_new=1, so
    the prefill's token is the whole generation) joins and leaves inside
    ``_admit`` — the boundary advance must still land that churn as an
    epoch instead of leaving the gate dirty."""
    jax = pytest.importorskip("jax")
    import numpy as np
    from repro.models.registry import get_api, get_config
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("smollm-135m").reduced()
    api = get_api(cfg)
    params = api.init_params(jax.random.key(0))
    eng = ServeEngine(api, params, batch=2, window=32)
    reqs = [Request(rid=i, prompt=np.array([1 + i, 2], np.int32),
                    max_new=1) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [0, 1, 2]
    assert all(len(r.out) == 1 for r in reqs)
    assert not eng.gate.pending_churn
    assert eng.epoch >= 1
    eng.gate.verify_epoch()
    assert eng.gate.epoch.live == ()


def test_halving_doubling_accepts_non_pow2():
    """Shrink-to-3-style teams run the elimination pre-phase instead of
    being rejected (or falling back)."""
    pc = PhaserCollective(3, "data", kind="halving_doubling")
    xs = [np.full((5,), float(i + 1)) for i in range(3)]
    out = pc.simulate_allreduce(xs)
    for o in out:
        np.testing.assert_allclose(o, np.full((5,), 6.0))
    st = pc.stats()
    assert st["rounds"] == 2 + 3          # 1 core round each way + elim


def test_train_loop_resume_replays_elastic_churn(tmp_path):
    """A resumed run reconstructs the runtime by replaying the churn
    schedule up to the restored step: live set and epoch index match the
    pre-crash run instead of silently reverting to the initial team."""
    jax = pytest.importorskip("jax")
    from repro.checkpoint import CheckpointManager
    from repro.data import SyntheticLM
    from repro.models.registry import get_api, get_config
    from repro.optim import AdamW
    from repro.train.loop import TrainLoop

    cfg = get_config("smollm-135m").reduced()
    api = get_api(cfg)

    def fresh(d):
        return TrainLoop(api=api,
                         opt=AdamW(lr=1e-3, warmup=2, total_steps=8),
                         data=SyntheticLM(cfg.vocab_size, 2, 16, seed=3),
                         ckpt=CheckpointManager(str(d), async_write=False),
                         ckpt_every=4, log_every=10,
                         runtime=ElasticPhaserRuntime(3, seed=0),
                         elastic_events={1: [("join", None)],
                                         2: [("fail", None)]})

    a = fresh(tmp_path)
    a.run(4)                                  # "crash" after the ckpt @ 4
    pre_live, pre_epoch = sorted(a.runtime.live), a.runtime.epoch.index

    b = fresh(tmp_path)
    b.run(8, resume=True)
    assert sorted(b.runtime.live) == pre_live == [0, 1, 2]
    assert b.runtime.epoch.index >= pre_epoch == 2
    b.runtime.verify_epoch()


def test_controller_collective_kind_override_keeps_kind():
    from repro.runtime_elastic import ElasticController

    c = ElasticController(4, seed=0, kind="recursive_doubling")
    c.join(0)
    c.step_barrier(0)                       # epoch of 5: not a pow2 team
    assert c.epoch.kind == "recursive_doubling"   # elimination, no fallback
    # explicit overrides derive over the same live keys, any kind
    pc = c.collective("halving_doubling")
    assert pc.kind == "halving_doubling" and pc.n == 5
    pc = c.collective("phaser_scsl")
    assert pc.kind == "phaser_scsl" and pc.keys == c.epoch.live
