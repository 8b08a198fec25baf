"""Collective execution engine (DESIGN.md §4).

Four layers of evidence:

1. schedule properties — every kind derives a valid ``Schedule`` for
   EVERY team size 2..12 (the elimination derivations cover non-powers
   of two) and its host simulation equals the direct sum;
2. a hypothesis property sweep over (n, kind, keys, values) — skipped
   where the dev-only dependency is missing;
3. bucket layout round-trips the grad pytree exactly, with the alive
   flag riding the buffer — including the reverse-topological order and
   per-bucket readiness groups of the overlap pipeline (DESIGN.md §5);
4. program cache: LRU recency on hits and eviction, overlap config in
   the key, and the epoch-boundary swap ordering (the next epoch's
   program is compiled inside the boundary, never mid-phase);
5. numeric (subprocess, 8 host devices): the bucketed shard_map
   executor with the fused Pallas combine equals ``xla_psum`` for every
   kind at pow2 AND non-pow2 team sizes; the compiled gradient-sync
   program produces the same updated params as the psum program; and
   the pipelined (overlapped) program is BITWISE equal to the eager one
   across grow 4->6 / shrink 6->3 elastic epochs.
"""
import math
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.collective_exec import ProgramCache, make_layout
from repro.core.collective import (ALLREDUCE_KINDS, PhaserCollective,
                                   recursive_doubling_schedule)
from repro.runtime_elastic import ElasticPhaserRuntime


# ----------------------------- non-pow2 schedules (deterministic sweep)
def test_all_kinds_all_team_sizes_simulate_equals_sum():
    rng = np.random.default_rng(0)
    for n in range(2, 13):
        keys = tuple(sorted(rng.choice(200, size=n,
                                       replace=False).tolist()))
        for kind in ALLREDUCE_KINDS:
            pc = PhaserCollective(n, "data", kind=kind, keys=keys,
                                  seed=n % 4)
            sched = pc.unified_schedule()
            if sched is not None:
                sched.check()
            xs = [rng.normal(size=23).astype(np.float32)
                  for _ in range(n)]
            out = pc.simulate_allreduce(xs)
            want = np.sum(np.stack(xs), axis=0)
            for i, o in enumerate(out):
                np.testing.assert_allclose(
                    o, want, rtol=1e-5, atol=1e-5,
                    err_msg=f"{kind} n={n} rank={i}")


def test_recursive_doubling_non_pow2_uses_elimination_rounds():
    s = recursive_doubling_schedule(6)
    s.check()
    # fold extras (add), 2 XOR rounds over the 4-core, hydrate (copy)
    assert s.depth == 4
    assert s.ops[0] == "add" and s.ops[-1] == "copy"
    assert recursive_doubling_schedule(8).ops == ("add",) * 3


def test_elastic_epochs_keep_preferred_kind_non_pow2():
    for kind in ("recursive_doubling", "halving_doubling"):
        rt = ElasticPhaserRuntime(4, seed=0, kind=kind)
        rt.request_join()
        rt.advance()
        assert rt.epoch.n == 5 and rt.epoch.kind == kind
        rt.verify_epoch()


# --------------------------------------------- hypothesis property
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False


if HAVE_HYP:
    @given(st.integers(2, 12), st.sampled_from(ALLREDUCE_KINDS),
           st.integers(0, 10_000))
    @settings(max_examples=60, deadline=None)
    def test_any_team_size_any_kind_schedule_is_sound(n, kind, seed):
        rng = np.random.default_rng(seed)
        keys = tuple(sorted(rng.choice(500, size=n,
                                       replace=False).tolist()))
        pc = PhaserCollective(n, "data", kind=kind, keys=keys,
                              seed=seed % 7)
        sched = pc.unified_schedule()
        if sched is not None:
            sched.check()
        xs = [rng.normal(size=int(rng.integers(1, 40)))
              .astype(np.float32) for _ in range(n)]
        xs = [np.resize(x, xs[0].shape) for x in xs]   # equal shapes
        out = pc.simulate_allreduce(xs)
        want = np.sum(np.stack(xs), axis=0)
        for o in out:
            np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- bucket layout
def test_bucket_layout_roundtrip():
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.full((5,), 2.0, jnp.float32)}}
    lay = make_layout(tree)
    buf = lay.flatten(tree, 1.0)
    assert buf.shape == (lay.n_buckets, lay.bucket_elems)
    assert lay.bucket_elems % 128 == 0
    out, count = lay.unflatten(buf)
    assert float(count) == 1.0
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))
    # padding is zeros: total mass is payload + flag
    assert np.isclose(float(buf.sum()),
                      float(tree["a"].sum() + tree["b"]["c"].sum() + 1.0))


def test_bucket_layout_multi_bucket_sizing():
    spec = {"x": jax.ShapeDtypeStruct((1000,), jnp.float32)}
    lay = make_layout(spec, bucket_elems=256)
    # ceil(1001 / 256) = 4 buckets hold the payload and the flag; the
    # count rounds up to whole 8-row blocks of the combine kernel
    assert lay.n_buckets == 8 * math.ceil(math.ceil(1001 / 256) / 8)
    assert lay.flag_index == 1000
    buf = lay.flatten({"x": jnp.ones((1000,), jnp.float32)}, 0.0)
    out, count = lay.unflatten(buf)
    assert float(count) == 0.0
    assert out["x"].shape == (1000,)


def test_bucket_layout_reverse_topo_readiness_groups():
    """Output-side leaves come first (their grads finalize first under
    backprop), embeddings last; contiguous readiness classes become
    bucket groups and the group views round-trip exactly."""
    from repro.models.registry import get_api, get_config
    api = get_api(get_config("smollm-135m").reduced())
    lay = make_layout(api.param_spec(), bucket_elems=1024)
    paths = ["/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(
                 api.param_spec())[0]]
    order = [paths[i] for i in lay.perm]
    assert "final_norm" in order[0], order[0]          # loss side first
    assert "embed" in order[-1], order[-1]             # input side last
    assert lay.n_groups >= 3
    assert sum(lay.group_buckets) == lay.n_buckets
    assert lay.groups[0][0] == 0 and lay.groups[-1][1] == lay.n_buckets
    # per-group buffers == contiguous slices of the flat buffer, and
    # the round-trip (incl. contributor flag) is exact
    params = api.init_params(jax.random.key(0))
    bufs = lay.flatten_groups(params, 1.0)
    assert [b.shape[0] for b in bufs] == list(lay.group_buckets)
    flat = lay.flatten(params, 1.0)
    np.testing.assert_array_equal(
        np.asarray(flat), np.asarray(jnp.concatenate(bufs, 0)))
    tree, count = lay.unflatten_groups(bufs)
    assert float(count) == 1.0
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucket_layout_block_groups_scan_slice_subgroups():
    """``block_groups=K`` splits the monolithic blocks group into K
    scan-row sub-groups, LAST rows first (the order the backward scan
    emits stacked gradients), deepening the overlap past 3 groups; the
    group views and the full buffer still round-trip exactly."""
    from repro.models.registry import get_api, get_config
    api = get_api(get_config("smollm-135m").reduced(n_layers=4))
    base = make_layout(api.param_spec(), bucket_elems=1024)
    lay = make_layout(api.param_spec(), bucket_elems=1024,
                      block_groups=4)
    assert base.n_groups == 3
    assert lay.n_groups == base.n_groups + 3      # blocks: 1 -> 4 groups
    # the block sub-groups cover descending row ranges of the scan axis
    rows = [r for r in lay.group_rows if r is not None]
    assert rows == [(3, 4), (2, 3), (1, 2), (0, 1)], rows
    # row-split groups repeat the same stacked-leaf range
    blk_groups = [lay.group_leaves[g] for g in range(lay.n_groups)
                  if lay.group_rows[g] is not None]
    assert len(set(blk_groups)) == 1
    params = api.init_params(jax.random.key(0))
    bufs = lay.flatten_groups(params, 1.0)
    assert [b.shape[0] for b in bufs] == list(lay.group_buckets)
    flat = lay.flatten(params, 1.0)
    np.testing.assert_array_equal(
        np.asarray(flat), np.asarray(jnp.concatenate(bufs, 0)))
    for tree, count in (lay.unflatten(flat),
                        lay.unflatten_groups(bufs)):
        assert float(count) == 1.0
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # K > scan length clamps; K=1 is byte-identical to the base layout
    assert make_layout(api.param_spec(), bucket_elems=1024,
                       block_groups=64).n_groups == 3 + 3
    assert make_layout(api.param_spec(), bucket_elems=1024,
                       block_groups=1) == base


def test_bucket_layout_block_groups_hybrid_shared_leaves_unsplit():
    """Hybrid families carry loose class-1 leaves (shared attention)
    whose grads accumulate across the whole backward: they keep an
    UNSPLIT group after the scan-row sub-groups."""
    from repro.models.registry import get_api, get_config
    api = get_api(get_config("zamba2-7b").reduced())
    lay = make_layout(api.param_spec(), block_groups=2)
    rows = [r for r in lay.group_rows]
    assert (None, (1, 2), (0, 1)) == tuple(rows[:3]), rows
    params = api.init_params(jax.random.key(1))
    tree, count = lay.unflatten(lay.flatten(params, 1.0))
    assert float(count) == 1.0
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucket_layout_tree_order_single_group():
    """order="tree" preserves the pre-overlap layout: identity perm,
    one readiness group spanning every bucket."""
    from repro.models.registry import get_api, get_config
    api = get_api(get_config("smollm-135m").reduced())
    lay = make_layout(api.param_spec(), bucket_elems=1024, order="tree")
    assert lay.perm == tuple(range(len(lay.sizes)))
    assert lay.n_groups == 1
    assert lay.flag_index == lay.payload       # flag right after leaves


# ------------------------------------------------------- program cache
def test_program_cache_hits_on_revisited_member_set():
    built = []

    def builder(pc):
        built.append((pc.keys, pc.kind))
        return ("program", pc.keys, pc.kind)

    cache = ProgramCache(builder)
    rt = ElasticPhaserRuntime(3, seed=0)
    rt.bind_program_cache(cache)            # epoch 0 compiles eagerly
    assert cache.stats() == {"entries": 1, "hits": 0, "misses": 1}
    w = rt.request_join()
    rt.advance()                            # (0,1,2,3): new program
    rt.request_leave(w)
    rt.advance()                            # back to (0,1,2): cache HIT
    assert cache.stats() == {"entries": 2, "hits": 1, "misses": 2}
    assert built == [((0, 1, 2), "phaser_scsl"),
                     ((0, 1, 2, 3), "phaser_scsl")]
    # the cached program is the current epoch's
    assert cache.get(rt.collective()) == ("program", (0, 1, 2),
                                          "phaser_scsl")


def test_program_cache_lru_eviction():
    cache = ProgramCache(lambda pc: object(), capacity=2)
    pcs = [PhaserCollective(2, "data", keys=(i, i + 1), kind="xla_psum")
           for i in range(3)]
    for pc in pcs:
        cache.get(pc)
    assert len(cache) == 2
    assert pcs[0] not in cache and pcs[2] in cache


def test_program_cache_lru_hit_refreshes_recency():
    """A cache HIT must move the entry to most-recently-used: after
    touching pc0 again, inserting a third entry evicts pc1, not pc0."""
    cache = ProgramCache(lambda pc: object(), capacity=2)
    pcs = [PhaserCollective(2, "data", keys=(i, i + 1), kind="xla_psum")
           for i in range(3)]
    cache.get(pcs[0])
    cache.get(pcs[1])
    cache.get(pcs[0])                      # HIT: pc0 becomes MRU
    cache.get(pcs[2])                      # evicts the LRU = pc1
    assert pcs[0] in cache and pcs[2] in cache
    assert pcs[1] not in cache
    assert cache.stats() == {"entries": 2, "hits": 1, "misses": 3}


def test_program_cache_extra_key_separates_overlap_configs():
    """An eager and a pipelined cache over the same member set hold
    DISTINCT entries: the overlap/microbatch config rides the key."""
    built = []
    pc = PhaserCollective(3, "data", keys=(0, 1, 2), kind="xla_psum")
    eager = ProgramCache(lambda c: built.append("eager") or "E",
                         extra_key=("eager", 1))
    pipe = ProgramCache(lambda c: built.append("pipelined") or "P",
                        extra_key=("pipelined", 2))
    assert eager.get(pc) == "E" and pipe.get(pc) == "P"
    assert built == ["eager", "pipelined"]
    assert eager.full_key(pc) != pipe.full_key(pc)
    assert eager.full_key(pc)[:4] == pipe.full_key(pc)[:4]
    # one shared cache would also keep them apart if keyed fully
    assert eager.get(pc) == "E"            # hit, not rebuilt
    assert built == ["eager", "pipelined"]


def test_epoch_boundary_swap_ordering():
    """The boundary's program swap is ordered: the next epoch's program
    is compiled inside ``advance()`` (via the bound cache's on_epoch
    hook) BEFORE the boundary returns, and hooks observe (old, new) in
    order — a consumer never runs a phase against a missing program."""
    events = []

    def builder(pc):
        events.append(("compile", pc.keys))
        return ("program", pc.keys)

    cache = ProgramCache(builder)
    rt = ElasticPhaserRuntime(3, seed=0)
    rt.bind_program_cache(cache)           # epoch 0 compiles eagerly
    rt.on_epoch(lambda old, new: events.append(
        ("boundary", old.live, new.live)))
    assert events == [("compile", (0, 1, 2))]
    w = rt.request_join()
    # churn is pending but the swap must NOT happen mid-phase
    assert rt.pending_churn and len(events) == 1
    rt.advance()
    # compile lands inside the boundary, before the follow-up hooks
    assert events[1] == ("compile", (0, 1, 2, w))
    assert events[2] == ("boundary", (0, 1, 2), (0, 1, 2, w))
    assert rt.collective() in cache        # ready before the next phase


# --------------------------- device numerics (subprocess: 8-dev mesh)
@pytest.mark.slow
def test_engine_matches_psum_on_mesh_all_kinds_non_pow2():
    """The bucketed shard_map executor (fused Pallas combine) equals
    xla_psum for every kind at n in {3, 5, 6, 8}, and the compiled
    gradient-sync program computes the same masked step as the psum
    program."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.collective_exec import build_allreduce_program, build_gradsync_program
from repro.core.collective import ALLREDUCE_KINDS, PhaserCollective

rng = np.random.default_rng(0)
for n in (3, 5, 6, 8):
    x = jnp.asarray(rng.normal(size=(n, 4, 33)).astype(np.float32))
    want = np.asarray(x).sum(0)
    for kind in ALLREDUCE_KINDS:
        pc = PhaserCollective(n, "data", kind=kind, seed=1)
        f = build_allreduce_program(pc, jax.ShapeDtypeStruct((4, 33), jnp.float32))
        got = np.asarray(f(x))
        for i in range(n):
            np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-4,
                                       err_msg=f"{kind} n={n} rank {i}")

from repro.models.registry import get_api, get_config
from repro.optim import AdamW
from repro.data.synthetic import make_batch
cfg = get_config("smollm-135m").reduced()
api = get_api(cfg)
opt = AdamW(lr=1e-3, warmup=2, total_steps=10)
params = api.init_params(jax.random.key(0))
opt_state = opt.init(params)
n = 6
bs = [make_batch(cfg.vocab_size, 2, 16, seed=100 + w, step=0) for w in range(n)]
batch = {k: jnp.asarray(np.stack([b[k] for b in bs])) for k in bs[0]}
alive = jnp.asarray([1, 1, 1, 1, 1, 0], jnp.float32)
prog = build_gradsync_program(
    api, opt, PhaserCollective(n, "data", kind="recursive_doubling"),
    stacked=True)
ref = build_gradsync_program(
    api, opt, PhaserCollective(n, "data", kind="xla_psum"), stacked=True)
p1, o1, m1 = prog.step(params, opt_state, batch, alive)
p2, o2, m2 = ref.step(params, opt_state, batch, alive)
r1, r2 = prog.reduce_metrics(m1), ref.reduce_metrics(m2)
np.testing.assert_allclose(float(r1["loss"]), float(r2["loss"]), rtol=1e-5)
assert float(r1["alive"]) == 5.0
for a, b in zip(jax.tree_util.tree_leaves(p1),
                jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-5)
print("OK")
"""
    import os
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": "src"},
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_overlapped_program_bitwise_equals_eager_across_elastic_epochs():
    """The overlap acceptance gate (DESIGN.md §5): the pipelined
    program (reverse-topo bucket groups, double-buffered rounds,
    microbatch streams) produces BITWISE-equal loss+params vs the eager
    program at every step across grow 4->6 / shrink 6->3 elastic
    epochs, and both match the xla_psum baseline within f32 tolerance."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.collective_exec import ProgramCache, build_gradsync_program
from repro.core.collective import PhaserCollective
from repro.data.synthetic import make_batch
from repro.models.registry import get_api, get_config
from repro.optim import AdamW
from repro.runtime_elastic import ElasticPhaserRuntime

cfg = get_config("smollm-135m").reduced()
api = get_api(cfg)
opt = AdamW(lr=3e-3, warmup=2, total_steps=12)
M = 2                                     # microbatches per worker
mk = lambda overlap, kind: ProgramCache(
    lambda pc: build_gradsync_program(
        api, opt, PhaserCollective(pc.n, pc.axis_name, kind=kind,
                                   keys=pc.keys, seed=pc.seed),
        stacked=True, overlap=overlap, microbatches=M,
        bucket_elems=1024, block_groups=2),
    extra_key=(overlap, M, 2))
pipe = mk("pipelined", "recursive_doubling")
eager = mk("eager", "recursive_doubling")
psum = mk("eager", "xla_psum")

rt = ElasticPhaserRuntime(4, seed=0, kind="recursive_doubling")
rt.bind_program_cache(pipe)
p0 = api.init_params(jax.random.key(0))
state = {n: (p0, opt.init(p0)) for n in ("pipe", "eager", "psum")}

for step in range(12):
    if step == 4:
        rt.request_join(); rt.request_join()          # grow 4 -> 6
    if step == 8:
        for w in sorted(rt.live)[-3:]:
            rt.request_leave(w)                       # shrink 6 -> 3
    team = list(rt.epoch.live)
    alive = jnp.asarray([1.0 if w in rt.live else 0.0 for w in team],
                        jnp.float32)
    bs = [make_batch(cfg.vocab_size, 4, 16, seed=50 + w, step=step)
          for w in team]
    batch = {k: jnp.asarray(np.stack([b[k] for b in bs]))
             for k in bs[0]}
    pc = rt.collective()
    losses = {}
    for name, cache in (("pipe", pipe), ("eager", eager),
                        ("psum", psum)):
        prog = cache.get(pc)
        p, o = state[name]
        p, o, m = prog.step(p, o, batch, alive)
        state[name] = (p, o)
        losses[name] = float(prog.reduce_metrics(m)["loss"])
    # pipelined vs eager: bitwise (atol=0)
    assert losses["pipe"] == losses["eager"], (step, losses)
    for a, b in zip(jax.tree_util.tree_leaves(state["pipe"][0]),
                    jax.tree_util.tree_leaves(state["eager"][0])):
        assert (np.asarray(a) == np.asarray(b)).all(), step
    # both vs psum: f32 tolerance
    np.testing.assert_allclose(losses["pipe"], losses["psum"],
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(state["pipe"][0]),
                    jax.tree_util.tree_leaves(state["psum"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    rt.advance(step=step)
assert len(rt.epochs) == 3, len(rt.epochs)
for cache in (pipe, eager, psum):
    assert cache.stats()["misses"] == 3    # one program per member set
g = pipe.get(rt.collective())
# block_groups=2 splits the stacked-blocks group into 2 scan-row
# sub-groups: the pipelined overlap runs deeper than the 3 classes
assert g.meta["overlap"] == 1 and g.meta["bucket_groups"] >= 4
print("OK")
"""
    import os
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": "src"},
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
