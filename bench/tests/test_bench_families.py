"""Model families are found by the configuration's ``family`` key (a new
one by adding its file alone, a missing one refused by name), and the
dense family reads exactly what the harness read before it was split
out: the same seeded weights bit for bit, the same reference logits and
training readings, the same counts. The recorded values were taken
from the harness as it stood before the split; ``python
bench/tests/test_bench_families.py`` prints them again.

Float readings are held to float32 round-off (relative 1e-6): any
change of an equation moves them by far more, while XLA's CPU code may
round differently on another host's instruction set."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, model_ref
from bench_tiny import ROOT, tiny_cell

CELLS = {"smollm-135m": "smollm-135m.train",
         "granite-3-2b": "granite-3-2b.serve"}
SEEDS = (5, 2 ** 32 + 7)
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup": 1, "total_steps": 10000,
       "decay_min_rank": 2}


def _full(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for k, v in model_ref.flat_leaves(tree).items():
        a = np.asarray(v)
        h.update(k.encode())
        h.update(a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)
                 .tobytes())
    return h.hexdigest()


def _batches(vocab, n=3, rows=2, seq=32):
    rng = np.random.default_rng(17)
    out = []
    for _ in range(n):
        t = rng.integers(0, vocab, (rows, seq + 1)).astype(np.int32)
        out.append({"tokens": t[:, :-1], "targets": t[:, 1:]})
    return out


def readings(name):
    """What the test compares, for configuration ``name`` at the tiny
    cut the other benchmark tests use."""
    cfg = tiny_cell(CELLS[name]).config
    out = {"weights": [_digest(model_ref.make_weights(cfg, s,
                                                      jnp.bfloat16))
                       for s in SEEDS]}
    p32 = model_ref.make_weights(cfg, SEEDS[1], jnp.float32)
    toks = np.random.default_rng(3).integers(0, cfg["vocab_size"], (1, 24))
    for quant in (None, "fp8"):
        lg = np.asarray(model_ref._seq_logits(
            p32, jnp.asarray(toks, jnp.int32), model_ref._cfg_items(cfg),
            quant), np.float64)
        out[f"logits_{quant}"] = [float(lg.sum()), float((lg * lg).sum()),
                                  float(lg[5, 7]), float(lg[-1, -1])]
    r = model_ref.train_readings(
        cfg, OPT, model_ref.make_weights(cfg, SEEDS[0], jnp.bfloat16),
        _batches(cfg["vocab_size"]), block_rows=1)
    out["train"] = {"loss": r["loss"], "grad": r["grad"],
                    "change": r["change"]}
    counts = {}
    for tag, c in (("tiny", cfg), ("full", _full(name))):
        counts[tag] = [flops.param_count(c), flops.matmul_params(c),
                       flops.train_flops_per_token(c, 2048),
                       flops.decode_step(c, 16, 16 * 1000)]
    out["counts"] = counts
    return out


# taken from the harness before the families were split out
RECORDED = {'granite-3-2b': {'counts': {'full': [2533531648,
                                      2533365760,
                                      16207822848.0,
                                      {'bytes': 6377783296.0,
                                       'flops': 86310584320.0}],
                             'tiny': [90432,
                                      90112,
                                      2115456.0,
                                      {'bytes': 4276864.0,
                                       'flops': 11075584.0}]},
                  'logits_None': [-16.159278796840226,
                                  169.85479390146583,
                                  -0.2199488878250122,
                                  0.15789420902729034],
                  'logits_fp8': [-18.361351427833142,
                                 170.79098331638875,
                                 -0.22840836644172668,
                                 0.13487480580806732],
                  'train': {'change': {'blocks/attn/wk': 0.1281336396932602,
                                       'blocks/attn/wo': 0.18119552731513977,
                                       'blocks/attn/wq': 0.17827355861663818,
                                       'blocks/attn/wv': 0.1274619847536087,
                                       'blocks/ln1': 0.0,
                                       'blocks/ln2': 0.0,
                                       'blocks/mlp/down': 0.2530960440635681,
                                       'blocks/mlp/gate': 0.2547343373298645,
                                       'blocks/mlp/up': 0.25520002841949463,
                                       'embed': 0.25722452998161316,
                                       'final_norm': 0.0},
                            'grad': {'blocks/attn/wk': 0.07766955345869064,
                                     'blocks/attn/wo': 0.11933527141809464,
                                     'blocks/attn/wq': 0.07605303078889847,
                                     'blocks/attn/wv': 0.11412231624126434,
                                     'blocks/ln1': 0.02113315649330616,
                                     'blocks/ln2': 0.014348906464874744,
                                     'blocks/mlp/down': 0.09901747107505798,
                                     'blocks/mlp/gate': 0.07134978473186493,
                                     'blocks/mlp/up': 0.07449907064437866,
                                     'embed': 0.9694218635559082,
                                     'final_norm': 0.006475877482444048},
                            'loss': [5.56373405456543,
                                     5.551016807556152,
                                     5.597080230712891]},
                  'weights': ['afa1b6bc8ce922237cc1bf15bbbe778fdfcc72d83743fd8ca9b35f8aee1c27df',
                              '25bd6f6478793c04dcfe59edd78878d850bc618f2995b881d25735dc5779fbdf']},
 'smollm-135m': {'counts': {'full': [134515008,
                                     134479872,
                                     1019426688.0,
                                     {'bytes': 637670016.0,
                                      'flops': 5409275904.0}],
                            'tiny': [90432,
                                     90112,
                                     2115456.0,
                                     {'bytes': 4276864.0,
                                      'flops': 11075584.0}]},
                 'logits_None': [-15.416433176353166,
                                 169.9998222383342,
                                 -0.22250650823116302,
                                 0.15452232956886292],
                 'logits_fp8': [-18.478992214848404,
                                170.3349997238824,
                                -0.23021046817302704,
                                0.15115278959274292],
                 'train': {'change': {'blocks/attn/wk': 0.1277841180562973,
                                      'blocks/attn/wo': 0.18105627596378326,
                                      'blocks/attn/wq': 0.17841491103172302,
                                      'blocks/attn/wv': 0.12757065892219543,
                                      'blocks/ln1': 0.0,
                                      'blocks/ln2': 0.0,
                                      'blocks/mlp/down': 0.2529338002204895,
                                      'blocks/mlp/gate': 0.2547625005245209,
                                      'blocks/mlp/up': 0.25494495034217834,
                                      'embed': 0.2575712203979492,
                                      'final_norm': 0.0},
                           'grad': {'blocks/attn/wk': 0.07689102739095688,
                                    'blocks/attn/wo': 0.12056173384189606,
                                    'blocks/attn/wq': 0.07552289962768555,
                                    'blocks/attn/wv': 0.11481375247240067,
                                    'blocks/ln1': 0.021024905145168304,
                                    'blocks/ln2': 0.014548927545547485,
                                    'blocks/mlp/down': 0.10012651979923248,
                                    'blocks/mlp/gate': 0.07218290120363235,
                                    'blocks/mlp/up': 0.07549232989549637,
                                    'embed': 0.969038724899292,
                                    'final_norm': 0.006504931021481752},
                           'loss': [5.5638861656188965,
                                    5.551204681396484,
                                    5.597271919250488]},
                 'weights': ['afa1b6bc8ce922237cc1bf15bbbe778fdfcc72d83743fd8ca9b35f8aee1c27df',
                             '25bd6f6478793c04dcfe59edd78878d850bc618f2995b881d25735dc5779fbdf']}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_dense_family_reads_as_before_the_split(name):
    got, want = readings(name), RECORDED[name]
    assert got["weights"] == want["weights"]
    assert got["counts"] == want["counts"]
    for q in ("logits_None", "logits_fp8"):
        assert got[q] == pytest.approx(want[q], rel=1e-6)
    for k in ("loss", "grad", "change"):
        assert got["train"][k] == pytest.approx(want["train"][k], rel=1e-6)


STUB = '''"""A model of one square map between a tied embedding and head."""
import jax
import jax.numpy as jnp

from bench import model_ref


def _n(c):
    return c["vocab_size"] * c["hidden_size"] + c["hidden_size"] ** 2


def param_shapes(c):
    return {"embed": (c["vocab_size"], c["hidden_size"]),
            "w": (c["hidden_size"], c["hidden_size"])}


def init_leaf(key, path, shape, dtype):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def logits(c, params, tokens, quant=None):
    ein = model_ref.ein_for(quant)
    emb = params["embed"].astype(jnp.float32)
    h = ein("bsd,de->bse", emb[tokens], params["w"].astype(jnp.float32))
    return ein("bsd,vd->bsv", h, emb)


def program_kwargs(c):
    return {"family": "stub"}


param_count = matmul_params = _n


def train_flops_per_token(c, seq):
    return 6.0 * _n(c)


def decode_step(c, slots, positions, weight_bytes=2, kv_bytes=2):
    return {"flops": 2.0 * _n(c) * slots, "bytes": _n(c) * weight_bytes}
'''

PROBE = '''
import json
import numpy as np
import jax.numpy as jnp
from bench import flops, model_ref
cfg = {"name": "stub-1", "family": "stub", "vocab_size": 32,
       "hidden_size": 8}
w = model_ref.make_weights(cfg, 2 ** 33 + 1, jnp.float32)
lg = model_ref.logits(cfg, w, jnp.zeros((1, 4), jnp.int32))
t = np.arange(10, dtype=np.int32).reshape(2, 5) % 32
r = model_ref.train_readings(cfg, OPT, w, [{"tokens": t, "targets": t}],
                             block_rows=1)
print(json.dumps({"leaves": sorted(model_ref.flat_leaves(w)),
                  "logits": list(lg.shape), "loss": r["loss"],
                  "params": flops.param_count(cfg),
                  "decode": flops.decode_step(cfg, 2, 10)}))
'''


def _tree(d):
    return sorted(os.path.relpath(os.path.join(r, f), d)
                  for r, _, fs in os.walk(d) for f in fs
                  if "__pycache__" not in r)


def test_a_new_family_is_found_by_its_file_alone(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree(str(bench))
    (bench / "families" / "stub.py").write_text(STUB)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c",
                        f"OPT = {OPT!r}\n" + PROBE], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert got["leaves"] == ["embed", "w"]
    assert got["logits"] == [1, 4, 32]
    assert got["params"] == 32 * 8 + 8 * 8
    assert got["decode"] == {"flops": 2.0 * 320 * 2, "bytes": 2.0 * 320}
    assert np.isfinite(got["loss"]).all()
    assert _tree(str(bench)) == sorted(before + ["families/stub.py"])


@pytest.mark.parametrize("family,names", [(None, "tiny.json"),
                                          ("nosuch", "nosuch.py")])
def test_a_config_without_its_family_module_is_refused(tmp_path, family,
                                                       names):
    cfg = {"name": "tiny", "vocab_size": 32, "hidden_size": 8}
    if family:
        cfg["family"] = family
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    b = {"configs": [{"name": "tiny", "file": "tiny.json"}],
         "workloads": [{"name": "tiny.train", "config": "tiny",
                        "traffic": "train_2k", "chips": 1}],
         "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(harness.BenchError, match=names):
        harness.find_cell(str(tmp_path / "BENCHMARK.json"), "tiny.train")
    with pytest.raises(harness.BenchError,
                       match=names if family else "states no"):
        flops.param_count(cfg)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    print(json.dumps({n: readings(n) for n in sorted(CELLS)}, indent=1))
