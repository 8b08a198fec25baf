"""Each fault a cell can have, planted under a run that skips only the
harness's look for a chip, makes ``correct`` come out false; the same
run unbroken comes out true. The model is cut to a CPU's size; the
limits are the cells' own."""
import json

import jax.numpy as jnp
import pytest

from bench import harness
from bench_tiny import tiny_cell


def _run(name):
    line, checks = harness.run_cell(tiny_cell(name), seed=2 ** 31 + 3,
                                    seconds=1.0, traced=False, t_start=0.0,
                                    require_tpu=False)
    return json.loads(line)


def _state_unchanged(self, grads, state, params, **_):
    return params, state, {"lr": jnp.zeros(()), "grad_norm": jnp.zeros(())}


def _half_batch(orig):
    def loss_fn(self, params, batch, **kw):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return orig(self, params, half, **kw)
    return loss_fn


def test_sound_train_run_is_correct():
    assert _run("smollm-135m.train")["correct"]


def test_train_step_that_leaves_the_state_unchanged(monkeypatch):
    from repro.optim import AdamW
    monkeypatch.setattr(AdamW, "update", _state_unchanged)
    res = _run("smollm-135m.train")
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_train_step_on_half_the_batch(monkeypatch):
    from repro.models.registry import ModelAPI
    monkeypatch.setattr(ModelAPI, "loss_fn", _half_batch(ModelAPI.loss_fn))
    assert not _run("smollm-135m.train")["correct"]


def test_sound_serve_run_is_correct():
    assert _run("granite-3-2b.serve")["correct"]


def test_served_token_altered_where_it_is_produced(monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine.step

    def step(self):
        n = orig(self)
        for r in self.slot_req:
            if r is not None and len(r.out) == 3:
                r.out[-1] = (r.out[-1] + 1) % self.cfg.vocab_size
        return n

    monkeypatch.setattr(ServeEngine, "step", step)
    res = _run("granite-3-2b.serve")
    assert not res["correct"]
