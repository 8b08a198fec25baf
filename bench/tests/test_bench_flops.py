"""Operations and bytes from shapes, checked against hand counts."""
import json
import os

import pytest

from bench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_smollm_parameters_and_train_flops():
    c = _config("smollm-135m")
    # 30 x (576 x (576 + 2 x 192 + 576) + 3 x 576 x 1536 + 2 x 576)
    # + 49152 x 576 (tied) + 576
    assert flops.param_count(c) == 134_515_008
    assert 6 * flops.param_count(c) == pytest.approx(0.807e9, rel=1e-3)
    # plus attention: 6 x 30 layers x 2048 positions x 576
    assert flops.train_flops_per_token(c, 2048) == \
        6 * 134_515_008 + 6 * 30 * 2048 * 576


def test_granite_parameters():
    assert flops.param_count(_config("granite-3-2b")) == 2_533_531_648


def test_bucket_combine_launch_bytes():
    # smollm-135m's eager layout: 2072 buckets of 64Ki float32
    assert flops.bucket_combine_bytes(2072, 65536) == 3 * 2072 * 65536 * 4
    assert flops.bucket_combine_bytes(2072, 65536) == pytest.approx(1.63e9,
                                                                    rel=1e-3)


def test_decode_step_least_time_is_bytes_bound():
    c = _config("granite-3-2b")
    w = flops.decode_step(c, live_slots=16, live_positions=16 * 1000)
    # weights once in bf16, then keys and values of every live position
    assert w["bytes"] == 2 * 2_533_531_648 + 2 * 40 * 512 * 16_000 * 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    lt = flops.least_time(w["flops"], w["bytes"], peaks)
    assert lt["bound"] == "bytes"
    assert lt["s"] == pytest.approx(w["bytes"] / 819e9)
