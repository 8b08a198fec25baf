"""Pallas kernel validation: interpret=True execution vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (flash_attention_op, flash_decode_op,
                               mamba2_scan_op, mlstm_op)

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Kh,S,hd,win", [
    (2, 4, 4, 256, 64, None),          # MHA causal
    (1, 8, 2, 256, 64, None),          # GQA 4:1
    (2, 4, 2, 512, 32, 128),           # GQA + sliding window
    (1, 2, 1, 128, 128, None),         # MXU-aligned head_dim
])
def test_flash_attention_vs_ref(B, H, Kh, S, hd, win, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (B, H, S, hd), dtype)
    k = rand(ks[1], (B, Kh, S, hd), dtype)
    v = rand(ks[2], (B, Kh, S, hd), dtype)
    out = flash_attention_op(q, k, v, causal=True, sliding_window=win,
                             block_q=128, block_k=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, sliding_window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[dtype])


def test_flash_attention_block_shape_sweep():
    B, H, S, hd = 1, 2, 512, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = rand(ks[0], (B, H, S, hd), jnp.float32)
    k = rand(ks[1], (B, H, S, hd), jnp.float32)
    v = rand(ks[2], (B, H, S, hd), jnp.float32)
    want = ref.attention_ref(q, k, v)
    for bq, bk in [(64, 64), (128, 256), (256, 128), (512, 512)]:
        out = flash_attention_op(q, k, v, block_q=bq, block_k=bk,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- decode
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Kh,W,hd", [
    (2, 4, 4, 512, 64), (2, 8, 2, 1024, 64), (1, 4, 1, 256, 128)])
def test_flash_decode_vs_ref(B, H, Kh, W, hd, dtype):
    ks = jax.random.split(jax.random.key(2), 4)
    q = rand(ks[0], (B, H, hd), dtype)
    k = rand(ks[1], (B, Kh, W, hd), dtype)
    v = rand(ks[2], (B, Kh, W, hd), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, W)
    valid = (jnp.arange(W)[None, :] < lengths[:, None]).astype(jnp.int32)
    out = flash_decode_op(q, k, v, valid, block_k=256, interpret=True)
    want = ref.decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[dtype])


# ---------------------------------------------------------------- mamba2
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,NH,S,P,N,chunk", [
    (2, 2, 256, 64, 16, 64), (1, 4, 512, 32, 64, 128),
    (2, 1, 128, 64, 64, 128)])
def test_mamba2_scan_vs_ref(B, NH, S, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(3), 5)
    x = rand(ks[0], (B, NH, S, P), dtype)
    Bm = rand(ks[1], (B, S, N), dtype) * 0.5
    Cm = rand(ks[2], (B, S, N), dtype) * 0.5
    dt = jax.nn.softplus(rand(ks[3], (B, NH, S), jnp.float32))
    a = jnp.exp(-jax.nn.softplus(rand(ks[4], (B, NH, S), jnp.float32)))
    out = mamba2_scan_op(x, Bm, Cm, a, dt, chunk=chunk, interpret=True)
    want = ref.mamba2_ref(x, Bm, Cm, a, dt)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-3,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-3)


# ---------------------------------------------------------------- mlstm
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,NH,S,hd,chunk", [
    (2, 2, 256, 64, 64), (1, 4, 512, 32, 128)])
def test_mlstm_vs_ref(B, NH, S, hd, chunk, dtype):
    ks = jax.random.split(jax.random.key(4), 5)
    q = rand(ks[0], (B, NH, S, hd), dtype)
    k = rand(ks[1], (B, NH, S, hd), dtype) / np.sqrt(hd)
    v = rand(ks[2], (B, NH, S, hd), dtype)
    logi = rand(ks[3], (B, NH, S), jnp.float32) * 0.5
    logf = jax.nn.log_sigmoid(rand(ks[4], (B, NH, S), jnp.float32) + 2.0)
    out = mlstm_op(q, k, v, logi, logf, chunk=chunk, interpret=True)
    want = ref.mlstm_ref(q, k, v, logi, logf)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- bucket combine
@pytest.mark.parametrize("op", ["add", "copy"])
@pytest.mark.parametrize("gate", [0, 1])
def test_bucket_combine_vs_ref(op, gate):
    from repro.kernels.ops import bucket_combine_op

    rng = np.random.default_rng(7)
    acc = jnp.asarray(rng.normal(size=(3, 256)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(3, 256)).astype(np.float32))
    out = bucket_combine_op(acc, y, jnp.asarray(bool(gate)), op=op,
                            interpret=True)
    if op == "add":
        want = np.asarray(acc) + gate * np.asarray(y)
    else:
        want = np.asarray(y) if gate else np.asarray(acc)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


@pytest.mark.parametrize("op", ["add", "copy"])
@pytest.mark.parametrize("rows", [8, 24])
def test_bucket_combine_row_blocks_bitwise(rows, op):
    """Whole 8-row blocks (one grid step or several) combine exactly as
    the elementwise reference, bit for bit."""
    from repro.kernels.ops import bucket_combine_op

    rng = np.random.default_rng(rows)
    acc = rng.normal(size=(rows, 384)).astype(np.float32)
    y = rng.normal(size=(rows, 384)).astype(np.float32)
    for gate in (False, True):
        out = bucket_combine_op(jnp.asarray(acc), jnp.asarray(y),
                                jnp.asarray(gate), op=op, interpret=True)
        if op == "add":
            want = acc + y if gate else acc
        else:
            want = y if gate else acc
        np.testing.assert_array_equal(np.asarray(out), want)


def test_bucket_combine_executes_schedule_like_simulate():
    """Chained combines reproduce the host simulate_schedule semantics
    on a 3-rank elimination schedule (kernel as the round primitive)."""
    from repro.core.collective import recursive_doubling_schedule, simulate_schedule
    from repro.kernels.ops import bucket_combine_op

    sched = recursive_doubling_schedule(3)
    rng = np.random.default_rng(1)
    vals = [rng.normal(size=(2, 128)).astype(np.float32) for _ in range(3)]
    accs = [jnp.asarray(v) for v in vals]
    for r, pairs in enumerate(sched.rounds):
        incoming = {d: accs[s] for s, d in pairs}
        accs = [bucket_combine_op(accs[i],
                                  incoming.get(i, jnp.zeros_like(accs[i])),
                                  jnp.asarray(i in incoming),
                                  op=sched.op(r), interpret=True)
                for i in range(3)]
    want = simulate_schedule(sched, vals)
    for got, w in zip(accs, want):
        np.testing.assert_allclose(np.asarray(got), w, rtol=1e-5,
                                   atol=1e-5)
