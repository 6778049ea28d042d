"""Per-kernel allclose sweeps: every Pallas kernel validated in
interpret=True mode against its pure-jnp ref.py oracle across shapes,
dtypes, and block sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.int8_matmul import (int8_matmul, int8_matmul_ref,
                                       quantize_weights)
from repro.kernels.mlstm_scan import mlstm_ref, mlstm_scan
from repro.kernels.platform import use_interpret
from repro.kernels.ssm_scan import ssm_scan, ssm_scan_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _mk(rng, shape, dtype):
    return jax.random.normal(rng, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------- flash attn

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,hd,hq,hkv,bq,bk", [
    (128, 64, 4, 4, 64, 64),     # MHA
    (256, 64, 8, 2, 128, 64),    # GQA 4:1
    (128, 128, 4, 1, 64, 128),   # MQA, wide head
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention(dtype, s, hd, hq, hkv, bq, bk, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _mk(ks[0], (2, hq, s, hd), dtype)
    k = _mk(ks[1], (2, hkv, s, hd), dtype)
    v = _mk(ks[2], (2, hkv, s, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                          bk=bk, backend="interpret")
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_cross_lengths():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _mk(ks[0], (1, 2, 64, 64), jnp.float32)
    k = _mk(ks[1], (1, 2, 256, 64), jnp.float32)
    v = _mk(ks[2], (1, 2, 256, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, bq=64, bk=64,
                          backend="interpret")
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------- ssm scan

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,di,ds,bd,bc", [
    (64, 128, 16, 128, 32),
    (128, 256, 16, 128, 64),
    (256, 128, 8, 64, 256),
])
def test_ssm_scan(dtype, s, di, ds, bd, bc):
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = _mk(ks[0], (2, s, di), dtype) * 0.5
    dt = jax.nn.softplus(_mk(ks[1], (2, s, di), jnp.float32) * 0.3 - 1.0)
    b_t = _mk(ks[2], (2, s, ds), dtype) * 0.5
    c_t = _mk(ks[3], (2, s, ds), dtype) * 0.5
    a = -jnp.exp(jax.random.normal(ks[4], (di, ds)) * 0.3)
    d = jax.random.normal(ks[5], (di,)) * 0.1
    out = ssm_scan(x, dt.astype(dtype), b_t, c_t, a, d, bd=bd, bc=bc,
                   backend="interpret")
    ref = ssm_scan_ref(x, dt.astype(dtype), b_t, c_t, a, d)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **TOL[dtype])


# ------------------------------------------------------------------ mlstm

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,hd,bc", [(64, 32, 16), (128, 64, 32),
                                     (128, 64, 128)])
def test_mlstm_scan(dtype, s, hd, bc):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = _mk(ks[0], (2, 2, s, hd), dtype)
    k = _mk(ks[1], (2, 2, s, hd), dtype)
    v = _mk(ks[2], (2, 2, s, hd), dtype)
    li = _mk(ks[3], (2, 2, s), jnp.float32) * 0.5
    lf = jax.nn.log_sigmoid(_mk(ks[4], (2, 2, s), jnp.float32) + 2.0)
    out = mlstm_scan(q, k, v, li, lf, bc=bc, backend="interpret")
    ref = mlstm_ref(q, k, v, li, lf)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_mlstm_chunk_invariance():
    """Chunk size must not change the math (stability invariant)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = _mk(ks[0], (1, 1, 128, 32), jnp.float32)
    k = _mk(ks[1], (1, 1, 128, 32), jnp.float32)
    v = _mk(ks[2], (1, 1, 128, 32), jnp.float32)
    li = _mk(ks[3], (1, 1, 128), jnp.float32)
    lf = jax.nn.log_sigmoid(_mk(ks[4], (1, 1, 128), jnp.float32) + 1.0)
    o32 = mlstm_scan(q, k, v, li, lf, bc=32, backend="interpret")
    o128 = mlstm_scan(q, k, v, li, lf, bc=128, backend="interpret")
    np.testing.assert_allclose(np.asarray(o32), np.asarray(o128), rtol=1e-4,
                               atol=1e-4)


# ------------------------------------------------------------ int8 matmul

@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (64, 256, 128, 64, 64, 128),
    (128, 128, 256, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul(m, k, n, bm, bn, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = _mk(ks[0], (m, k), dtype)
    w = jax.random.normal(ks[1], (k, n), jnp.float32)
    wq, sc = quantize_weights(w)
    out = int8_matmul(x, wq, sc, backend="interpret", bm=bm, bn=bn, bk=bk)
    ref = int8_matmul_ref(x, wq, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_int8_quantization_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 128))
    wq, sc = quantize_weights(w)
    deq = wq.astype(jnp.float32) * sc[None, :]
    err = jnp.max(jnp.abs(deq - w) / (jnp.max(jnp.abs(w), axis=0)[None] + 1e-9))
    assert float(err) <= 1.0 / 127.0 + 1e-6


# ---------------------------------------------------------- backend pick

@pytest.mark.parametrize("platform,interpret", [("tpu", False),
                                                ("cpu", True),
                                                ("gpu", None)])
def test_auto_backend_by_platform(monkeypatch, platform, interpret):
    """`auto` compiles on TPU, interprets on the CPU, and refuses any
    other platform instead of silently interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no Pallas backend"):
            use_interpret("auto")
    else:
        assert use_interpret("auto") is interpret
    assert use_interpret("pallas") is False
    assert use_interpret("interpret") is True
    with pytest.raises(ValueError):
        use_interpret("mosaic")
