"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body runs in Python on CPU). tests/test_tpu_compile.py compiles
the same kernels for a described TPU v5e."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.flash_prefill.ref import flash_prefill_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


@pytest.mark.slow
@pytest.mark.parametrize("s,h,kv,d,bs,mb", [
    (4, 8, 2, 128, 16, 8),
    (2, 4, 4, 64, 32, 4),
    (3, 9, 3, 64, 16, 5),       # GQA ratio 3 (smollm-like)
    (1, 16, 1, 128, 32, 16),    # MQA (recurrentgemma-like)
    (5, 8, 8, 96, 16, 3),       # MHA, phi3-like head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(s, h, kv, d, bs, mb, dtype, rng):
    nb = s * mb + 1
    q = jnp.asarray(rng.normal(size=(s, h, d)), dtype)
    pk = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), dtype)
    pv = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), dtype)
    bt = jnp.asarray(rng.integers(0, nb, size=(s, mb)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, mb * bs + 1, size=(s,)), jnp.int32)
    ref = paged_attention_ref(q, pk, pv, bt, lens)
    pal = paged_attention(q, pk, pv, bt, lens, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_attention_single_token_context(rng):
    """ctx=1 edge: only the freshly-written slot participates."""
    s, h, kv, d, bs, mb = 2, 4, 2, 64, 16, 4
    nb = 16
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, size=(s, mb)), jnp.int32)
    lens = jnp.ones((s,), jnp.int32)
    ref = paged_attention_ref(q, pk, pv, bt, lens)
    pal = paged_attention(q, pk, pv, bt, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # with ctx=1, output must equal v at the first slot (softmax of one)
    v0 = np.asarray(pv)[np.asarray(bt)[:, 0], :, 0]       # (S, KV, D)
    v0 = np.repeat(v0, h // kv, axis=1)
    np.testing.assert_allclose(np.asarray(ref), v0, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("b,t,h,kv,d,window,bq,bk", [
    (2, 256, 4, 2, 64, 0, 64, 64),
    (1, 256, 8, 8, 128, 0, 128, 128),
    (2, 512, 4, 1, 64, 128, 64, 128),   # windowed (griffin-like)
    (1, 128, 9, 3, 64, 0, 32, 64),
    (1, 512, 2, 2, 128, 256, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_sweep(b, t, h, kv, d, window, bq, bk, dtype, rng):
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, kv, d)), dtype)
    ref = flash_prefill_ref(q, k, v, window)
    pal = flash_prefill(q, k, v, window=window, bq=bq, bk=bk, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_prefill_is_causal(rng):
    """Perturbing future tokens must not change earlier outputs."""
    b, t, h, d = 1, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    out1 = flash_prefill(q, k, v, bq=64, bk=64, interpret=True)
    k2 = k.at[:, t // 2:].add(5.0)
    v2 = v.at[:, t // 2:].add(5.0)
    out2 = flash_prefill(q, k2, v2, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out1[:, :t // 2]),
                               np.asarray(out2[:, :t // 2]),
                               rtol=1e-6, atol=1e-6)
