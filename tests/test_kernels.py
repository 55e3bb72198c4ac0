"""Per-kernel validation: Pallas (interpret mode on CPU) vs the pure-jnp
oracles in repro.kernels.ref, swept over shapes/dtypes per the brief."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref

KEY = jax.random.key(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------- flash attention --------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),      # MHA
    (2, 256, 8, 2, 16, 128, 64),     # GQA 4:1
    (1, 192, 4, 1, 64, 64, 64),      # MQA, ragged S/block
    (2, 64, 2, 2, 128, 64, 32),      # TPU-width head_dim
])
def test_flash_attention_sweep(B, S, H, KV, hd, bq, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    pos = jnp.arange(S)
    if S % bq or S % bk:
        pytest.skip("non-divisible block")
    out = ops.flash_attention(q, k, v, pos, pos, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,cap", [(32, 0.0), (0, 50.0), (64, 30.0)])
def test_flash_attention_window_softcap(window, cap):
    B, S, H, hd = 1, 256, 2, 32
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32)
    pos = jnp.arange(S)
    out = ops.flash_attention(q, k, v, pos, pos, window=window,
                              logit_cap=cap, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, pos, pos, window=window,
                                   logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_noncausal():
    B, S, H, hd = 1, 128, 2, 16
    ks = jax.random.split(KEY, 3)
    q, k, v = (_rand(ks[i], (B, S, H, hd), jnp.float32) for i in range(3))
    pos = jnp.arange(S)
    out = ops.flash_attention(q, k, v, pos, pos, causal=False, block_q=64,
                              block_k=64)
    want = ref.flash_attention_ref(q, k, v, pos, pos, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------- flash decode -----------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,KV,hd,bk", [
    (2, 256, 4, 4, 32, 64),
    (3, 512, 8, 2, 64, 128),
    (1, 128, 4, 1, 128, 64),
])
def test_flash_decode_sweep(B, T, H, KV, hd, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, H, hd), dtype)
    kc = _rand(ks[1], (B, T, KV, hd), dtype)
    vc = _rand(ks[2], (B, T, KV, hd), dtype)
    pos = jnp.asarray(
        np.random.default_rng(0).integers(1, T - 1, size=(B,)), jnp.int32)
    out = ops.flash_decode(q, kc, vc, pos, block_k=bk)
    want = ref.flash_decode_ref(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_decode_respects_cache_length():
    """Entries beyond pos must not influence the output."""
    B, T, H, hd = 1, 128, 2, 16
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    kc = _rand(ks[1], (B, T, H, hd), jnp.float32)
    vc = _rand(ks[2], (B, T, H, hd), jnp.float32)
    pos = jnp.array([40], jnp.int32)
    out1 = ops.flash_decode(q, kc, vc, pos, block_k=32)
    kc2 = kc.at[:, 60:].set(99.0)
    vc2 = vc.at[:, 60:].set(-99.0)
    out2 = ops.flash_decode(q, kc2, vc2, pos, block_k=32)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ---------------------------- ssd state scan ----------------------------------

@pytest.mark.parametrize("B,nc,nh,hd,N,Q", [
    (1, 2, 1, 4, 8, 16), (2, 4, 3, 8, 16, 32), (1, 8, 2, 16, 32, 64),
])
def test_ssd_state_scan_sweep(B, nc, nh, hd, N, Q):
    ks = jax.random.split(KEY, 4)
    states = _rand(ks[0], (B, nc, nh, hd, N), jnp.float32)
    totals = -jnp.abs(_rand(ks[1], (B, nc, nh), jnp.float32))
    C = _rand(ks[2], (B, nc, Q, N), jnp.float32)
    cum = -jnp.abs(_rand(ks[3], (B, nc, Q, nh), jnp.float32))
    y, fin = ops.ssd_state_scan(states, totals, C, cum)
    yr, finr = ref.ssd_state_scan_ref(states, totals, C, cum)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(finr),
                               rtol=1e-5, atol=1e-5)


# ---------------------------- rmsnorm ------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (3, 17, 128), (2, 5, 7, 32)])
def test_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], shape, dtype)
    w = 0.1 * _rand(ks[1], shape[-1:], jnp.float32)
    out = ops.rmsnorm(x, w, block_rows=4)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@settings(max_examples=15, deadline=None)
@given(rows=st.integers(1, 33), d=st.sampled_from([8, 32, 128]),
       seed=st.integers(0, 2**16))
def test_property_rmsnorm_matches_oracle(rows, d, seed):
    x = jax.random.normal(jax.random.key(seed), (rows, d))
    w = jax.random.normal(jax.random.key(seed + 1), (d,)) * 0.1
    np.testing.assert_allclose(
        np.asarray(ops.rmsnorm(x, w, block_rows=8)),
        np.asarray(ref.rmsnorm_ref(x, w)), rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), s=st.sampled_from([64, 128]),
       h=st.sampled_from([1, 2, 4]))
def test_property_flash_attention_rowsum(seed, s, h):
    """Attention output is a convex combination of V rows: with V = const c,
    output must be exactly c everywhere (softmax rows sum to 1)."""
    ks = jax.random.split(jax.random.key(seed), 2)
    q = jax.random.normal(ks[0], (1, s, h, 16))
    k = jax.random.normal(ks[1], (1, s, h, 16))
    v = jnp.full((1, s, h, 16), 3.5)
    pos = jnp.arange(s)
    out = ops.flash_attention(q, k, v, pos, pos, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-5)
