"""Optimizer substrate: AdamW vs a numpy reference, clipping, schedules,
and error-feedback gradient compression (convergence property)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import make_mesh, shard_map
from jax.sharding import AxisType

from repro.optim import (
    AdamW, clip_by_global_norm, compressed_pod_mean, cosine_warmup,
    dequantize_int8, quantize_int8)


def _np_adamw_step(p, g, m, v, t, lr=1e-2, b1=0.9, b2=0.95, eps=1e-8,
                   wd=0.1):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    delta = mh / (np.sqrt(vh) + eps)
    if p.ndim >= 2:
        delta = delta + wd * p
    return p - lr * delta, m, v


def test_adamw_matches_numpy_reference():
    opt = AdamW(learning_rate=1e-2, grad_clip_norm=0.0)
    params = {"w": jnp.array([[1.0, -2.0], [0.5, 3.0]]),
              "b": jnp.array([0.1, -0.1])}
    state = opt.init(params)
    g = {"w": jnp.array([[0.1, 0.2], [-0.3, 0.4]]),
         "b": jnp.array([0.05, -0.02])}
    np_p = {k: np.asarray(v) for k, v in params.items()}
    np_m = {k: np.zeros_like(v) for k, v in np_p.items()}
    np_v = {k: np.zeros_like(v) for k, v in np_p.items()}
    for t in range(1, 4):
        params, state, _ = opt.update(g, state, params)
        for k in np_p:
            np_p[k], np_m[k], np_v[k] = _np_adamw_step(
                np_p[k], np.asarray(g[k]), np_m[k], np_v[k], t)
    for k in np_p:
        np.testing.assert_allclose(np.asarray(params[k]), np_p[k],
                                   rtol=1e-5, atol=1e-6)


def test_grad_clip_global_norm():
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(90 + 160), rel=1e-5)
    new_norm = float(jnp.sqrt(sum(jnp.sum(x ** 2)
                                  for x in jax.tree.leaves(clipped))))
    assert new_norm == pytest.approx(1.0, rel=1e-5)


def test_adamw_descends_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    fn = lambda p: jnp.sum((p["x"] - 1.0) ** 2)
    for _ in range(200):
        g = jax.grad(fn)(params)
        params, state, _ = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["x"]), [1.0, 1.0],
                               atol=1e-2)


def test_cosine_warmup_shape():
    lr = cosine_warmup(1e-3, warmup_steps=10, total_steps=100)
    vals = [float(lr(jnp.asarray(s))) for s in [0, 5, 10, 50, 100]]
    assert vals[0] == 0.0
    assert vals[1] == pytest.approx(5e-4)
    assert vals[2] == pytest.approx(1e-3)
    assert vals[3] < vals[2]
    assert vals[4] == pytest.approx(1e-4, rel=1e-2)


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (1000,))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_compressed_pod_mean_and_error_feedback():
    """shard_map over a 1-sized pod axis: mean == identity, and the carried
    error equals the quantization residual."""
    mesh = make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.key(1), (64,))
    e0 = jnp.zeros_like(x)
    fn = shard_map(
        lambda g, e: compressed_pod_mean(g, e, "pod"),
        mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2, check_vma=False)
    mean, err = fn(x, e0)
    np.testing.assert_allclose(np.asarray(mean + err), np.asarray(x),
                               rtol=1e-5, atol=1e-6)


def test_error_feedback_sgd_converges():
    """Quadratic descent *through the compressor* still converges (the
    error-feedback guarantee)."""
    mesh = make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    P = jax.sharding.PartitionSpec
    comp = jax.jit(shard_map(
        lambda g, e: compressed_pod_mean(g, e, "pod"), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False))
    x = jnp.array([4.0, -7.0, 2.0])
    err = jnp.zeros_like(x)
    for _ in range(300):
        g = 2 * (x - 1.0)
        g_hat, err = comp(g, err)
        x = x - 0.05 * g_hat
    np.testing.assert_allclose(np.asarray(x), 1.0, atol=5e-2)
