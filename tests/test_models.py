"""Model zoo: per-arch smoke tests (all 10 assigned architectures at
reduced config), decode/forward consistency, family-specific invariants,
and hypothesis property tests (causality)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.models import layers as L
from repro.models import xlstm as XL
from repro.models.moe import moe_dense, router_probs
from repro.models.zoo import build_model

KEY = jax.random.key(0)


# ---------------------- per-arch smoke (assigned archs) ----------------------

@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_and_train_step(name):
    """Reduced same-family config: one forward + one train step on CPU,
    asserting output shapes and no NaNs (per the brief)."""
    cfg = get_smoke_config(name)
    m = build_model(cfg)
    params = m.init_params(KEY)
    batch = m.make_batch(jax.random.key(1), 2, 32)
    logits = m.forward(params, batch)
    S_out = 32 if cfg.family != "vlm" else 32
    assert logits.shape == (2, S_out, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    loss, grads = jax.value_and_grad(lambda p: m.loss(p, batch))(params)
    assert bool(jnp.isfinite(loss))
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    assert bool(jnp.isfinite(gn)) and float(gn) > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_configs_match_assignment(name):
    """The full configs carry the exact assigned hyperparameters."""
    cfg = get_config(name)
    expected = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
        "granite-3-8b": (40, 4096, 32, 8, 12800, 49280),  # padded 49155
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
    }[name]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected


def test_moe_expert_counts():
    m = get_config("moonshot-v1-16b-a3b")
    assert (m.n_experts, m.experts_per_token, m.n_shared_experts) == (64, 6, 2)
    q = get_config("qwen3-moe-30b-a3b")
    assert (q.n_experts, q.experts_per_token, q.n_shared_experts) == (128, 8, 0)


def test_param_counts_in_expected_range():
    """Rough sanity: named sizes should be near their advertised params."""
    # NB: targets follow from the ASSIGNED hyperparameters, which for
    # moonshot (48L x 64 experts x d_ff 1408) imply ~29B total (the "16B"
    # in the marketing name corresponds to a different layer count).
    approx = {"granite-8b": 8e9, "granite-34b": 34e9, "gemma2-9b": 9e9,
              "pixtral-12b": 12e9, "moonshot-v1-16b-a3b": 29e9,
              "qwen3-moe-30b-a3b": 30e9, "xlstm-1.3b": 1.3e9,
              "zamba2-7b": 7e9}
    for name, target in approx.items():
        n = get_config(name).param_count()
        assert 0.5 * target < n < 1.7 * target, (name, n / 1e9)
    # MoE active << total
    q = get_config("qwen3-moe-30b-a3b")
    assert q.active_param_count() < 0.25 * q.param_count()


# ---------------------- decode == forward ------------------------------------

@pytest.mark.parametrize("name", ["granite-34b", "gemma2-9b",
                                  "qwen3-moe-30b-a3b", "zamba2-7b",
                                  "xlstm-1.3b", "musicgen-large"])
def test_decode_matches_forward(name):
    cfg = get_smoke_config(name)
    m = build_model(cfg)
    params = m.init_params(KEY)
    S = 12
    batch = m.make_batch(jax.random.key(2), 2, S)
    if "embeds" in batch or "patch_embeds" in batch:
        pytest.skip("token-free frontends covered by smoke test")
    full = m.forward(params, batch)
    cache = m.init_cache(2, S)
    step = jax.jit(m.decode_step)
    errs = []
    for t in range(S):
        pos = jnp.full((2,), t, jnp.int32)
        lg, cache = step(params, cache, batch["tokens"][:, t], pos)
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    assert max(errs) < 2.5e-2, errs


# ---------------------- family invariants -------------------------------------

def test_gemma2_local_differs_from_global():
    """The sliding window must change attention output beyond the window."""
    q = jax.random.normal(KEY, (1, 32, 2, 8))
    k = jax.random.normal(jax.random.key(1), (1, 32, 2, 8))
    v = jax.random.normal(jax.random.key(2), (1, 32, 2, 8))
    pos = jnp.arange(32)
    a_g = L.attention_reference(q, k, v, pos, pos, window=0)
    a_l = L.attention_reference(q, k, v, pos, pos, window=4)
    assert float(jnp.abs(a_g[:, :4] - a_l[:, :4]).max()) < 1e-6
    assert float(jnp.abs(a_g[:, 8:] - a_l[:, 8:]).max()) > 1e-4


def test_softcap_bounds_logits():
    x = jnp.linspace(-1000, 1000, 101)
    assert float(jnp.abs(L.softcap(x, 30.0)).max()) <= 30.0


def test_router_gates_normalized():
    x = jax.random.normal(KEY, (64, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    gates, idx, probs = router_probs(x, w, k=2)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert idx.shape == (64, 2)
    assert int(idx.max()) < 8


def test_moe_dense_matches_manual_combine():
    D, E, F, T = 8, 4, 16, 6
    params = {
        "router": jax.random.normal(KEY, (D, E)),
        "wi": jax.random.normal(jax.random.key(1), (E, D, 2 * F)) * 0.1,
        "wo": jax.random.normal(jax.random.key(2), (E, F, D)) * 0.1,
    }
    x = jax.random.normal(jax.random.key(3), (1, T, D))
    y = moe_dense(x, params, k=2)
    # manual: for each token, run its top-2 experts
    gates, idx, _ = router_probs(x.reshape(T, D), params["router"], 2)
    manual = np.zeros((T, D), np.float32)
    for t in range(T):
        for j in range(2):
            e = int(idx[t, j])
            h = x.reshape(T, D)[t] @ params["wi"][e]
            g, u = np.split(np.asarray(h), 2)
            act = np.asarray(jax.nn.silu(g)) * u
            manual[t] += float(gates[t, j]) * (act @ np.asarray(params["wo"][e]))
    np.testing.assert_allclose(np.asarray(y[0]), manual, rtol=2e-4,
                               atol=2e-5)


def test_mlstm_chunked_equals_stepwise():
    """Chunked mLSTM == its own sequential recurrence."""
    B, S, nh, hd = 2, 16, 2, 8
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, nh, hd))
    k = jax.random.normal(ks[1], (B, S, nh, hd))
    v = jax.random.normal(ks[2], (B, S, nh, hd))
    ig = jax.random.normal(ks[3], (B, S, nh))
    fg = jax.random.normal(ks[4], (B, S, nh)) + 2.0
    h_chunk, (finC, finN) = XL.mlstm_chunked(q, k, v, ig, fg, chunk=4)
    state = (jnp.zeros((B, nh, hd, hd)), jnp.zeros((B, nh, hd)))
    outs = []
    for t in range(S):
        o, state = XL.mlstm_decode_step(q[:, t], k[:, t], v[:, t],
                                        ig[:, t], fg[:, t], state)
        outs.append(o)
    h_step = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_step),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(finC), np.asarray(state[0]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(finN), np.asarray(state[1]),
                               rtol=2e-4, atol=2e-4)


# ---------------------- property: causality -----------------------------------

@settings(max_examples=8, deadline=None)
@given(t_cut=st.integers(2, 10), seed=st.integers(0, 100))
def test_property_causality(t_cut, seed):
    """Perturbing tokens at position >= t_cut must not change logits at
    positions < t_cut (decoder-only causal invariant)."""
    cfg = get_smoke_config("granite-8b")
    m = build_model(cfg)
    params = m.init_params(KEY)
    toks = jax.random.randint(jax.random.key(seed), (1, 12), 0,
                              cfg.vocab_size, jnp.int32)
    toks2 = toks.at[0, t_cut:].set(
        (toks[0, t_cut:] + 7) % cfg.vocab_size)
    l1 = m.forward(params, {"tokens": toks})
    l2 = m.forward(params, {"tokens": toks2})
    np.testing.assert_allclose(np.asarray(l1[0, :t_cut]),
                               np.asarray(l2[0, :t_cut]),
                               rtol=1e-3, atol=1e-3)


def test_gemma2_pair_scan_equals_unrolled():
    """The local/global pair-scan (scan_layers=True) must match the
    python-unrolled loop — the structural trick behind correct gemma2
    FLOP accounting."""
    import dataclasses
    cfg = get_smoke_config("gemma2-9b")
    m_scan = build_model(dataclasses.replace(cfg, scan_layers=True))
    m_unroll = build_model(dataclasses.replace(cfg, scan_layers=False))
    params = m_scan.init_params(KEY)
    batch = m_scan.make_batch(jax.random.key(3), 2, 24)
    a = m_scan.forward(params, batch)
    b = m_unroll.forward(params, batch)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-2, atol=2e-2)   # bf16 order-of-ops


def test_moe_capacity_dropping_grace():
    """With capacity_factor << 1 the EP-style capacity math drops tokens;
    dropped tokens must pass through as zeros in the routed output (the
    residual carries them), never NaN."""
    from jax import make_mesh, shard_map
    from jax.sharding import AxisType

    from repro.models.moe import _ep_local

    D, E, T = 16, 4, 32
    k1, k2, k3 = jax.random.split(KEY, 3)
    xt = jax.random.normal(k1, (T, D))
    router = jax.random.normal(k2, (D, E))
    wi = 0.1 * jax.random.normal(k3, (E, D, 64))
    wo = 0.1 * jax.random.normal(k3, (E, 32, D))
    mesh = make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    P = jax.sharding.PartitionSpec
    fn = shard_map(
        lambda x: _ep_local(x, router, wi, wo, k=2, n_experts=E,
                            capacity_factor=0.25, model_axis="model",
                            n_model=1, tokens_replicated=True),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
    out = fn(xt)
    assert bool(jnp.isfinite(out).all())
    # some rows must be exactly zero (dropped) at cf=0.25
    row_norms = jnp.linalg.norm(out, axis=-1)
    assert int((row_norms == 0).sum()) > 0


def test_long_500k_config_consistency():
    """long_500k decode state sizes are O(1) in sequence for the two
    long-capable archs (the DESIGN §Arch-applicability requirement)."""
    from repro.configs.shapes import LONG_CAPABLE
    for name in LONG_CAPABLE:
        cfg = get_smoke_config(name)
        m = build_model(cfg)
        c_small = m.init_cache(1, 64)
        c_large = m.init_cache(1, 256)
        import jax as _j
        small = [x.size for x in _j.tree.leaves(c_small)]
        large = [x.size for x in _j.tree.leaves(c_large)]
        # ssm/recurrent states identical; only attention KV (hybrid) grows
        grows = sum(1 for s, l in zip(small, large) if l > s)
        same = sum(1 for s, l in zip(small, large) if l == s)
        assert same >= grows, (name, small, large)
