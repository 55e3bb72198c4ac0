"""Distribution correctness, run in subprocesses with 8 forced host
devices (the parent process must keep seeing 1 device — the brief forbids
setting XLA_FLAGS globally).

Covered:
  * DP x TP train step == single-device numerics
  * MoE expert-parallel (shard_map + all_to_all) == dense oracle
  * decode with a sequence-sharded KV cache == unsharded decode
  * a miniature multi-pod (2,2,2) dry-run lowers AND compiles
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax
        assert jax.device_count() == {devices}
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_dp_tp_train_step_matches_single_device():
    run_sub("""
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.launch.shardings import assemble, opt_state_shardings
        from repro.launch.steps import build_train_step
        from repro.models.zoo import build_model
        from repro.optim import AdamW

        cfg = get_smoke_config("granite-8b")
        model = build_model(cfg)
        opt = AdamW(learning_rate=1e-3)
        params = model.init_params(jax.random.key(0))
        batch = model.make_batch(jax.random.key(1), 4, 16)

        def grad_fn(p, b, ctx):
            return jax.value_and_grad(lambda q: model.loss(q, b, ctx))(p)

        # single-device reference (loss + grads: the distributed compute)
        l_ref, g_ref = jax.jit(lambda p, b: grad_fn(p, b, None))(params,
                                                                 batch)

        # 2x4 DP x TP
        mesh = make_local_mesh(2, 4)
        ctx, sh = assemble(model, mesh, "train", 4, 16)
        l_d, g_d = jax.jit(
            lambda p, b: grad_fn(p, b, ctx),
            in_shardings=(sh["opt_params"], sh["batch"]),
            out_shardings=(None, sh["opt_params"]))(params, batch)
        np.testing.assert_allclose(float(l_ref), float(l_d), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_d)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            denom = max(np.abs(a).max(), 1e-6)
            assert np.abs(a - b).max() / denom < 2e-2, np.abs(a - b).max()

        # and the full train step must at least run sharded + finite
        opt_sh = opt_state_shardings(sh["opt_params"], mesh)
        state = opt.init(params)
        step = jax.jit(build_train_step(model, opt, ctx, 1),
                       in_shardings=(sh["opt_params"], opt_sh, sh["batch"]),
                       out_shardings=(sh["opt_params"], opt_sh, None))
        p_d, s_d, m_d = step(params, state, batch)
        assert np.isfinite(float(m_d["loss"]))
        print("DP+TP OK")
    """)


@pytest.mark.slow
def test_moe_ep_matches_dense():
    run_sub("""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.models.sharding import ModelContext, default_rules
        from repro.models.moe import moe_block
        from repro.models.zoo import build_model

        cfg = get_smoke_config("qwen3-moe-30b-a3b")
        mesh = make_local_mesh(2, 4)          # EP over model=4 (8 experts)
        rules = default_rules()
        ctx_ep = ModelContext(mesh=mesh, rules=rules, moe_impl="ep")
        k = jax.random.key(0)
        D, E, F = 32, 8, 16
        params = {
            "router": jax.random.normal(k, (D, E)) * 0.5,
            "wi": jax.random.normal(jax.random.key(1), (E, D, 2 * F)) * 0.1,
            "wo": jax.random.normal(jax.random.key(2), (E, F, D)) * 0.1,
        }
        x = jax.random.normal(jax.random.key(3), (8, 16, D), jnp.float32)
        y_dense = moe_block(x, params, k=2, n_experts=E, n_shared=0,
                            capacity_factor=8.0, ctx=None)
        y_ep = moe_block(x, params, k=2, n_experts=E, n_shared=0,
                         capacity_factor=8.0, ctx=ctx_ep)
        # capacity_factor 8 => no drops; EP must equal dense combine
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                                   rtol=2e-4, atol=2e-5)
        print("MoE EP == dense OK")
    """)


@pytest.mark.slow
def test_seq_sharded_decode_matches_unsharded():
    run_sub("""
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.launch.shardings import assemble
        from repro.launch.steps import build_serve_step
        from repro.models.zoo import build_model

        cfg = get_smoke_config("granite-34b")     # MQA decode
        model = build_model(cfg)
        params = model.init_params(jax.random.key(0))
        B, T = 4, 32
        cache = model.init_cache(B, T)
        toks = jax.random.randint(jax.random.key(1), (B,), 0,
                                  cfg.vocab_size, jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)

        ref_logits, _ = model.decode_step(params, cache, toks, pos)

        mesh = make_local_mesh(2, 4)              # kv_seq sharded over model
        ctx, sh = assemble(model, mesh, "decode", B, T)
        assert ctx.rules["kv_seq"] == ("model",)
        step = jax.jit(build_serve_step(model, ctx),
                       in_shardings=(sh["params"], sh["cache"],
                                     sh["tokens"], sh["tokens"]),
                       out_shardings=(None, sh["cache"]))
        d_logits, new_cache = step(params, cache, toks, pos)
        np.testing.assert_allclose(np.asarray(ref_logits),
                                   np.asarray(d_logits),
                                   rtol=2e-2, atol=2e-2)
        print("seq-sharded decode OK")
    """)


@pytest.mark.slow
def test_ring_seq_parallel_mlstm_matches_baseline():
    """The affine-state-exchange sequence-parallel mLSTM (§Perf iter 12)
    must match the single-device chunked scan across a 4-way seq shard."""
    run_sub("""
        from repro.configs import get_smoke_config
        from repro.models.zoo import build_model
        from repro.models.sharding import ModelContext
        from repro.launch.shardings import make_rules
        from repro.launch.mesh import make_local_mesh

        cfg = get_smoke_config("xlstm-1.3b")
        m = build_model(cfg)
        p = m.init_params(jax.random.key(0))
        b = m.make_batch(jax.random.key(1), 2, 64)
        ref = m.forward(p, b)
        mesh = make_local_mesh(2, 4)
        rules = make_rules(cfg, mesh, "prefill", 2, parallelism="ring")
        ctx = ModelContext(mesh=mesh, rules=rules)
        out = jax.jit(lambda pp, bb: m.forward(pp, bb, ctx))(p, b)
        err = float(jnp.abs(ref.astype(jnp.float32)
                            - out.astype(jnp.float32)).max())
        assert err < 0.05, err
        print("ring seq-parallel OK", err)
    """)


@pytest.mark.slow
def test_mini_multipod_dryrun_compiles():
    """A (2,2,2) pod mesh version of the dry-run on a reduced config —
    proves the pod axis shards end-to-end inside CI."""
    run_sub("""
        import dataclasses
        from jax import make_mesh
        from jax.sharding import AxisType
        from repro.configs import get_smoke_config
        from repro.launch.shardings import assemble, opt_state_shardings
        from repro.launch.steps import build_train_step
        from repro.models.zoo import build_model
        from repro.optim import AdamW

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
        cfg = dataclasses.replace(get_smoke_config("granite-8b"),
                                  microbatches=2)
        model = build_model(cfg)
        opt = AdamW()
        ctx, sh = assemble(model, mesh, "train", 8, 32)
        assert ctx.rules["batch"] == ("pod", "data")
        opt_sh = opt_state_shardings(sh["opt_params"], mesh)
        params = model.abstract_params()
        state = jax.eval_shape(opt.init, params)
        batch = model.batch_shapes(8, 32)
        step = build_train_step(model, opt, ctx)
        compiled = jax.jit(step, in_shardings=(sh["opt_params"], opt_sh,
                                               sh["batch"]),
                           out_shardings=(sh["opt_params"], opt_sh, None)
                           ).lower(params, state, batch).compile()
        assert compiled.cost_analysis() is not None
        txt = compiled.as_text()
        assert "all-reduce" in txt or "all-gather" in txt
        print("mini multi-pod dry-run OK")
    """)
