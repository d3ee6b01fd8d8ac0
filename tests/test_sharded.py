"""Sharded executors (SUMMA/Cannon) + small-mesh jit of the real step fns.

These need >1 device, so they run in a subprocess with
``xla_force_host_platform_device_count=8`` (the main test process must keep
seeing ONE device).
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = "from repro.launch.mesh import auto_mesh\n" + textwrap.dedent(code)
    p = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, env=env, timeout=420)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_summa_2d_matches_dense():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.exec.sharded import matmul_2d
        mesh = auto_mesh((2, 4), ("x", "y"))
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((32, 96)), jnp.float32)
        out = matmul_2d(a, b, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                                   rtol=1e-4, atol=1e-4)
        print("ok")
    """)


def test_cannon_matches_dense():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.exec.sharded import matmul_cannon
        mesh = auto_mesh((2, 2), ("x", "y"))
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        out = matmul_cannon(a, b, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                                   rtol=1e-4, atol=1e-4)
        print("ok")
    """)


def test_reduce_scatter_matmul():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.exec.sharded import reduce_scatter_matmul
        mesh = auto_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
        out = reduce_scatter_matmul(a, b, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                                   rtol=1e-4, atol=1e-4)
        print("ok")
    """)


def test_train_step_on_small_mesh():
    """The real train_step jits + runs with real shardings on a 2x4 mesh."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs.base import get_plan, get_reduced
        from repro.models import lm as M
        from repro.train.steps import make_train_step
        from repro.launch import specs as S
        from repro.data.pipeline import DataConfig, make_batch

        mesh = auto_mesh((2, 4), ("data", "model"))
        cfg = replace(get_reduced("qwen3-8b"), d_ff=192)
        plan = replace(get_plan("qwen3-8b", "train_4k"), microbatches=2)
        step, init_opt = make_train_step(cfg, plan, mesh)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        p_sh = S.params_shardings(cfg, plan, mesh)
        params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
        opt = init_opt(params)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8,
                          microbatches=2)
        batch = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}
        b_sh = S.batch_shardings(cfg, S.SHAPES["train_4k"], plan, mesh,
                                 train=True)
        jitted = jax.jit(step, in_shardings=(p_sh, None, None),
                         donate_argnums=(0,))
        with mesh:
            p2, o2, m = jitted(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        print("loss", float(m["loss"]))
    """)


def test_decode_step_on_small_mesh():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs.base import get_plan, get_reduced
        from repro.models import lm as M
        from repro.models.decode import init_cache
        from repro.train.steps import make_decode_step, make_prefill_step

        mesh = auto_mesh((2, 4), ("data", "model"))
        cfg = get_reduced("olmoe-1b-7b")
        plan = get_plan("olmoe-1b-7b", "decode_32k")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        pre = make_prefill_step(cfg, plan, mesh, max_len=24)
        dec = make_decode_step(cfg, plan, mesh)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
        with mesh:
            cache, lg, tok = jax.jit(pre)(params, {"tokens": toks})
            for _ in range(3):
                cache, lg, tok = jax.jit(dec)(params, cache, tok)
        assert np.isfinite(np.asarray(lg)).all()
        print("ok")
    """)


def test_moe_expert_parallel_matches_scatter():
    """The shard_map expert-parallel MoE (the on-mesh default) must produce
    the same outputs as the GSPMD scatter implementation."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.moe import moe_ffn
        from repro.models.moe_ep import moe_ffn_ep

        mesh = auto_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        B, S, D, E, F, K = 4, 8, 16, 8, 12, 2
        params = {
            "router": jnp.asarray(rng.standard_normal((D, E)) * 0.1,
                                  jnp.float32),
            "w1": jnp.asarray(rng.standard_normal((E, D, F)) * 0.1,
                              jnp.float32),
            "w3": jnp.asarray(rng.standard_normal((E, D, F)) * 0.1,
                              jnp.float32),
            "w2": jnp.asarray(rng.standard_normal((E, F, D)) * 0.1,
                              jnp.float32),
        }
        x = jnp.asarray(rng.standard_normal((B, S, D)), jnp.float32)
        # high capacity -> no drops -> implementations must agree exactly
        y_ref, aux_ref = jax.jit(lambda x, p: moe_ffn(
            x, p, top_k=K, capacity_factor=8.0))(x, params)
        with mesh:
            y_ep, aux_ep = jax.jit(lambda x, p: moe_ffn_ep(
                x, p, top_k=K, capacity_factor=8.0, act=jax.nn.silu,
                mesh=mesh, batch_axes=("data",)))(x, params)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)
        # aux is computed per data shard and averaged (standard DP-MoE
        # approximation): mean of per-shard f*p != global f*p exactly
        np.testing.assert_allclose(float(aux_ep), float(aux_ref),
                                   rtol=0.25)
        # grads flow through the shard_map path
        def loss(p):
            y, aux = moe_ffn_ep(x, p, top_k=K, capacity_factor=8.0,
                                act=jax.nn.silu, mesh=mesh,
                                batch_axes=("data",))
            return (y ** 2).sum() + aux
        with mesh:
            g = jax.jit(jax.grad(loss))(params)
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree.leaves(g))
        print("ok")
    """)


def test_gather_once_matches_standard_train_step():
    """gather_once restructures the grad computation; one step must match
    the standard path (bf16-accumulation tolerance)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs.base import get_plan, get_reduced
        from repro.models import lm as M
        from repro.train.steps import make_train_step
        from repro.launch import specs as S
        from repro.data.pipeline import DataConfig, make_batch

        mesh = auto_mesh((2, 4), ("data", "model"))
        cfg = replace(get_reduced("qwen3-8b"), d_ff=192)
        base_plan = replace(get_plan("qwen3-8b", "train_4k"),
                            microbatches=2)
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8,
                          microbatches=2)
        batch = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}
        params0 = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        outs = {}
        for name, plan in [("std", base_plan),
                           ("g1", replace(base_plan, gather_once=True))]:
            step, init_opt = make_train_step(cfg, plan, mesh)
            p_sh = S.params_shardings(cfg, plan, mesh)
            params = {k: jax.device_put(v, p_sh[k])
                      for k, v in params0.items()}
            opt = init_opt(params)
            with mesh:
                p2, o2, m = jax.jit(step)(params, opt, batch)
            outs[name] = (float(m["loss"]), p2)
        assert abs(outs["std"][0] - outs["g1"][0]) < 1e-4
        for k in outs["std"][1]:
            np.testing.assert_allclose(
                np.asarray(outs["std"][1][k], np.float32),
                np.asarray(outs["g1"][1][k], np.float32),
                rtol=2e-2, atol=2e-3)
        print("ok")
    """)
