"""Distribution tests — run in subprocesses with 8 host devices.

Each scenario script sets XLA_FLAGS before importing jax (device count is
locked at first init, and the main pytest process must stay at 1 device
for the smoke tests), exercising:
  * sharded train step on a (2,2,2) pod/data/model mesh ≡ single-device
  * elastic checkpoint: save on (4,2), restore+continue on (2,2,2)
  * int8+EF compressed pod-axis gradient psum ≈ dense psum
  * GPipe pipeline over the pod axis ≡ sequential stack
  * dry-run cell on the reduced mesh end-to-end
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n: int = 8, timeout: int = 900) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={n}'\n"
        + textwrap.dedent(script)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, (
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


class TestShardedTraining:
    def test_sharded_step_matches_single_device(self):
        out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.sharding import use_rules
        from repro.training import AdamWConfig, init_opt_state, make_train_step
        from repro.launch.sharding import rules_for, sharding_tree
        from repro.models.config import ShapeConfig

        cfg = get_smoke_config("yi_9b")
        m = build_model(cfg)
        params = jax.tree.map(lambda x: x.astype(jnp.float32),
                              m.init(jax.random.PRNGKey(0)))
        opt = init_opt_state(params)
        B, S = 8, 32
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                         cfg.vocab_size),
        }
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=0)
        step = make_train_step(m, ocfg, remat=False)

        # single device reference
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

        # sharded: mesh (pod=2, data=2, model=2)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", S, B, "train")
        # smoke config dims are tiny: use divisibility-driven rules vs
        # the 2-way model axis
        rules = rules_for(cfg, shape, mesh)
        axes = m.axes()
        p_sh = sharding_tree(axes, rules, mesh)
        params_s = jax.device_put(params, p_sh)
        opt_s = init_opt_state(params_s)
        with use_rules(rules, mesh):
            p_out, _, m_out = jax.jit(
                step, in_shardings=(p_sh, None, None))(params_s, opt_s, batch)
        np.testing.assert_allclose(float(m_out["loss"]),
                                   float(m_ref["loss"]), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-3)
        print("SHARDED_MATCH_OK")
        """)
        assert "SHARDED_MATCH_OK" in out

    def test_elastic_checkpoint_across_meshes(self, tmp_path):
        out = run_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.training import Checkpointer, init_opt_state
        from repro.launch.sharding import rules_for, sharding_tree
        from repro.models.config import ShapeConfig

        cfg = get_smoke_config("olmo_1b")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        axes = m.axes()
        shape = ShapeConfig("t", 32, 8, "train")

        # save from a (4,2) data,model mesh
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        sh1 = sharding_tree(axes, rules_for(cfg, shape, mesh1), mesh1)
        p1 = jax.device_put(params, sh1)
        ck = Checkpointer(r"{tmp_path}")
        ck.save(1, p1)

        # restore onto a (2,2,2) pod,data,model mesh
        mesh2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        sh2 = sharding_tree(axes, rules_for(cfg, shape, mesh2), mesh2)
        step, p2, _ = ck.restore(target=params, shardings=sh2)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        print("ELASTIC_OK")
        """)
        assert "ELASTIC_OK" in out


class TestGradCompression:
    def test_compressed_psum_close_to_dense(self):
        out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.sharding import shard_map
        from repro.training.grad_comp import compressed_psum, init_error_state

        mesh = jax.make_mesh((8,), ("pod",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 64),
                                     jnp.float32)

        def body(g_local, e_local):
            ghat, e = compressed_psum({"w": g_local[0]}, {"w": e_local[0]},
                                      "pod")
            return ghat["w"], e["w"]

        f = shard_map(body, mesh=mesh,
                      in_specs=(P("pod"), P("pod")),
                      out_specs=(P(), P("pod")))
        err = jnp.zeros((8, 64, 64))
        ghat, err = f(g_global, err)
        dense = jnp.mean(g_global, axis=0)
        # int8 quantization error per element ≤ scale/2 ≈ max|g|/254
        tol = float(jnp.max(jnp.abs(g_global))) / 100
        np.testing.assert_allclose(np.asarray(ghat), np.asarray(dense),
                                   atol=tol)
        # error feedback: accumulated residual bounded by one quant step
        assert float(jnp.max(jnp.abs(err))) <= tol
        print("COMPRESS_OK")
        """)
        assert "COMPRESS_OK" in out


class TestPipeline:
    def test_gpipe_matches_sequential(self):
        out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.training.pipeline import pipeline_apply

        mesh = jax.make_mesh((4,), ("pod",))
        D = 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (4, D, D),
                               jnp.float32) / np.sqrt(D)

        def stage(x, w):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (8, D), jnp.float32)
        # sequential reference
        y_ref = x
        for i in range(4):
            y_ref = stage(y_ref, ws[i])
        y = pipeline_apply(stage, x, ws, mesh=mesh, axis="pod", n_micro=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-5)
        print("PIPELINE_OK")
        """)
        assert "PIPELINE_OK" in out


class TestDryrunReducedMesh:
    def test_cell_on_8_devices(self):
        """The dry-run machinery end-to-end on a reduced (4,2) mesh."""
        out = run_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.sharding import use_rules
        from repro.launch.sharding import (rules_for, sharding_tree,
                                           input_specs)
        from repro.models.config import ShapeConfig
        from repro.training import AdamWConfig, init_opt_state, make_train_step

        cfg = get_smoke_config("qwen3_moe_30b_a3b")
        m = build_model(cfg)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        shape = ShapeConfig("t", 64, 8, "train")
        rules = rules_for(cfg, shape, mesh)
        params_shapes = m.param_shapes()
        axes = m.axes()
        p_sh = sharding_tree(axes, rules, mesh)
        structs, b_sh = input_specs(cfg, shape, rules, mesh)
        step = make_train_step(m, AdamWConfig(), remat=True)
        opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
        with use_rules(rules, mesh):
            lowered = jax.jit(
                step, in_shardings=(p_sh, None, b_sh)).lower(
                params_shapes, opt_shapes, structs)
            compiled = lowered.compile()
        assert compiled.cost_analysis()["flops"] > 0
        print("DRYRUN_CELL_OK")
        """)
        assert "DRYRUN_CELL_OK" in out
