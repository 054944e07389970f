"""Distributed runtime tests (subprocess with 8 host devices): sharding
specs, sync/LGC train steps, serve step, and a reduced-mesh dry-run."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(py: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        out = _run("""
            import jax
            from repro.configs import get_smoke_config, list_archs
            from repro.launch import compat
            from repro.launch.mesh import make_host_mesh
            from repro.launch import sharding_rules as rules
            from repro.models import transformer as tf
            mesh = make_host_mesh(8, model=2)
            for arch in list_archs():
                cfg = get_smoke_config(arch)
                params = jax.eval_shape(
                    lambda k: tf.init_params(cfg, k),
                    jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
                specs = rules.param_specs(cfg, params, mesh)
                n1 = len(jax.tree_util.tree_leaves(params))
                n2 = len(jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec)))
                assert n1 == n2, (arch, n1, n2)
            print("ok")
        """)
        assert "ok" in out

    def test_full_config_specs_divisible_on_production_mesh(self):
        """Every full-size param must be divisible by its spec'd axes."""
        out = _run("""
            import jax
            from repro.configs import get_config, list_archs
            from repro.launch.mesh import make_production_mesh
            from repro.launch import sharding_rules as rules
            from repro.models import transformer as tf
            mesh = make_production_mesh()
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for arch in list_archs():
                cfg = get_config(arch)
                params = jax.eval_shape(
                    lambda k: tf.init_params(cfg, k),
                    jax.ShapeDtypeStruct((2,), jax.numpy.uint32))
                specs = rules.param_specs(cfg, params, mesh)
                flat_p = jax.tree_util.tree_leaves_with_path(params)
                flat_s = jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(
                        x, jax.sharding.PartitionSpec))
                for (path, leaf), spec in zip(flat_p, flat_s):
                    for dim, ax in zip(leaf.shape, spec):
                        if ax is None: continue
                        n = sizes[ax] if isinstance(ax, str) else 1
                        assert dim % n == 0, (arch, path, leaf.shape, spec)
            print("ok")
        """, devices=256)
        assert "ok" in out


class TestTrainSteps:
    def test_sync_step_loss_decreases(self):
        out = _run("""
            import jax, jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.data.tokens import TokenPipeline
            from repro.launch import compat
            from repro.launch.mesh import make_host_mesh
            from repro.launch import sharding_rules as rules
            from repro.launch.steps import make_sync_train_step
            from repro.models import transformer as tf
            from repro.optim.optimizers import OptimizerConfig, get_optimizer
            cfg = get_smoke_config("qwen2-1.5b")
            mesh = make_host_mesh(8, model=2)
            jax.set_mesh(mesh)
            params = tf.init_params(cfg, jax.random.PRNGKey(0))
            opt_init, _ = get_optimizer("adamw", OptimizerConfig(lr=1e-3))
            opt = opt_init(params)
            pipe = TokenPipeline(cfg.vocab_size, 64, 16)
            step = make_sync_train_step(cfg, accum_steps=2,
                                        opt_cfg=OptimizerConfig(lr=1e-3))
            pspecs = rules.param_specs(cfg, params, mesh)
            params = rules.place(params, pspecs, mesh)
            opt = rules.place(opt, rules.opt_state_specs(pspecs, opt), mesh)
            x, y = pipe.next_batch()
            batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
            bspecs = rules.batch_specs(cfg, batch, mesh)
            from jax.sharding import PartitionSpec as P
            ospecs = rules.opt_state_specs(pspecs, opt)
            jstep = jax.jit(step,
                            in_shardings=compat.shardings(
                                mesh, (pspecs, ospecs, bspecs)),
                            out_shardings=compat.shardings(
                                mesh, (pspecs, ospecs, P())))
            losses = []
            for i in range(20):
                x, y = pipe.next_batch()
                params, opt, l = jstep(params, opt,
                                       {"tokens": jnp.asarray(x),
                                        "labels": jnp.asarray(y)})
                losses.append(float(l))
            print("first", losses[0], "last", losses[-1])
            assert losses[-1] < losses[0]
        """)
        assert "first" in out

    @pytest.mark.parametrize("aggregate", ["dense_masked", "sparse_gather",
                                           "bucket_sparse", "none"])
    def test_lgc_step_runs_and_learns(self, aggregate):
        out = _run(f"""
            import jax, jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.data.tokens import TokenPipeline
            from repro.launch import compat
            from repro.launch.mesh import make_host_mesh
            from repro.launch import sharding_rules as rules
            from repro.launch.steps import (LGCStepConfig, init_ef_tree,
                                            make_lgc_train_step)
            from repro.models import transformer as tf
            cfg = get_smoke_config("qwen2-1.5b")
            mesh = make_host_mesh(8, model=1)
            jax.set_mesh(mesh)
            params = tf.init_params(cfg, jax.random.PRNGKey(0))
            lgc = LGCStepConfig(local_steps=2, local_lr=5e-3,
                                sparsity=(0.02, 0.03),
                                aggregate="{aggregate}")
            pipe = TokenPipeline(cfg.vocab_size, 64, 16)
            x, y = pipe.next_batch()
            batch = {{"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}}
            bspecs = rules.batch_specs(cfg, batch, mesh)
            pspecs = rules.param_specs(cfg, params, mesh)
            params = rules.place(params, pspecs, mesh)
            from jax.sharding import PartitionSpec as P
            especs = rules.ef_specs(pspecs, "data")
            step = jax.jit(make_lgc_train_step(cfg, mesh, lgc, bspecs),
                           in_shardings=compat.shardings(
                               mesh, (pspecs, especs, bspecs)),
                           out_shardings=compat.shardings(
                               mesh, (pspecs, especs, P())))
            ef = rules.place(init_ef_tree(params, 8), especs, mesh)
            losses = []
            for i in range(15):
                x, y = pipe.next_batch()
                params, ef, l = step(params, ef,
                                     {{"tokens": jnp.asarray(x),
                                       "labels": jnp.asarray(y)}})
                losses.append(float(l))
            print("first", losses[0], "last", losses[-1])
            assert losses[-1] < losses[0]
            # error memory is active for compressed modes
            import numpy as np
            efn = sum(float(jnp.sum(jnp.abs(e))) for e in
                      jax.tree_util.tree_leaves(ef))
            print("ef mass", efn)
            if "{aggregate}" != "none":
                assert efn > 0
        """)
        assert "first" in out


class TestServing:
    def test_serve_step_sharded(self):
        out = _run("""
            import jax, jax.numpy as jnp
            from repro.configs import get_smoke_config
            from repro.launch import compat
            from repro.launch.mesh import make_host_mesh
            from repro.launch import sharding_rules as rules
            from repro.launch.steps import make_serve_step
            from repro.models import transformer as tf
            cfg = get_smoke_config("zamba2-1.2b")
            mesh = make_host_mesh(8, model=2)
            jax.set_mesh(mesh)
            params = tf.init_params(cfg, jax.random.PRNGKey(0))
            b = 8
            cache = tf.init_cache(cfg, b, 64)
            tok = jnp.ones((b, 1), jnp.int32)
            cspecs = rules.cache_specs(cfg, cache, mesh)
            pspecs = rules.param_specs(cfg, params, mesh)
            tspec = rules.batch_specs(cfg, {"token": tok}, mesh)["token"]
            params = rules.place(params, pspecs, mesh)
            cache = rules.place(cache, cspecs, mesh)
            tok = rules.place(tok, tspec, mesh)
            step = jax.jit(make_serve_step(cfg),
                           in_shardings=compat.shardings(mesh, (pspecs, tspec, cspecs)),
                           out_shardings=compat.shardings(mesh, (tspec, cspecs)))
            for i in range(4):
                tok, cache = step(params, tok, cache)
            assert int(cache["pos"]) == 4
            print("ok", tok.shape)
        """)
        assert "ok" in out


class TestDryRunIntegration:
    def test_dryrun_cli_smoke_mesh(self):
        """The real dryrun module, 512 fake devices, one cheap pair."""
        env = dict(os.environ, PYTHONPATH=SRC)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             "whisper-small", "--shape", "decode_32k"],
            capture_output=True, text=True, env=env, timeout=1200,
            cwd=os.path.dirname(SRC))
        assert out.returncode == 0, out.stderr[-3000:]
        assert "all dry-runs compiled OK" in out.stdout
