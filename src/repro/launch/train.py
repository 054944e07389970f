"""Training driver: runs real steps on the host devices (CPU here, TPU pod
in production) with the same step functions the dry-run lowers.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 50 --mode lgc --devices 8

``--devices N`` simulates an N-device mesh on the host (set before jax
import); the LGC mode then treats the data axis as N FL devices.
"""
import argparse
import os
import sys


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "lgc", "lgc_sparse", "fedavg"])
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--sparsity", default="0.01,0.02,0.02")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args()


def main():
    args = _parse()
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.checkpoint import save_checkpoint
    from repro.configs import get_config, get_smoke_config
    from repro.data.tokens import TokenPipeline
    from repro.launch import sharding_rules as rules
    from repro.launch import compat
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (LGCStepConfig, init_ef_tree,
                                    make_lgc_train_step, make_sync_train_step)
    from repro.models import transformer as tf
    from repro.optim.optimizers import OptimizerConfig, get_optimizer

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh(args.devices, model=args.model_parallel)
    jax.set_mesh(mesh)

    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M mode={args.mode} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch)
    pspecs = rules.param_specs(cfg, params, mesh)
    params = rules.place(params, pspecs, mesh)
    x0, y0 = pipe.next_batch()
    batch0 = {"tokens": jnp.asarray(x0), "labels": jnp.asarray(y0)}
    if cfg.arch_type == "vlm":
        batch0["prefix"] = jnp.zeros((args.batch, cfg.n_prefix_tokens, 1024),
                                     cfg.dtype)
    if cfg.arch_type == "audio":
        batch0["prefix"] = jnp.zeros((args.batch, cfg.encoder_seq,
                                      cfg.d_model), cfg.dtype)
    bspecs = rules.batch_specs(cfg, batch0, mesh)

    losses = []
    if args.mode == "sync":
        opt_init, _ = get_optimizer(cfg.optimizer,
                                    OptimizerConfig(lr=args.lr))
        opt_state = opt_init(params)
        opt_state = rules.place(
            opt_state, rules.opt_state_specs(pspecs, opt_state), mesh)
        step = jax.jit(make_sync_train_step(
            cfg, opt_cfg=OptimizerConfig(lr=args.lr)),
            in_shardings=compat.shardings(mesh, (pspecs, rules.opt_state_specs(pspecs, opt_state),
                          bspecs)),
            donate_argnums=(0, 1))
        state = (params, opt_state)
        for i in range(args.steps):
            x, y = pipe.next_batch()
            batch = dict(batch0, tokens=jnp.asarray(x), labels=jnp.asarray(y))
            params, opt_state, loss = step(*state, batch)
            state = (params, opt_state)
            losses.append(float(loss))
            if i % args.log_every == 0:
                print(f"step {i:5d} loss {losses[-1]:.4f}")
    else:
        lgc = LGCStepConfig(
            local_steps=args.local_steps,
            sparsity=tuple(float(x) for x in args.sparsity.split(",")),
            local_lr=args.lr,
            aggregate={"lgc": "dense_masked", "lgc_sparse": "sparse_gather",
                       "fedavg": "none"}[args.mode])
        from repro.launch.mesh import fl_axis_name
        fl_ax = fl_axis_name(mesh)
        n_fl = dict(zip(mesh.axis_names, mesh.devices.shape))[fl_ax]
        especs = rules.ef_specs(pspecs, fl_ax)
        ef = rules.place(init_ef_tree(params, n_fl), especs, mesh)
        step = jax.jit(make_lgc_train_step(cfg, mesh, lgc, bspecs),
                       in_shardings=compat.shardings(mesh, (pspecs, especs, bspecs)),
                       donate_argnums=(0, 1))
        for i in range(args.steps):
            x, y = pipe.next_batch()
            batch = dict(batch0, tokens=jnp.asarray(x), labels=jnp.asarray(y))
            params, ef, loss = step(params, ef, batch)
            losses.append(float(loss))
            if i % args.log_every == 0:
                print(f"round {i:5d} (H={args.local_steps}) "
                      f"loss {losses[-1]:.4f}")
        if args.ckpt_dir:
            save_checkpoint(args.ckpt_dir, args.steps, params)

    if args.ckpt_dir and args.mode == "sync" and args.ckpt_every:
        save_checkpoint(args.ckpt_dir, args.steps, params)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    if not (np.isfinite(losses[-1]) and losses[-1] < losses[0]):
        print("WARNING: loss did not decrease", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
