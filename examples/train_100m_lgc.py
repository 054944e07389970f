"""End-to-end driver: train the qwen2_100m federated task with LGC gradient
compression, one FL device per accelerator present.

This drives the registry task (``make_task("qwen2_100m", ...)``), i.e. the
real shard_map train step the dry-run lowers for the production mesh.
Loss must decrease; the script also reports the LGC wire savings vs a
dense exchange.

  PYTHONPATH=src python examples/train_100m_lgc.py --steps 300   # TPU
  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/train_100m_lgc.py \\
      --preset smoke --steps 2 --m-devices 8               # CPU host mesh

On the CPU (``JAX_PLATFORMS=cpu``) ``--m-devices`` sets the number of
virtual host devices; elsewhere it must not exceed the devices present.
"""
import argparse
import os

from repro.launch.compat import enable_compile_cache, force_host_device_count


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--preset", default="full", choices=["full", "smoke"])
    ap.add_argument("--m-devices", type=int, default=None,
                    help="FL devices (default: every device present)")
    ap.add_argument("--batch-per-device", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-lr", type=float, default=3e-3)
    ap.add_argument("--sparsity", default="0.01,0.02,0.02")
    ap.add_argument("--aggregate", default="sparse_gather",
                    choices=["dense_masked", "sparse_gather",
                             "bucket_sparse", "none"])
    ap.add_argument("--backend", default="exact",
                    choices=["exact", "pallas"],
                    help="pallas = fused Pallas compression kernels on the "
                         ">=PALLAS_MIN_ELEMS dense-path leaves (compiled "
                         "on TPU, interpreted on CPU)")
    ap.add_argument("--scenario", default=None,
                    help="e.g. gilbert_flaky for lossy multi-channel uplinks")
    args = ap.parse_args()

    on_cpu = os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"
    if on_cpu and args.m_devices:
        force_host_device_count(args.m_devices)   # before backend init
    enable_compile_cache()
    import jax
    from repro.launch.steps import lgc_wire_bytes_per_round
    from repro.models.paper_models import make_task

    present = len(jax.devices())
    m = args.m_devices or present
    if m > present:
        ap.error(f"--m-devices {m}: only {present} "
                 f"{jax.devices()[0].platform} devices are present")

    task = make_task("qwen2_100m", m_devices=m, scenario=args.scenario,
                     preset=args.preset,
                     sparsity=tuple(float(x)
                                    for x in args.sparsity.split(",")),
                     aggregate=args.aggregate, local_steps=args.local_steps,
                     local_lr=args.local_lr,
                     batch_per_device=args.batch_per_device, seq=args.seq,
                     backend=args.backend)
    n = task.param_count()
    print(f"{task.name}: {n/1e6:.1f}M params, {task.m_devices} FL devices "
          f"on {jax.devices()[0].device_kind}, H={args.local_steps} local "
          f"steps, sparsity {args.sparsity}, aggregate {args.aggregate}")

    out = task.run(args.steps, log_every=20)
    losses = out["losses"]

    import jax.numpy as jnp
    from repro.models import transformer as tf
    p = jax.eval_shape(lambda k: tf.init_params(task.arch, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    wire = lgc_wire_bytes_per_round(p, task.step_cfg)
    dense_mb = wire["none"] / 1e6
    lgc_mb = max(wire[args.aggregate], 1) / 1e6
    print(f"\nwire per round per device: dense {dense_mb:.1f} MB vs "
          f"LGC {lgc_mb:.1f} MB  ({dense_mb/lgc_mb:.1f}x reduction)")
    if args.steps >= 20:
        assert losses[-1] < losses[0], "loss must decrease"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"over {args.steps} rounds "
          f"({out['device_steps_per_s']:.2f} device-steps/s)")


if __name__ == "__main__":
    main()
