import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks device count on first init.
__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) and report
memory analysis, cost analysis, and roofline terms.  No real allocation --
all inputs are ShapeDtypeStructs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --multipod --mode lgc

Modes (train_4k only; serving shapes always use the plain serve path):
  sync        standard data+tensor-parallel step (framework baseline)
  lgc         paper Algorithm 1 (H local steps + histogram-LGC, dense psum)
  lgc_sparse  LGC with layered sparse all_gather (multi-channel, beyond-paper)
  lgc_bucket  LGC with bucket-argmax selection (sort-free, shard-local --
              the TPU-native variant, EXPERIMENTS.md I-C6)
  fedavg      H local steps, dense exchange (no compression) -- paper baseline
"""

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.roofline import analyze_compiled
from repro.configs import get_config, list_archs
from repro.launch import sharding_rules as rules
from repro.launch import shapes as shp
from repro.launch import compat
from repro.launch.mesh import fl_axis_name, make_production_mesh
from repro.launch.steps import (ACCUM_STEPS, LGCStepConfig,
                                make_lgc_train_step, make_prefill_step,
                                make_serve_step, make_sync_train_step)
from repro.models import transformer as tf
from repro.optim.optimizers import get_optimizer


def _abstract_params(cfg):
    return jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              mode: str = "sync", lgc_cfg: LGCStepConfig | None = None,
              cfg_overrides: dict | None = None):
    """Lower + compile one (arch, shape, mesh, mode). Returns (report, extras)."""
    cfg = get_config(arch)
    shape = shp.SHAPES[shape_name]
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    jax.set_mesh(mesh)
    fl_ax = fl_axis_name(mesh)
    if mode in ("lgc", "lgc_sparse", "lgc_bucket", "fedavg") and cfg.fsdp:
        # (a) FL devices must hold whole replicas along the FL axis;
        # (b) FSDP params + gather inside the pod-manual shard_map region
        #     trip an XLA SpmdPartitioner CHECK (ExpandDeviceGroupsWithIota,
        #     spmd_partitioner_util.cc:504) -- recorded in EXPERIMENTS.md.
        cfg = dataclasses.replace(cfg, fsdp=False)
    if shape_name == "prefill_32k":
        cfg = dataclasses.replace(cfg, attn_q_chunk=256)

    params_sds = _abstract_params(cfg)
    pspecs = rules.param_specs(cfg, params_sds, mesh)
    specs = shp.input_specs(cfg, shape_name)

    if shape.kind == "train":
        batch_specs = rules.batch_specs(cfg, specs, mesh)
        if mode == "sync":
            opt_init, _ = get_optimizer(cfg.optimizer)
            opt_sds = jax.eval_shape(opt_init, params_sds)
            ospecs = rules.opt_state_specs(pspecs, opt_sds)
            step = make_sync_train_step(
                cfg, accum_steps=ACCUM_STEPS.get(arch, 1))
            jitted = jax.jit(step,
                             in_shardings=compat.shardings(mesh, (pspecs, ospecs, batch_specs)),
                             out_shardings=compat.shardings(mesh, (pspecs, ospecs, P())))
            args = (params_sds, opt_sds, specs)
        else:
            lgc = lgc_cfg or LGCStepConfig(
                aggregate={"lgc": "dense_masked",
                           "lgc_sparse": "sparse_gather",
                           "lgc_bucket": "bucket_sparse",
                           "fedavg": "none"}[mode])
            step = make_lgc_train_step(cfg, mesh, lgc, batch_specs,
                                       param_spec_tree=pspecs)
            n_fl = dict(zip(mesh.axis_names, mesh.devices.shape))[fl_ax]
            ef_sds = jax.eval_shape(
                lambda p: jax.tree_util.tree_map(
                    lambda x: jnp.zeros((n_fl,) + x.shape,
                                        jnp.dtype(lgc.ef_dtype)), p),
                params_sds)
            especs = rules.ef_specs(pspecs, fl_ax)
            jitted = jax.jit(step,
                             in_shardings=compat.shardings(mesh, (pspecs, especs, batch_specs)),
                             out_shardings=compat.shardings(mesh, (pspecs, especs, P())))
            args = (params_sds, ef_sds, specs)
        n_tokens = shape.global_batch * shape.seq_len

    elif shape.kind == "prefill":
        batch_specs = rules.batch_specs(cfg, specs, mesh)
        step = make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=compat.shardings(mesh, (pspecs, batch_specs)))
        args = (params_sds, specs)
        n_tokens = shape.global_batch * shape.seq_len

    else:  # decode
        cspecs = rules.cache_specs(cfg, specs["cache"], mesh)
        tok_spec = rules.batch_specs(cfg, {"token": specs["token"]}, mesh)["token"]
        step = make_serve_step(cfg, window=shp.window_for(cfg, shape_name))
        jitted = jax.jit(step,
                         in_shardings=compat.shardings(mesh, (pspecs, tok_spec, cspecs)),
                         out_shardings=compat.shardings(mesh, (tok_spec, cspecs)))
        args = (params_sds, specs["token"], specs["cache"])
        n_tokens = shape.global_batch          # one new token per sequence

    t0 = time.time()
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mesh_desc = "2x16x16" if multi_pod else "16x16"
    report = analyze_compiled(
        compiled, cfg, arch=arch, shape=shape_name, shape_kind=shape.kind,
        n_tokens=n_tokens, mesh_desc=mesh_desc, mode=mode, n_chips=n_chips)
    extras = {"t_lower_s": round(t_lower, 1),
              "t_compile_s": round(t_compile, 1),
              "memory_analysis": str(compiled.memory_analysis())}
    return report, extras


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(shp.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "lgc", "lgc_sparse", "lgc_bucket", "fedavg"])
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--sparsity", default="0.01,0.02,0.02")
    args = ap.parse_args(argv)

    pairs = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(shp.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    lgc_cfg = LGCStepConfig(
        local_steps=args.local_steps,
        sparsity=tuple(float(x) for x in args.sparsity.split(",")),
        aggregate={"lgc": "dense_masked", "lgc_sparse": "sparse_gather",
                   "lgc_bucket": "bucket_sparse",
                   "fedavg": "none", "sync": "dense_masked"}[args.mode])

    failures = []
    for arch, shape_name, mp in pairs:
        tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'} [{args.mode}]"
        try:
            report, extras = lower_one(arch, shape_name, multi_pod=mp,
                                       mode=args.mode, lgc_cfg=lgc_cfg)
            print(report.summary(), flush=True)
            print("   ", extras["memory_analysis"][:160], flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps({**report.asdict(), **extras}) + "\n")
        except Exception as e:  # noqa: BLE001 -- report and continue
            failures.append((tag, repr(e)))
            print(f"FAIL {tag}: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e[:200])
        sys.exit(1)
    print("\nall dry-runs compiled OK")


if __name__ == "__main__":
    main()
