"""Mesh scaling of the sharded LGC engine: device-steps/s vs shard count.

The batched engine vectorizes the device axis on ONE chip; the sharded
engine (``engine="sharded"``) partitions it over the FL axis of a real mesh
so each mesh device simulates M/D edge devices and only the server
aggregation crosses the slow axis.  This bench sweeps the mesh size D for a
fixed fleet (default M=256) and reports two throughputs per row:

* ``device_steps_per_s``        -- end-to-end ``run()`` wall, compile included
  (the number CI users see on a fresh process);
* ``steady_device_steps_per_s`` -- the window program alone: compile once,
  then chain K sync windows back-to-back.  This is the scaling metric: the
  window IS the engine hot loop, and XLA compile time (~10s, independent of
  D) would otherwise swamp the mesh signal at bench budgets.

This is a CPU study: each D runs in a fresh subprocess on the CPU backend
(``JAX_PLATFORMS=cpu``), because the host device count
(``--xla_force_host_platform_device_count``) must be fixed before jax
imports -- so the children never need a chip, whatever the parent holds.
Its rates are XLA:CPU numbers, not device numbers.  ``--out`` (and
``benchmarks/run.py``) writes BENCH_sharded.json for CI artifact upload.

Read the scaling ratio against ``physical_cores`` and ``cpu_util`` in the
JSON: D virtual host devices cannot beat the machine's core count, and this
LR workload is memory-bandwidth-bound on CPU (the minibatch gather moves
~50 MB/round at M=256), so host-mesh ratios near 1.0 on 2-core boxes are
the hardware ceiling, not an engine defect.  The host mesh proves the
mechanism (collectives + sharded state residency) on every push; real
multi-chip meshes, where each shard owns its own memory system, are the
deployment target.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from .common import emit


def _steady_window_rate(sim, eng, m: int, h: int, k_windows: int):
    """Throughput of the compiled window program: chain ``k_windows`` sync
    windows (all devices sync every window, like the end-to-end run with
    fixed controllers) and time everything after the first, compiling, call."""
    import jax
    import jax.numpy as jnp

    sim._decide_devices(range(m), 0)
    k_cap = eng._k_cap()
    sync = jnp.ones((m,), bool)
    ks_mat = eng._ks_mat()
    valid = jnp.ones((h,), bool)

    def win(state, i):
        ts = jnp.arange(i * h, (i + 1) * h, dtype=jnp.int32)
        etas = jnp.asarray([sim._eta(t) for t in range(i * h, (i + 1) * h)],
                           jnp.float32)
        return eng._window(*state, eng.data, eng.n_dev,
                           eng.dev_ids, ts, etas, valid, sync, ks_mat,
                           k_cap=k_cap)

    state = (sim.params, eng.w_hat, eng.anchor, eng.ef, eng.scen_carry)
    out = win(state, 0)
    jax.block_until_ready(out)                     # compile + first window
    state = out[:5]
    t0w, t0c = time.time(), os.times()
    for i in range(1, k_windows + 1):
        out = win(state, i)
        state = out[:5]
    jax.block_until_ready(out)
    wall = time.time() - t0w
    tc = os.times()
    cpu = (tc.user + tc.system) - (t0c.user + t0c.system)
    return m * h * k_windows / wall, cpu / wall


def _worker(n_devices: int, m: int, rounds: int, engine: str,
            k_windows: int) -> None:
    from repro.launch.compat import force_host_device_count
    force_host_device_count(n_devices)     # before first backend init
    import jax
    assert len(jax.devices()) == n_devices, (
        f"worker asked for {n_devices} host devices, backend exposes "
        f"{len(jax.devices())} -- XLA_FLAGS override did not take")
    from repro.core import FLConfig, FixedController, LGCSimulator
    from repro.core.fl_batched import BatchedEngine, ShardedEngine
    from repro.models.paper_models import make_mnist_task

    h = 4
    task = make_mnist_task("lr", m_devices=m, n_train=max(2000, 32 * m))
    cfg = FLConfig(rounds=rounds, eval_every=max(rounds // 2, 1))

    def ctrls():
        return [FixedController(h, [200, 300, 392]) for _ in range(m)]

    # end-to-end: History semantics, compile included
    sim = LGCSimulator(task, cfg, ctrls(), mode="lgc", engine=engine)
    t0 = time.time()
    hist = sim.run()
    wall = time.time() - t0

    # steady state: the window program alone on a fresh engine
    sim2 = LGCSimulator(task, cfg, ctrls(), mode="lgc", engine=engine)
    eng = (ShardedEngine(sim2) if engine == "sharded" else
           BatchedEngine(sim2))
    steady, util = _steady_window_rate(sim2, eng, m, h, k_windows)

    print(json.dumps({
        "engine": engine, "n_devices": n_devices, "m_devices": m,
        "rounds": rounds, "wall_s": round(wall, 3),
        "device_steps_per_s": round(m * rounds / wall, 1),
        "steady_device_steps_per_s": round(steady, 1),
        "cpu_util": round(util, 2),
        "final_loss": round(hist.loss[-1], 4),
    }))


def _spawn(n_devices: int, m: int, rounds: int, engine: str,
           k_windows: int) -> dict:
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded_scaling",
         "--worker", "--devices", str(n_devices), "--m", str(m),
         "--rounds", str(rounds), "--engine", engine,
         "--k-windows", str(k_windows)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"sharded bench worker (D={n_devices}) failed:\n"
                           + out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(device_counts=(1, 2, 4, 8), m: int = 256, rounds: int = 40,
        k_windows: int = 25, emit_csv: bool = True) -> dict:
    rows = [_spawn(1, m, rounds, "batched", k_windows)]  # unsharded reference
    for d in device_counts:
        rows.append(_spawn(d, m, rounds, "sharded", k_windows))
    if emit_csv:
        for row in rows:
            emit(f"sharded_scaling_{row['engine']}_d{row['n_devices']}_m{m}",
                 row["wall_s"] * 1e6 / rounds,
                 f"steady_device_steps_per_s="
                 f"{row['steady_device_steps_per_s']};"
                 f"cpu_util={row['cpu_util']};"
                 f"final_loss={row['final_loss']}")
    sharded = {r["n_devices"]: r["steady_device_steps_per_s"] for r in rows
               if r["engine"] == "sharded"}
    d_max = max(sharded)
    scaling = round(sharded[d_max] / sharded[1], 2) if 1 in sharded else None
    if emit_csv and scaling is not None:
        emit(f"sharded_scaling_ratio_1_to_{d_max}_m{m}", 0.0,
             f"scaling={scaling}x")
    return {"benchmark": "sharded_scaling", "task": "lr-mnist",
            "m_devices": m, "rounds": rounds, "k_windows": k_windows,
            "physical_cores": os.cpu_count(), "rows": rows,
            "device_steps_scaling_1_to_max": scaling}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--device-counts", default="1,2,4,8")
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--k-windows", type=int, default=25)
    ap.add_argument("--engine", default="sharded")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.worker:
        _worker(args.devices, args.m, args.rounds, args.engine,
                args.k_windows)
        return
    res = run(device_counts=tuple(int(x) for x in
                                  args.device_counts.split(",")),
              m=args.m, rounds=args.rounds, k_windows=args.k_windows)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
