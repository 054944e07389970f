"""Bring-up check: the LGC main path on one TPU chip.

Runs the system through the entry points a user calls and checks what
comes out:

  (a) ``make_task("qwen2_100m", preset="full", m_devices=1, seq=512,
      batch_per_device=4, local_steps=2)`` -> ``LGCTransformerTask.run`` ->
      ``make_lgc_train_step``, a few sync rounds each for
      ``aggregate="dense_masked", backend="pallas"`` (every matmul leaf
      through the compiled Pallas compression kernels) and the task's
      default ``aggregate="sparse_gather"``;
  (b) one ``dense_masked`` round from the same state and batch with
      ``backend="exact"`` (the ``kernels/ref.py`` oracle), which must equal
      the ``backend="pallas"`` round bit for bit;
  (c) one ``run_baseline`` of ``cnn_mnist`` on the batched simulator engine
      with ``backend="pallas"``, M=8, two sync windows.

Every loss must be finite, the parameters must move, and the pallas step's
compiled HLO must hold ``tpu_custom_call`` (kernels compiled, not
interpreted).  With ``--four-chips`` it runs only the cross-chip phase:
``qwen2_100m`` at full width on a ``(data=4, model=1)`` mesh, the
``sparse_gather`` exchange held allclose to ``dense_masked`` under a
saturating sparsity ladder, the four stacked error-feedback rows distinct,
and params, EF and batch sharded over all four chips.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

QWEN = dict(preset="full", seq=512, batch_per_device=4, local_steps=2)
SATURATING = (1.0, 0.5, 0.5)     # cumulative clamp: every coordinate sent


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _compiled_step(task):
    """The task's jitted step compiled at its live shapes (a cache hit
    after ``task.run``)."""
    import jax
    import jax.numpy as jnp
    b = task.build()
    tokens = jax.ShapeDtypeStruct(
        (task.m_devices * task.batch_per_device, task.seq), jnp.int32)
    received = jax.ShapeDtypeStruct(
        (task.m_devices, task.step_cfg.n_channels), jnp.int32)
    return b["step"].lower(b["params"], b["ef"],
                           {"tokens": tokens, "labels": tokens},
                           received).compile()


def _tree_changed(before, after) -> bool:
    import jax
    import jax.numpy as jnp
    return any(bool(jnp.any(a != b)) for a, b in zip(
        jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(after)))


def _max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def phase_qwen_rounds(aggregate: str, backend: str, rounds: int = 4,
                      preset: str = "full", m_devices: int = 1,
                      **task_kw) -> dict:
    """(a): ``rounds`` sync rounds of the qwen2_100m task, checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.paper_models import make_task

    kw = {**QWEN, "preset": preset, **task_kw}
    task = make_task("qwen2_100m", m_devices=m_devices, aggregate=aggregate,
                     backend=backend, **kw)
    p0 = jax.tree_util.tree_map(jnp.copy, task.build()["params"])
    out = task.run(rounds)
    losses = out["losses"]
    require(bool(np.isfinite(losses).all()),
            f"{aggregate}/{backend}: non-finite loss {losses}")
    require(_tree_changed(p0, task.build()["params"]),
            f"{aggregate}/{backend}: parameters did not move")
    return {
        "phase": f"qwen2_100m {aggregate} {backend}",
        "m_devices": m_devices, "rounds": rounds,
        "param_count": task.param_count(), "losses": losses,
        "compile_and_first_round_s": out["first_round_s"],
        "steady_round_s": out["steady_round_s"],
        "tpu_custom_call": "tpu_custom_call" in _compiled_step(task).as_text(),
    }


def phase_pallas_vs_exact(preset: str = "full", **task_kw) -> dict:
    """(b): one dense_masked round, pallas vs exact, same state and batch."""
    import jax
    from repro.models.paper_models import make_task

    kw = {**QWEN, "preset": preset, **task_kw}
    ends = {}
    for backend in ("pallas", "exact"):
        task = make_task("qwen2_100m", m_devices=1, aggregate="dense_masked",
                         backend=backend, **kw)
        out = task.run(1)
        b = task.build()
        ends[backend] = (out["losses"], jax.device_get(b["params"]),
                         jax.device_get(b["ef"]))
        del task, b
    (lp, pp, ep), (le, pe, ee) = ends["pallas"], ends["exact"]
    diff = max(_max_abs_diff(pp, pe), _max_abs_diff(ep, ee))
    require(lp == le, f"pallas vs exact: losses differ {lp} vs {le}")
    require(diff == 0.0,
            f"pallas vs exact: params/EF differ, max abs diff {diff:.3e}")
    return {"phase": "qwen2_100m dense_masked pallas vs exact",
            "loss": lp[0], "max_abs_diff": diff, "bitwise": diff == 0.0}


def phase_cnn_window(m_devices: int = 8, h: int = 4, windows: int = 2,
                     n_train: int = 2000) -> dict:
    """(c): batched simulator engine, cnn_mnist, Pallas backend."""
    import numpy as np
    from repro.core import FLConfig, run_baseline
    from repro.models.paper_models import make_task

    task = make_task("cnn_mnist", m_devices=m_devices, n_train=n_train)
    t0 = time.perf_counter()
    hist = run_baseline(task, FLConfig(rounds=h * windows, eval_every=h),
                        "lgc", h=h, engine="batched", backend="pallas")
    wall = time.perf_counter() - t0
    require(len(hist.loss) >= windows and np.isfinite(hist.loss).all(),
            f"cnn_mnist: losses {hist.loss}")
    require(hist.uplink_mb[-1] > 0, "cnn_mnist: nothing crossed the uplink")
    return {"phase": "cnn_mnist batched pallas", "m_devices": m_devices,
            "sync_windows": windows, "losses": hist.loss,
            "uplink_mb": hist.uplink_mb[-1], "wall_s_with_compile": wall}


def phase_four_chips(preset: str = "full", rounds: int = 2,
                     **task_kw) -> dict:
    """--four-chips: the layered exchange across a (data=4, model=1) mesh."""
    import jax
    import numpy as np
    from repro.models.paper_models import make_task

    n = 4
    kw = {**QWEN, "preset": preset, **task_kw}
    res = {"phase": "qwen2_100m four chips", "m_devices": n, "rounds": rounds}
    params = {}
    for aggregate in ("dense_masked", "sparse_gather"):
        task = make_task("qwen2_100m", m_devices=n, aggregate=aggregate,
                         sparsity=SATURATING, backend="exact", **kw)
        out = task.run(rounds)
        params[aggregate] = jax.device_get(task.build()["params"])
        res[f"saturating {aggregate}"] = {
            k: out[k] for k in ("losses", "first_round_s", "steady_round_s")}
        del task
    ld = res["saturating dense_masked"]["losses"]
    ls = res["saturating sparse_gather"]["losses"]
    require(np.isfinite(ld).all() and np.isfinite(ls).all(),
            f"four chips: non-finite losses {ld} {ls}")
    np.testing.assert_allclose(ls, ld, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(params["sparse_gather"]),
                    jax.tree_util.tree_leaves(params["dense_masked"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-3, rtol=1e-2)
    res["saturating max abs param diff"] = _max_abs_diff(
        params["sparse_gather"], params["dense_masked"])

    # the task's own path (default sparsity): EF rows and shardings
    task = make_task("qwen2_100m", m_devices=n, backend="exact", **kw)
    out = task.run(rounds)
    res["default sparse_gather"] = {
        k: out[k] for k in ("losses", "first_round_s", "steady_round_s")}
    require(np.isfinite(out["losses"]).all(),
            f"four chips sparse_gather: losses {out['losses']}")
    b = task.build()
    ef_leaves = jax.tree_util.tree_leaves(b["ef"])
    res["ef_rows_distinct"] = any(
        not np.allclose(rows, rows[:1])
        for rows in (np.asarray(leaf, np.float32).reshape(n, -1)
                     for leaf in ef_leaves))
    require(res["ef_rows_distinct"],
            "four chips: the stacked EF rows are all equal")
    ins = jax.tree_util.tree_leaves(_compiled_step(task).input_shardings[0])
    require(all(len(s.device_set) == n for s in ins),
            "four chips: an input is not placed on all four chips")
    # after the replicated params come the EF leaves, then the two batch
    # leaves: each split over the FL axis
    shapes = [x.shape for x in ef_leaves] + 2 * [
        (n * task.batch_per_device, task.seq)]
    n_params = len(jax.tree_util.tree_leaves(b["params"]))
    require(all(s.shard_shape(shape)[0] == shape[0] // n
                for s, shape in zip(ins[n_params:], shapes)),
            "four chips: EF or batch not split over the FL axis")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip exchange phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    want = 4 if args.four_chips else 1
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < want:
        print(f"chip_smoke: needs {want} chips, found {len(jax.devices())}",
              file=sys.stderr)
        return 2

    from repro.launch.compat import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        phases = [phase_four_chips]
    else:
        phases = [
            lambda: phase_qwen_rounds("dense_masked", "pallas"),
            lambda: phase_qwen_rounds("sparse_gather", "pallas"),
            phase_pallas_vs_exact,
            phase_cnn_window,
        ]
    for phase in phases:
        t0 = time.perf_counter()
        res = phase()
        if res["phase"] == "qwen2_100m dense_masked pallas":
            require(res["tpu_custom_call"],
                    "pallas step HLO holds no tpu_custom_call")
        res["phase_wall_s"] = time.perf_counter() - t0
        print(json.dumps(res), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
