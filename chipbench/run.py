"""Run one benchmark cell once on the chips JAX finds.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's task for the cell (``cell.make_task``), gives
it the seed's weights and token stream, and drives it through three sync
rounds with ``task.run(3)``: these compile every program the window uses,
time a steady round, and are the rounds the reference checks.  The window
is one call ``task.run(n)``, ``n = ceil(seconds / round)``, on the host
clock (``run`` reads every round's loss back, so the last round has ended
when it returns).  With ``--trace 1`` the same call runs under the
profiler for a few rounds instead, and the per-layer metrics are read from
the trace.  Then the program's state is freed, the reference replays the
three checked rounds, and the last line of standard output is the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the compile cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
CHECK_ROUNDS = 3


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts backend compiles (cache hits included) from JAX's events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


class Recorder:
    """Wraps the task's step for the checked rounds: keeps each round's
    delivery mask and the per-leaf norms the comparison reads off the state
    the step returns: after round 1 the update the server applied and the
    device's whole progress (that update plus the new error memory, mean
    over the FL devices), after round 3 the change from the initial
    weights; and the shapes and shardings of the step's arguments, for its
    compiled text."""

    def __init__(self, step, seed: int, sigma: float):
        self.step, self.seed, self.sigma = step, seed, sigma
        self.masks, self.update, self.progress, self.change = [], None, None, None
        self.arg_shapes = None

    def __call__(self, params, ef, batch, received):
        import jax
        import numpy as np
        from chipbench import weights as W
        if self.arg_shapes is None:
            self.arg_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                (params, ef, batch, received))
        self.masks.append(np.asarray(received))
        params, ef, loss = self.step(params, ef, batch, received)
        r = len(self.masks)
        if r == 1:
            self.update = np.asarray(W.norms_from_init(params, self.seed,
                                                       self.sigma))
            self.progress = np.asarray(W.norms_from_init(
                params, self.seed, self.sigma, plus=ef))
        if r == CHECK_ROUNDS:
            self.change = np.asarray(W.norms_from_init(params, self.seed,
                                                       self.sigma))
        return params, ef, loss


def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def step_hlo(step, arg_shapes) -> str:
    """The compiled text of the timed step: lowered again for the shapes
    and shardings of its arguments, which finds in JAX's caches the
    executable the window ran."""
    return step.lower(*arg_shapes).compile().as_text()


def _per_layer(cell, bench: dict, ctx) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        value = importlib.import_module(f"chipbench.metrics.{m['name']}"
                                        ).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def prepare(cell, seed: int, step_wrapper=None):
    """Set-up: the built task with the seed's weights and feed, driven
    through the checked rounds.  ``step_wrapper`` plants a fault in the
    timed step (tests only).  Returns (task, shapes, recorder, run())."""
    import jax
    from chipbench import cell as C, feed, weights as W

    task = C.make_task(cell, seed)
    b = task.build()
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), b["params"])
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, b["params"])
    sigma = cell.config["initializer_range"]
    b["params"] = None
    b["params"] = W.make(shapes, seed, sigma, shardings)
    b["pipe"] = feed.for_cell(cell, seed)
    step = b["step"] if step_wrapper is None else step_wrapper(b["step"])
    rec = Recorder(step, seed, sigma)
    b["step"] = rec
    checked = task.run(CHECK_ROUNDS)
    b["step"] = step
    return task, shapes, rec, checked


def check(cell, seed: int, shapes, rec, checked) -> dict:
    """The numbers compared, once the program's state is gone."""
    from chipbench import reference, weights as W
    prog = {"losses": checked["losses"], "update": rec.update,
            "progress": rec.progress, "change": rec.change}
    t_ref = time.perf_counter()
    ref = reference.run_reference(cell, seed, shapes,
                                  rec.masks[:CHECK_ROUNDS], CHECK_ROUNDS)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses "
        f"{ref['losses']}, program losses {prog['losses']}")
    log("leaf: program/reference norms of update, progress, change; "
        "reference first gradient")
    for i, name in enumerate(W.leaf_names(shapes)):
        log(f"  {name}: " + "  ".join(
            f"{prog[k][i]:.6g}/{ref[k][i]:.6g}"
            for k in ("update", "progress", "change"))
            + f"  {ref['grad'][i]:.6g}")
    return reference.readings(prog, ref)


def free(task) -> None:
    """Drop the program's arrays so the reference has the chip's memory."""
    task._built.clear()
    gc.collect()


def run_cell(cell, bench: dict, seed: int, seconds: float, trace: bool, *,
             step_wrapper=None) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import numpy as np
    from chipbench import counts, reference, weights as W
    from chipbench import trace as T

    counter = CompileCounter()
    task, shapes, rec, checked = prepare(cell, seed, step_wrapper)
    round_s = checked["steady_round_s"]
    n = max(1, math.ceil(seconds / round_s))
    log(f"set-up rounds: losses {checked['losses']}, steady round "
        f"{round_s} s, window {n} rounds")

    dev0 = _device_info(jax, cell.chips)
    peak = counts.peaks(dev0["kind"]) if dev0["platform"] == "tpu" else None
    flops_round = (counts.model_flops_per_token(cell.config,
                                                cell.traffic["seq_len"])
                   * cell.tokens_per_round)
    metrics, extra = {}, {}
    setup_s = time.perf_counter() - T_PROCESS
    c0 = counter.n
    if not trace:
        t0 = time.perf_counter()
        out = task.run(n)
        wall = time.perf_counter() - t0
        log(f"backend compiles inside the window: {counter.n - c0}")
        tokens_s = n * cell.tokens_per_round / wall
        metrics["tokens_per_s"] = {"value": tokens_s, "unit": "tokens/s"}
        if peak is not None:
            metrics["mfu"] = {"value": 100.0 * tokens_s * flops_round
                              / cell.tokens_per_round
                              / (cell.chips * peak["bf16_flops_per_s"]),
                              "unit": "%"}
    else:
        n = min(n, cell.traffic["traced_rounds"])
        with tempfile.TemporaryDirectory() as d:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans from the runtime
            jax.profiler.start_trace(d, profiler_options=opts)
            with jax.profiler.TraceAnnotation(T.WINDOW):
                out = task.run(n)
            jax.profiler.stop_trace()
            log(f"backend compiles inside the window: {counter.n - c0}")
            view = T.load(d, chips=cell.chips,
                          hlo=step_hlo(task._built["step"], rec.arg_shapes))
        leaves = tuple((name, tuple(s.shape)) for name, s in zip(
            W.leaf_names(shapes), jax.tree_util.tree_leaves(shapes)))
        ctx = T.Context(view=view, rounds=n, chips=cell.chips, peak=peak,
                        flops_per_round=flops_round,
                        compress_bytes_per_round=cell.traffic["fl_devices"]
                        * counts.compress_bytes(
                            [int(np.prod(shape)) for _, shape in leaves],
                            task.step_cfg.pallas_min_elems),
                        cell=cell, leaves=leaves)
        metrics = _per_layer(cell, bench, ctx)
        extra["busy_s"], extra["window_s"] = view.busy_s(), view.window_s()
        breakdown = view.breakdown()
    failed = int(np.sum(~np.isfinite(out["losses"])))
    device = _device_info(jax, cell.chips) | extra
    if not trace:
        metrics["peak_hbm_gb"] = {"value": device["memory_peak_bytes"] / 1e9,
                                  "unit": "GB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    # the program's state goes before the reference runs on the chip
    free(task)
    del task, out
    correct, checks = reference.judge(
        check(cell, seed, shapes, rec, checked), cell.limits)
    result = {"correct": bool(correct and failed == 0), "attempted": n,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import cell as C
    bench = C.load_benchmark()
    cell = C.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"chipbench: needs a TPU, JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"chipbench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devs)}")
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
