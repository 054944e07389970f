"""The 100M-stack frontier: bytes-on-wire vs device-steps/s.

Drives the ``qwen2_100m`` registry task across (sparsity, layer policy)
settings and publishes one row per point:

  * ``wire_bytes_per_round_per_device`` -- analytic uplink bytes from the
    clamped per-leaf channel budgets (launch.steps.lgc_wire_bytes_per_round);
  * ``collective_bytes_hlo`` -- what the COMPILED step actually moves, from
    the post-optimization HLO (analysis.roofline.collective_bytes_from_hlo
    + analysis.hlo_cost trip-count-aware totals), for one representative
    point per aggregate mode;
  * ``device_steps_per_s`` + the loss trajectory (compile excluded).

All points run in one process, one FL device per device present (a chip
belongs to one process at a time).  On the CPU (``JAX_PLATFORMS=cpu``)
``--m-devices`` fixes the host mesh size before the backend starts;
``benchmarks/run.py`` does the same for its own process.

CI runs the smoke preset (same arch family, tiny dims) and gates the rows
against the committed BENCH_100m_baseline.json: wire-bytes ceiling and
loss-decrease floor (benchmarks/check_regression.py::check_100m).  The
full ~128M-parameter sweep is a manual run:

    PYTHONPATH=src python -m benchmarks.bench_100m --preset full --rounds 12

Timings use backend="exact": on the CPU the Pallas kernels run in
interpret mode, a parity backend 10-30x slower than the compiled oracle
(ARCHITECTURE.md §12) -- routing through it would benchmark the
interpreter, not the algorithm.  Rates from a CPU run are not device
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from .common import emit

# (aggregate, sparsity) frontier: the paper's 1%+2%+2% ladder, a 4x-fatter
# ladder, the bucket variant, the dense-psum ceiling and the FedAvg baseline
POINTS = (
    ("sparse_gather", (0.01, 0.02, 0.02)),
    ("sparse_gather", (0.04, 0.08, 0.08)),
    ("bucket_sparse", (0.01, 0.02, 0.02)),
    ("dense_masked", (0.01, 0.02, 0.02)),
    ("none", (0.01, 0.02, 0.02)),
)
# one representative HLO lowering per aggregate mode (an extra AOT compile
# each; the analytic wire numbers cover every point)
HLO_MODES = ("sparse_gather", "bucket_sparse", "dense_masked")


def _point(aggregate: str, sparsity: tuple, preset: str, m_devices: int,
           rounds: int, seq: int, local_lr: float, with_hlo: bool) -> dict:
    import jax.numpy as jnp
    from repro.models.paper_models import make_task

    task = make_task("qwen2_100m", m_devices=m_devices, preset=preset,
                     sparsity=sparsity, aggregate=aggregate,
                     local_lr=local_lr, seq=seq, backend="exact")
    out = task.run(rounds)
    losses = out["losses"]
    row = {
        "task": "qwen2_100m", "preset": preset, "aggregate": aggregate,
        "sparsity": "+".join(f"{f:g}" for f in sparsity),
        "m_devices": m_devices, "rounds": rounds,
        "param_count": task.param_count(),
        "wire_bytes_per_round_per_device": task.wire_bytes_per_round(),
        "device_steps_per_s": round(out["device_steps_per_s"], 3),
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "loss_decrease": round(losses[0] - losses[-1], 4),
    }
    if with_hlo:
        from repro.analysis.hlo_cost import analyze_hlo
        from repro.analysis.roofline import collective_bytes_from_hlo
        b = task.build()
        x, y = b["pipe"].next_batch()
        batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
        received = jnp.ones((m_devices, task.step_cfg.n_channels), jnp.int32)
        text = (b["step"].lower(b["params"], b["ef"], batch, received)
                .compile().as_text())
        cost = analyze_hlo(text)
        row["collective_bytes_hlo"] = collective_bytes_from_hlo(text)
        row["hlo_flops"] = cost.flops
        row["hlo_bytes"] = cost.bytes
    return row


def run(preset: str = "smoke", m_devices: int | None = None,
        rounds: int = 6, seq: int = 32, local_lr: float = 5e-3,
        with_hlo: bool = True, emit_csv: bool = True) -> dict:
    """Every frontier point in this process; ``m_devices`` defaults to the
    devices present."""
    import jax

    m_devices = m_devices or len(jax.devices())
    rows = []
    hlo_done: set = set()
    for aggregate, sparsity in POINTS:
        hlo = (with_hlo and aggregate in HLO_MODES
               and aggregate not in hlo_done)
        hlo_done.add(aggregate)
        row = _point(aggregate, sparsity, preset, m_devices, rounds, seq,
                     local_lr, hlo)
        rows.append(row)
        dense = row["param_count"] * 4
        wire = max(row["wire_bytes_per_round_per_device"], 1)
        if emit_csv:
            emit(f"lgc_100m_{aggregate}_{row['sparsity']}",
                 0.0 if row["device_steps_per_s"] == 0 else
                 1e6 / row["device_steps_per_s"],
                 f"wire_bytes={row['wire_bytes_per_round_per_device']};"
                 f"vs_dense={dense / wire:.0f}x;"
                 f"loss_decrease={row['loss_decrease']}")
    return {"bench": "lgc_100m", "device": jax.devices()[0].device_kind,
            "rows": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--m-devices", type=int, default=None,
                    help="FL devices (default: every device present; on "
                         "the CPU, the host mesh size)")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--local-lr", type=float, default=5e-3)
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--out", default="BENCH_100m.json")
    args = ap.parse_args(argv)
    if (args.m_devices
            and os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"):
        from repro.launch.compat import force_host_device_count
        force_host_device_count(args.m_devices)   # before backend init
    from repro.launch.compat import enable_compile_cache
    enable_compile_cache()
    result = run(preset=args.preset, m_devices=args.m_devices,
                 rounds=args.rounds, seq=args.seq, local_lr=args.local_lr,
                 with_hlo=not args.no_hlo)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out} ({len(result['rows'])} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
