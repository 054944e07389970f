"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 [--program]

For each seed it prints one JSON line of the numbers ``correct`` compares
(``reference.readings``):
  * ``control``: the reference computed with float8 matmul operands, put
    in the program's place (the precision below the configuration's
    bfloat16);
  * ``half_batch``: the reference training each local step on half its
    sequences, the mean taken over the rest (a planted fault);
  * with ``--program``: the program's own sound readings, driven through
    the checked rounds as a benchmark run's set-up drives them (no window).
A state left unchanged reads 1 on ``update_gap`` by construction and
needs no run.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench.run import CHECK_ROUNDS, check, free, log, prepare


def shapes_of(cell):
    """The program's parameter shapes for the cell (shapes only)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    from chipbench.cell import arch_config
    arch = arch_config(cell.config)
    return jax.eval_shape(lambda k: tf.init_params(arch, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def stand_in_readings(cell, seed: int, shapes=None) -> dict:
    """Readings of the control and the half-batch fault against the
    reference, over the checked rounds with the scenario's masks."""
    from chipbench import reference as R
    from chipbench.cell import delivery_masks
    shapes = shapes_of(cell) if shapes is None else shapes
    masks = delivery_masks(cell, seed, CHECK_ROUNDS)
    ref = R.run_reference(cell, seed, shapes, masks, CHECK_ROUNDS)
    out = {}
    for name, kw in (("control", {"precision": "fp8"}),
                     ("half_batch", {"half_batch": True})):
        other = R.run_reference(cell, seed, shapes, masks, CHECK_ROUNDS,
                                **kw)
        out[name] = R.readings(other, ref)
    return out


def program_readings(cell, seed: int) -> dict:
    task, shapes, rec, checked = prepare(cell, seed)
    free(task)
    del task
    return check(cell, seed, shapes, rec, checked)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from chipbench.cell import load_cell
    from chipbench.run import CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        row = {"workload": cell.name, "seed": seed}
        if args.program:
            row["program"] = program_readings(cell, seed)
        else:
            row |= stand_in_readings(cell, seed)
        log(json.dumps(row))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
