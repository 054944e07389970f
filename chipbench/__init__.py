"""Chip benchmark of the LGC federated sync round.

One run measures one cell of ``BENCHMARK.json`` (a model configuration
under a traffic mix) on the chips JAX finds, and checks what the timed
path produced against a plain float32 reference:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``configs/<config>.json``, the
model family it names in ``families/<family>.py``,
``traffic/<traffic>.json``, ``limits/<cell>.json`` and one reducer module
per per-layer metric in ``metrics/<metric>.py``.  The peak table is
``peaks.json``.  Nothing here is imported by the program.
"""
