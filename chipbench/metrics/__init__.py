"""Per-layer metric reducers, one module per metric named in
``BENCHMARK.json``.  Each defines ``read(ctx) -> float | None`` over a
:class:`chipbench.trace.Context`; ``None`` when the trace holds nothing it
reads, and the harness then leaves the metric out."""
