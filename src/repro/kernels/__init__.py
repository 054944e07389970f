"""Pallas TPU kernels for the LGC compression hot path + decode attention.

Kernels (each validated against ref.py oracles in interpret mode,
tests/test_kernels.py, and compiled for a described TPU v5e,
tests/test_tpu_compile.py; :func:`platform.resolve_interpret` runs them
compiled on TPU and interpreted on CPU):
  topk_threshold   -- maxabs + 256-bin magnitude histogram (2-pass Top_k)
  layered_sparsify -- fused layered sparsify + error-feedback update
  swa_attention    -- sliding-window flash decode attention (long_500k)

``backend="pallas"`` routes both FL engines through
:func:`lgc_compress_hist`; the engines must still agree with each other on
it (tests/test_fl.py::TestEngineEquivalence::
test_pallas_backend_matches_loop_and_learns -- the equivalence ladder of
docs/ARCHITECTURE.md §1 holds per backend, not just for the exact oracle).
"""
from .ops import lgc_compress_hist, lgc_compress_hist_ref, selected_counts
from .topk_threshold import histogram, maxabs, thresholds_from_counts
from .layered_sparsify import sparsify_ef

__all__ = [
    "lgc_compress_hist", "lgc_compress_hist_ref", "selected_counts",
    "histogram", "maxabs", "thresholds_from_counts", "sparsify_ef",
]
