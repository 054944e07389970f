"""Jitted public wrappers around the Pallas compression kernels.

``lgc_compress_hist`` is the end-to-end histogram-LGC pipeline used by the
distributed training step and the benchmarks:

  1. maxabs (Pallas, pass 1)
  2. 256-bin magnitude histogram of u = e + delta (Pallas, pass 2)
  3. per-layer thresholds from the CDF (host, 256 scalars)
  4. fused layered-sparsify + error-feedback (Pallas, pass 3)

Matches :func:`repro.kernels.ref.hist_lgc_compress` exactly (same bins and
edges); validated in tests/test_kernels.py across shapes and dtypes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .layered_sparsify import sparsify_ef
from .topk_threshold import histogram, maxabs, thresholds_from_counts


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def lgc_compress_hist(e: jax.Array, delta: jax.Array, cum_ks: jax.Array,
                      received: jax.Array, *, block_rows: int = 64,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Histogram-LGC with error feedback. Returns (g, e_new), f32 (D,).

    The statistics passes read u = e + delta, one fused add that XLA folds
    into the kernels' input copy; the sparsify pass recomputes u in VMEM.
    """
    u = e.astype(jnp.float32) + delta.astype(jnp.float32)
    m = maxabs(u, block_rows=block_rows, interpret=interpret)
    counts = histogram(u, m, block_rows=block_rows, interpret=interpret)
    thr = thresholds_from_counts(counts, m, cum_ks)
    return sparsify_ef(e, delta, thr, received, block_rows=block_rows,
                       interpret=interpret)


@jax.jit
def lgc_compress_hist_ref(e, delta, cum_ks, received):
    """Oracle path (pure jnp), exported for benchmarks."""
    return ref.hist_lgc_compress(e, delta, cum_ks, received)


def selected_counts(g: jax.Array) -> jax.Array:
    """Number of transmitted coordinates (for wire-byte accounting)."""
    return jnp.sum((g != 0).astype(jnp.int32))
