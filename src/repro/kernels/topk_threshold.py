"""Pallas TPU kernels: magnitude statistics for histogram-Top_k selection.

TPU-native replacement for the global sort behind Top_k (must match the
:mod:`repro.kernels.ref` oracles bit-exactly --
tests/test_kernels.py::TestMaxAbs/TestHistogram):

  pass 1: ``maxabs``    -- blocked max-|x| reduction
  pass 2: ``histogram`` -- blocked 256-bin magnitude histogram
  host    : thresholds from the descending histogram CDF (256 scalars)

Both kernels view the flat gradient as a (rows, 128)-shaped matrix -- the
TPU vector-lane layout -- and tile over row blocks held in VMEM.  Neither
stores a scalar to VMEM: each accumulates into lane-shaped (8, 128) tiles
(one per bin for the histogram) and the final cross-lane reduction runs
outside the kernel.  The histogram scale ``256 / maxabs`` is computed
outside too, with the oracle's own expression, and enters the kernel
broadcast over one row of lanes (a vector operand, so the kernel also
batches under ``jax.vmap``) -- kernel and oracle bin every element
identically.

Grid iteration on TPU is sequential per core, so both kernels accumulate
into their (revisited) output block across grid steps; ``@pl.when(step==0)``
initialises it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

N_BINS = 256
LANES = 128
SUBLANES = 8


def _sublane_tiles(a: jax.Array) -> jax.Array:
    """(rows, 128) -> (rows // 8, 8, 128): whole vreg tiles, no relayout."""
    return a.reshape(-1, SUBLANES, LANES)


def _maxabs_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    a = _sublane_tiles(jnp.abs(x_ref[...].astype(jnp.float32)))
    o_ref[...] = jnp.maximum(o_ref[...], jnp.max(a, axis=0))


def _hist_kernel(scale_ref, x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
    a = jnp.abs(x_ref[...].astype(jnp.float32))            # (rows, 128)
    bins = jnp.clip((a * scale_ref[...]).astype(jnp.int32), 0, N_BINS - 1)
    bins = _sublane_tiles(bins)

    # o_ref[b] holds bin b's per-lane partial counts as one (8, 128) tile
    def count_bin(b, carry):
        o_ref[b] += jnp.sum((bins == b).astype(jnp.int32), axis=0)
        return carry
    jax.lax.fori_loop(0, N_BINS, count_bin, 0)


def _as_rows(x: jax.Array, block_rows: int) -> tuple[jax.Array, int, int]:
    """Pad flat x with zeros to a (rows,128) matrix, rows % block_rows == 0."""
    d = x.shape[0]
    per_block = block_rows * LANES
    padded = (d + per_block - 1) // per_block * per_block
    pad = padded - d
    xr = jnp.pad(x, (0, pad)).reshape(-1, LANES)
    return xr, xr.shape[0] // block_rows, pad


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def maxabs(x: jax.Array, *, block_rows: int = 64,
           interpret: bool | None = None) -> jax.Array:
    """max |x| over a flat vector. Returns (1,1) f32."""
    xr, n_blocks, _ = _as_rows(x, block_rows)
    tile = pl.pallas_call(
        _maxabs_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xr)
    return jnp.max(tile).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def histogram(x: jax.Array, maxabs_val: jax.Array, *, block_rows: int = 64,
              interpret: bool | None = None) -> jax.Array:
    """256-bin |x| histogram; padding-corrected. Returns (256,) int32."""
    xr, n_blocks, pad = _as_rows(x, block_rows)
    m = maxabs_val.reshape(())
    scale = jnp.where(m > 0, N_BINS / m, 0.0)     # == ref.hist_counts
    tiles = pl.pallas_call(
        _hist_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((N_BINS, SUBLANES, LANES),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N_BINS, SUBLANES, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(jnp.full((1, LANES), scale, jnp.float32), xr)
    counts = jnp.sum(tiles, axis=(1, 2))
    return counts.at[0].add(-pad)  # zero padding lands in bin 0


def thresholds_from_counts(counts: jax.Array, maxabs_val: jax.Array,
                           cum_ks: jax.Array) -> jax.Array:
    """Host-side (tiny): per-layer thresholds from the histogram CDF.

    Identical semantics to ref.hist_thresholds.
    """
    desc = jnp.cumsum(counts[::-1])[::-1]
    bin_w = maxabs_val.reshape(()) / N_BINS

    def one(k):
        ok = desc >= k
        b = jnp.where(jnp.any(ok),
                      jnp.max(jnp.where(ok, jnp.arange(N_BINS), -1)), 0)
        return b.astype(jnp.float32) * bin_w
    return jax.vmap(one)(cum_ks).astype(jnp.float32)
