"""Pallas TPU kernel: fused error-feedback layered sparsification.

The LGC hot path (Algorithm 1 lines 8-11) per element is

    u  = e + delta
    g  = u * 1[ layer(|u|) received ]
    e' = u - g

Unfused, this costs 5 HBM round-trips over D-sized vectors (read e, read
delta, write u, read u, write g, write e').  The fused kernel reads e and
delta once and writes g and e' once -- 4 D-sized transfers, the HBM lower
bound -- recomputing u in VMEM.  Layer membership is a chain of C threshold
comparisons against scalar bin edges produced by
:mod:`repro.kernels.topk_threshold` (C is static, <= 4 channels).  The
fused output must preserve the EF identity u == g + e' bit-exactly
(tests/test_kernels.py::TestSparsifyEF).

Blocks are (block_rows, 128) VMEM tiles over the lane-major view of the
flat gradient, same layout as the statistics kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret
from .topk_threshold import LANES, _as_rows


def _sparsify_ef_kernel(e_ref, d_ref, thr_ref, recv_ref, g_ref, enew_ref, *,
                        n_layers: int):
    u = e_ref[...].astype(jnp.float32) + d_ref[...].astype(jnp.float32)
    a = jnp.abs(u)
    g = jnp.zeros_like(u)
    hi = jnp.float32(jnp.inf)
    for c in range(n_layers):          # static unroll, C <= 4
        lo = thr_ref[0, c]
        mask = (a <= hi) & (a > lo)
        take = mask & (recv_ref[0, c] > 0)
        g = g + jnp.where(take, u, 0.0)
        hi = lo
    g_ref[...] = g.astype(g_ref.dtype)
    enew_ref[...] = (u - g).astype(enew_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def sparsify_ef(e: jax.Array, delta: jax.Array, thr: jax.Array,
                received: jax.Array, *, block_rows: int = 64,
                interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Fused layered sparsify + error-feedback update on flat vectors.

    Args:
      e, delta: (D,) error memory and net progress.
      thr: (C,) descending layer thresholds (bin edges).
      received: (C,) int32/bool channel delivery mask.

    Returns (g, e_new), both (D,) float32.
    """
    d = e.shape[0]
    n_layers = thr.shape[0]
    er, n_blocks, _ = _as_rows(e.astype(jnp.float32), block_rows)
    dr, _, _ = _as_rows(delta.astype(jnp.float32), block_rows)
    kernel = functools.partial(_sparsify_ef_kernel, n_layers=n_layers)
    g, e_new = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, n_layers), lambda i: (0, 0)),
            pl.BlockSpec((1, n_layers), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(er.shape, jnp.float32),
            jax.ShapeDtypeStruct(er.shape, jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(er, dr, thr.reshape(1, -1).astype(jnp.float32),
      received.reshape(1, -1).astype(jnp.int32))
    return g.reshape(-1)[:d], e_new.reshape(-1)[:d]
