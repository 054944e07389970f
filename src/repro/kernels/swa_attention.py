"""Pallas TPU kernel: sliding-window flash attention, decode step.

One new query token attends to a ring-buffer KV cache of window size W
(the sub-quadratic attention used by dense architectures at long_500k;
oracle agreement pinned by tests/test_kernels.py::TestSWADecode).  Per (batch, head) grid step the kernel holds the query row
and one W x Dh K/V tile in VMEM and runs an online-softmax (flash) loop
over W in chunks, so the softmax is single-pass and never materialises the
(W,) probability vector in HBM.

Constraints: W * Dh * 4 bytes * 2 (K and V) must fit VMEM -- true for the
production window (4096 x 128 ~ 4 MB).  For larger windows the grid would
gain a W dimension with output rescaling; not needed here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret


def _swa_decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, *,
                       chunk: int, window: int):
    q = q_ref[0, 0, :].astype(jnp.float32)                 # (Dh,)
    valid = len_ref[0, 0]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))

    m = jnp.float32(-jnp.inf)                              # running max
    l = jnp.float32(0.0)                                   # running denom
    acc = jnp.zeros((q.shape[-1],), jnp.float32)           # running numer

    for c0 in range(0, window, chunk):                     # static unroll
        k_blk = k_ref[0, 0, c0:c0 + chunk, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, c0:c0 + chunk, :].astype(jnp.float32)
        logits = (k_blk @ q) * scale                       # (chunk,)
        pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)[:, 0]
        logits = jnp.where(pos < valid, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits))
        # guard the all-masked chunk (exp(-inf - -inf)) case
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe)                       # (chunk,)
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * corr + jnp.sum(p)
        acc = acc * corr + p @ v_blk
        m = m_new

    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0, 0, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def swa_decode(q: jax.Array, k: jax.Array, v: jax.Array,
               length: jax.Array, *, chunk: int = 512,
               interpret: bool | None = None) -> jax.Array:
    """Flash decode attention over a sliding-window cache.

    Args:
      q: (B, H, Dh) new-token queries.
      k, v: (B, H, W, Dh) window cache (GQA already expanded or H == KV).
      length: (B,) valid entries per batch row.

    Returns (B, H, Dh) attention output, q.dtype.
    """
    b, h, dh = q.shape
    w = k.shape[2]
    chunk = min(chunk, w)
    assert w % chunk == 0, (w, chunk)
    kernel = functools.partial(_swa_decode_kernel, chunk=chunk, window=w)
    len2 = jnp.broadcast_to(length.reshape(b, 1), (b, 1)).astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, dh), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, w, dh), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, w, dh), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dh), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v, len2)
