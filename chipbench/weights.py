"""Seeded weights for a parameter tree, made on the device in one call.

Every leaf is drawn from its own key, ``fold_in(key(seed), leaf index)``,
so a leaf can be made again alone: :func:`norms_from_init` measures how far
a trained tree has moved from its initial weights one leaf at a time,
without a second copy of the whole tree.  Matrices and the embedding are
N(0, initializer_range) as the published configs state; norm scales are
1 + N(0, initializer_range) and biases N(0, initializer_range), so that a
dropped scale or bias changes the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _names(path) -> tuple[str, ...]:
    return tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path)


def leaf_names(tree) -> list[str]:
    """'/'-joined key path of every leaf, in tree-leaf order."""
    return ["/".join(_names(p))
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaf(key, index: int, name: str, shape, dtype, sigma: float):
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    x = x * sigma
    if name.endswith("scale"):
        x = x + 1.0
    return x.astype(dtype)


def seed_key(seed: int):
    return jax.random.key(seed % 2**32)


def make(shapes, seed: int, sigma: float, shardings=None):
    """A tree shaped like ``shapes`` (ShapeDtypeStructs), on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [("/".join(_names(p)), s.shape, s.dtype) for p, s in flat]

    def build(key):
        leaves = [_leaf(key, i, n, shape, dt, sigma)
                  for i, (n, shape, dt) in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _norms_fn(specs: tuple, sigma: float):
    @jax.jit
    def norms(key, leaves, plus):
        out = []
        for i, ((n, shape, dt), leaf) in enumerate(zip(specs, leaves)):
            d = (_leaf(key, i, n, shape, dt, sigma).astype(jnp.float32)
                 - leaf.astype(jnp.float32))
            if plus is not None:      # (n_fl, *shape): add the mean row
                d = d + jnp.mean(plus[i].astype(jnp.float32), axis=0)
            out.append(jnp.linalg.norm(d.reshape(-1)))
        return jnp.stack(out)
    return norms


def norms_from_init(tree, seed: int, sigma: float, plus=None):
    """Per-leaf L2 norm of (initial weights - ``tree``), float32 (n_leaves,);
    with ``plus`` (a tree of stacked ``(n_fl, *leaf)`` rows) the mean row is
    added to each difference first."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    specs = tuple(("/".join(_names(p)), tuple(x.shape), jnp.dtype(x.dtype))
                  for p, x in flat)
    rows = None if plus is None else jax.tree_util.tree_leaves(plus)
    return _norms_fn(specs, sigma)(seed_key(seed), [x for _, x in flat],
                                   rows)


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norm, float32 (n_leaves,)."""
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).reshape(-1))
                      for x in jax.tree_util.tree_leaves(tree)])
