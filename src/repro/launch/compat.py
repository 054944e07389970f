"""Process setup and thin sharding helpers over the installed jax (0.9).

* :func:`enable_compile_cache` -- where JAX keeps compiled programs; the
  entry points (``chip_smoke.py``, the examples, ``benchmarks/run.py``)
  call it, the library never does on import.
* :func:`force_host_device_count` -- a CPU host mesh of ``n`` virtual
  devices, for CPU studies and tests.
* :func:`make_mesh` / :func:`shardings` / :func:`shard_map` -- the
  explicit-sharding surface the launch stack uses.

The sharded FL engine (:class:`repro.core.fl_batched.ShardedEngine`) uses
the fully-manual :func:`shard_map` path (``axis_names=None``); the LGC
train step is manual over the FL axis and every size-1 axis.
"""
from __future__ import annotations

import os
import pathlib
from typing import Sequence

import jax

#: compile-cache home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path, because the path is part of the cache's key
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives at ``<repo>/.jax_cache`` (git-ignored).  Call it once, before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_device_count(n: int) -> None:
    """Make the CPU backend expose ``n`` virtual devices (a host mesh).

    Rewrites ``XLA_FLAGS`` with ``--xla_force_host_platform_device_count=n``;
    any pre-existing occurrence of the flag is dropped first, because XLA
    honours the LAST occurrence -- naively prepending would let an inherited
    environment value (e.g. the test-sharded CI lane's =8) silently win.
    Must run before the first jax backend initialisation in the process.
    """
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count=")]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n}"])


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shardings(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree for jit in/out_shardings."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` manual over ``axis_names`` only (auto elsewhere).

    Replica/vma checking is disabled (the LGC step's gather patterns trip
    it).
    """
    kwargs = {"check_vma": False}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
