"""Model families: what the benchmark knows of one architecture.

A configuration file names its family (``"family": "dense"``), and the
harness finds ``families/<family>.py`` by that name.  A family module
exports:

  * ``arch_kwargs(config) -> dict``: the program's ``ArchConfig`` keywords,
    ``arch_type`` included (the file's ``program`` dict goes on top);
  * ``matmul_params(config)`` and ``model_flops_per_token(config, seq)``:
    PaLM's count (arXiv:2204.02311, app. B), N the weights an activation
    multiplies per token (the active ones, where experts are routed);
  * ``row_axis(path, ndim) -> int | None``: the axis along which the
    sparse uplink selects per row in the leaf at the ``/``-joined ``path``
    (the program's model-sharded axis); ``None``: the leaf is one row;
  * ``loss_fn(pf, tokens, labels, config, precision)``: the plain float32
    forward and mean next-token loss, every matmul through :func:`mm` or
    :func:`ein` so that ``precision="fp8"`` makes the control;
  * ``TINY``: widths at which the CPU tests run the family.

The helpers here are what every family's reference shares: float32 at
``highest`` matmul precision, and the float8 operands of the control.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def load(config: dict):
    """The family module a configuration names."""
    name = config["family"]
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"configuration {config.get('name')!r} names the model family "
            f"{name!r}, but there is no file chipbench/families/{name}.py",
            name=module) from None


def q(x, precision: str):
    """``x`` as a matmul operand of the given precision."""
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(a, b, precision: str):
    return jnp.matmul(q(a, precision), q(b, precision), precision=HIGHEST)


def ein(spec: str, a, b, precision: str):
    return jnp.einsum(spec, q(a, precision), q(b, precision),
                      precision=HIGHEST)
