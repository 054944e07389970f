"""Which way a Pallas kernel runs: compiled on the chip, interpreted on CPU.

Every kernel wrapper in this package takes ``interpret=None`` and resolves
it here from the backend the program runs on.  There is no fallback: a
platform the kernels were not written for is an error, never a silent
switch to the interpreter.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else the mode of the backend JAX runs on.

    ``cpu`` -> interpret (the parity mode the CPU tests run);
    ``tpu`` -> compiled Mosaic kernels; anything else raises.
    """
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(f"Pallas kernels run compiled on tpu or interpreted on "
                     f"cpu; the backend here is {platform!r}")
