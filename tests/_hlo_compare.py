"""Compiled HLO text less what only debuggers read, so that two compiles
of one program can be compared."""
from __future__ import annotations

import base64
import re

_TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def _mosaic_body(m) -> str:
    """A Mosaic kernel's serialized body, printed without its source
    locations (they name the calling file and function)."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    with jmlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(m.group(2)))
        return m.group(1) + module.operation.get_asm(enable_debug_info=False)


def without_debug_info(hlo: str) -> str:
    """``hlo`` less each op's ``metadata={...}``, the stack-frame tables
    and the source locations inside Mosaic kernel bodies."""
    out, skip = [], False
    for line in hlo.split("\n"):
        if line in _TABLES:
            skip = True
        elif skip:
            skip = line != ""
        else:
            line = re.sub(r", metadata=\{[^}]*\}", "", line)
            out.append(re.sub(r'("body":")([A-Za-z0-9+/=]+)', _mosaic_body,
                              line))
    return "\n".join(out)
