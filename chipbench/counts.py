"""What the yardstick counts from shapes: model FLOPs per token, the
compression layer's least bytes, and the chip's peaks.

Model FLOPs follow PaLM (arXiv:2204.02311) appendix B: ``6 N + 12 L H Q T``
per trained token, N the parameters that multiply an activation (the input
embedding excluded, the LM head included, a tied head counted once as the
head), L layers, H query heads of width Q, T the sequence length.
Recomputed (rematerialised) work is not counted.  The configuration's
model family (``families/``) makes the count.
"""
from __future__ import annotations

import json
import pathlib

from chipbench import families

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

#: the compression kernels' least traffic per element routed to them: read
#: the error memory and the net progress, write the sent part and the new
#: error memory, float32 each
COMPRESS_BYTES_PER_ELEMENT = 16


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def padded_vocab(vocab: int) -> int:
    """The program pads the vocabulary to a multiple of 256 rows."""
    return -(-vocab // 256) * 256


def matmul_params(config: dict) -> int:
    """N of PaLM's count, as the configuration's model family counts it."""
    return families.load(config).matmul_params(config)


def model_flops_per_token(config: dict, seq: int) -> int:
    return families.load(config).model_flops_per_token(config, seq)


def compress_bytes(leaf_sizes, min_elems: int) -> int:
    """Least bytes the compression kernels move per FL device per round:
    the leaves of at least ``min_elems`` elements go through them."""
    return COMPRESS_BYTES_PER_ELEMENT * sum(n for n in leaf_sizes
                                            if n >= min_elems)
