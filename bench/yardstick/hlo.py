"""Bytes a compiled module ships across racks by collective-permute.

Storage racks are chips on the ``(pod, node)`` mesh, so a permute whose
source and target devices lie in different pods crosses a rack.  Each
such instruction moves its per-device result once.  On the TPU the async
``collective-permute-start`` returns ``(operand, result, contexts...)``:
its second shape is the buffer moved.
"""
from __future__ import annotations

import re

_BYTES = {"u8": 1, "s8": 1, "pred": 1, "u16": 2, "s16": 2, "bf16": 2, "f16": 2,
          "u32": 4, "s32": 4, "f32": 4, "u64": 8, "s64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([0-9,]*)\]")
_PERMUTE = re.compile(r"=\s+(.+?)\s+collective-permute(?:-start)?\(")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_PAIR = re.compile(r"\{(\d+),(\d+)\}")


def _nbytes(dtype: str, dims: str) -> int:
    n = _BYTES[dtype]
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def cross_pod_bytes(hlo_text: str, nodes_per_pod: int) -> int:
    """Sum of the result bytes of every permute that crosses a pod."""
    total = 0
    for line in hlo_text.splitlines():
        m = _PERMUTE.search(line)
        pairs = _PAIRS.search(line)
        if m is None or pairs is None:
            continue
        shapes = _SHAPE.findall(m.group(1))
        moved = shapes[1] if m.group(1).startswith("(") else shapes[0]
        if any(int(s) // nodes_per_pod != int(d) // nodes_per_pod
               for s, d in _PAIR.findall(pairs.group(1))):
            total += _nbytes(*moved)
    return total
