"""Shapes of one HLO instruction, from its text as the trace names a device
op: ``%name = <result shape(s)> custom-call(<operand shapes> ...), ...``."""
from __future__ import annotations

import re
from typing import List, Tuple

_ARRAY = re.compile(r"\b(pred|[subf]\d+|bf16)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}

Array = Tuple[str, Tuple[int, ...]]


def _arrays(text: str) -> List[Array]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARRAY.findall(text)]


def shapes(text: str) -> Tuple[List[Array], List[Array]]:
    """(results, operands) of an instruction's text."""
    _, _, rest = text.partition(" = ")
    op = rest.find("custom-call(")
    start = op + len("custom-call")
    depth, end = 0, len(rest)
    for i in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            end = i
            break
    return _arrays(rest[:op]), _arrays(rest[start:end + 1])


def nbytes(arrays: List[Array]) -> int:
    total = 0
    for dt, dims in arrays:
        n = 1
        for d in dims:
            n *= d
        total += n * _BYTES[dt]
    return total
