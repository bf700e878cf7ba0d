"""Bytes a compiled step puts on the wire, counted from its optimized HLO.

The arithmetic is ``tests/test_hlo_comm_bytes.py::wire_bytes_of_hlo`` (ring
model, per participating chip), copied here so that no later PR can move
the yardstick, with two repairs: the group size is read from each
instruction's ``replica_groups`` and not passed in, and the ``-start`` half
of an async all-gather / collective-permute, whose result tuple repeats its
operands, is counted once.

    all-reduce          2 (N-1)/N x result bytes
    reduce-scatter        (N-1)   x result bytes   (the result is 1/N of the input)
    all-gather          (N-1)/N   x result bytes
    all-to-all          (N-1)/N   x result bytes
    collective-permute            result bytes

A count, exact for a given compiled program: it says what the program asks
of the interconnect, not how long that takes.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_WIRE_WEIGHT = {
    "all-reduce": lambda b, n: 2 * (n - 1) / n * b,
    "reduce-scatter": lambda b, n: (n - 1) * b,
    "all-gather": lambda b, n: (n - 1) / n * b,
    "all-to-all": lambda b, n: (n - 1) / n * b,
    "collective-permute": lambda b, n: b,
}
#: async halves whose result tuple is (operands..., results..., context...)
_OPERANDS_IN_RESULT = ("all-gather", "collective-permute")

# result-type tokens like f32[128,64] or u8[4096]; a layout suffix such as
# {0:T(1024)} has no "name[" in it and is skipped
_SHAPE = re.compile(r"(\w+)\[([0-9,]*)\]")
# ``%name = TYPE op(``: TYPE is one token or one parenthesised tuple, and
# the op name follows it directly, so a fusion that merely mentions a
# collective in its metadata does not match
_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?[%\w.-]+ = (\(.*?\)|\S+) ("
    + "|".join(_WIRE_WEIGHT) + r")(-start|-done)?\(")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([0-9,]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[([0-9,]+)\]<=")


def _shapes(type_str: str) -> list[tuple[int, bool]]:
    """(bytes, has dimensions) of every array in an HLO result type."""
    sizes = []
    for dtype, dims in _SHAPE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        sizes.append((numel * _DTYPE_BYTES[dtype], bool(dims)))
    return sizes


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_LIST.search(line)
    if m and m.group(1):
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA.search(line)
    if m:
        return int(m.group(1).split(",")[-1])
    return default


def collectives(hlo_text: str, n_devices: int) -> list[dict]:
    """Every collective instruction of ``hlo_text``: ``{"op", "bytes"
    (result payload), "group", "wire_bytes"}``.  ``n_devices`` is the group
    size of an instruction that names no replica groups (all devices)."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) == "-done":
            continue  # the -done half of an async pair: counted at -start
        type_str, op, half = m.groups()
        sizes = _shapes(type_str)
        if half == "-start" and op in _OPERANDS_IN_RESULT:
            arrays = [b for b, has_dims in sizes if has_dims]  # drop u32[] context
            payload = sum(arrays[len(arrays) // 2:])
        else:
            payload = sum(b for b, _ in sizes)
        group = _group_size(line, n_devices)
        out.append({"op": op, "bytes": payload, "group": group,
                    "wire_bytes": _WIRE_WEIGHT[op](payload, group)})
    return out


def wire_bytes(hlo_text: str, n_devices: int) -> float:
    """Ring-model bytes per chip per execution of the program."""
    return float(sum(c["wire_bytes"] for c in collectives(hlo_text, n_devices)))
