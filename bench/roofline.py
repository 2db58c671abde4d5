"""Operations and bytes of the program's kernels, from their shapes, and
the chip peaks they are held against (peaks.json, keyed by device_kind).

Both kernels measured here are memory-bound by their least work: hashing
one COO entry to one bit, or scoring one packed row against a query, is
trivial next to moving the bytes, so the least time is the bytes over the
HBM bandwidth.  No operation term is counted: an MXU or VPU count would
credit the kernels' own way of doing the work (the sketch kernel's d-wide
compare-reduce per entry), which is what a faster kernel would remove.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device is not in the peaks table: no roofline can be taken."""


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _DTYPE_BYTES[dtype]
    return total


def custom_call_bytes(op_text: str) -> int:
    """Bytes a custom call (a Pallas kernel) moves at least, read from its
    HLO text as the device trace names it: every operand read once and
    every output written once.

        %k = s32[8192,128]{...} custom-call(s32[8192,1024]{...} %a, ...), ...
    """
    lhs, _, rhs = re.sub(r"\{[^{}]*\}", "", op_text).partition(
        " custom-call(")
    if not rhs:
        raise ValueError(f"not a custom call: {op_text[:120]!r}")
    outputs = lhs.partition(" = ")[2]
    operands = rhs.partition("), ")[0]
    return _shape_bytes(outputs) + _shape_bytes(operands)


def least_seconds(n_bytes: float, device_kind: str) -> float:
    return n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
