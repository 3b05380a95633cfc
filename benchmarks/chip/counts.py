"""Operations and bytes of the work a cell's step does, from shapes.

These are the benchmark's own counts: a later change to the program cannot
move them. ``contraction_flops`` counts the five V x h x h contractions the
pdADMM-G update family needs per layer (entry residual z - pW - b, r W^T and
g W in the p-step, p^T r and p g in the W-step). ``pallas_calls`` reads the
bytes each Pallas call moves from the operand and result shapes of its
instruction in the compiled program, padded shapes included.
"""
from __future__ import annotations

import base64
import json
import re
from pathlib import Path

CONTRACTIONS_PER_LAYER = 5

DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
               "u8": 1, "pred": 1, "s16": 2, "u16": 2, "f64": 8, "s64": 8,
               "u64": 8}

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def contraction_flops(nodes: int, hidden: int, layers: int) -> float:
    """FLOPs of one ADMM iteration's contractions over all layers:
    5 per layer, 2 V h^2 each."""
    return float(CONTRACTIONS_PER_LAYER * layers * 2 * nodes * hidden * hidden)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of this kind; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(table)}")
    return table[device_kind]


_SHAPE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")


def shape_bytes(text: str) -> int:
    """Sum of the bytes of every array shape written in an HLO fragment,
    e.g. ``f32[157952,1024]{1,0}`` -> 646,971,392."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def pallas_calls(hlo_text: str) -> dict:
    """Pallas calls of a compiled program: instruction name -> (kernel body
    name, bytes the call reads and writes). The name is read from the Mosaic
    module each ``tpu_custom_call`` carries; the bytes from the result shape
    and the operand shapes (``operand_layout_constraints``), which are the
    padded shapes the kernel sees."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip().split()[-1].lstrip("%")
        result = rhs.split(" custom-call(", 1)[0]
        m = re.search(r"operand_layout_constraints=\{(.*?\})\}", rhs)
        operands = m.group(1) if m else ""
        m = re.search(r'"body":"([^"]*)"', rhs)
        body = base64.b64decode(m.group(1)) if m else b""
        k = re.search(rb"\.py\x00([A-Za-z_][\w.<>]*)\x00", body)
        kernel = k.group(1).decode() if k else "pallas_call"
        out[name] = (kernel, shape_bytes(result) + shape_bytes(operands))
    return out
