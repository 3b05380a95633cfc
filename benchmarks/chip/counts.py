"""Operations and bytes of the work a cell's step does, from shapes.

These are the benchmark's own counts: a later change to the program cannot
move them. ``contraction_flops`` counts the five V x h x h contractions the
pdADMM-G update family needs per layer (entry residual z - pW - b, r W^T and
g W in the p-step, p^T r and p g in the W-step). ``pallas_calls`` reads the
HBM bytes each Pallas call moves, or has an async copy move for it, from the
compiled program's layouts of its operands and result.
"""
from __future__ import annotations

import base64
import json
import math
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


# an array shape with its layout, e.g. f32[19968,128]{1,0:T(8,128)S(1)}
_ARRAY = re.compile(r"\b(" + "|".join(DTYPE_BYTES)
                    + r")\[([0-9,]*)\](?:\{([^}]*)\})?")
_TILE = re.compile(r"T\(([0-9,]+)\)")
_SPACE = re.compile(r"S\((\d+)\)")
_DEFINITION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_COPY_DONE = re.compile(r" copy-done\(%([^\s)]+)\)")
HBM_SPACE = 0            # the default memory space; S(1) is a core's VMEM


def _dims(text: str) -> list:
    return [int(d) for d in filter(None, text.split(","))]


def shape_bytes(text: str) -> int:
    """Sum of the logical bytes of every array shape written in an HLO
    fragment, e.g. ``f32[157952,1024]{1,0}`` -> 646,971,392, whatever the
    layout and memory space. No reader uses it (they count ``hbm_bytes``);
    it is kept for the harness test of shape sizes."""
    return sum(math.prod(_dims(dims)) * DTYPE_BYTES[dtype]
               for dtype, dims, _ in _ARRAY.findall(text))


def hbm_bytes(text: str) -> int:
    """Bytes of the array shapes of an HLO fragment that lie in HBM, each as
    its layout stores it: dimensions padded up to the layout's first tile
    (``T(8,128)`` pads the most minor dimension to 128 and the next to 8).
    An array whose layout names another memory space (``S(1)``, VMEM) moves
    no HBM bytes."""
    total = 0
    for dtype, dims, layout in _ARRAY.findall(text):
        space = _SPACE.search(layout)
        if space and int(space.group(1)) != HBM_SPACE:
            continue
        shape = _dims(dims)
        tile = _TILE.search(layout)
        if tile and shape:
            minor_to_major = _dims(layout.split(":")[0])
            for t, dim in zip(reversed(_dims(tile.group(1))), minor_to_major):
                shape[dim] = -(-shape[dim] // t) * t
        total += math.prod(shape) * DTYPE_BYTES[dtype]
    return total


def _prefetched(definition: str, defined: dict, charged: set) -> int:
    """HBM bytes the async copy read that brought an operand out of HBM:
    the source array of its ``copy-start``. Each copy counts once, for the
    first call that reads what it brought."""
    m = _COPY_DONE.search(definition)
    if not m or m.group(1) in charged:
        return 0
    charged.add(m.group(1))
    start = defined.get(m.group(1), "").split(" copy-start(", 1)[0]
    arrays = [a.group(0) for a in _ARRAY.finditer(start)]
    return hbm_bytes(arrays[1]) if len(arrays) > 1 else 0


def pallas_calls(hlo_text: str) -> dict:
    """Pallas calls of a compiled program: instruction name -> (kernel body
    name, HBM bytes the call reads and writes). The name is read from the
    Mosaic module each ``tpu_custom_call`` carries. The shapes are those of
    the compiled program, local to one device where it is partitioned, and
    padded as the dispatch pads them. The bytes are ``hbm_bytes`` of the
    result and of each operand as the instruction that defines it lays it
    out: the compiler may place an operand or the result in VMEM (``S(1)``),
    and such an array moves no HBM bytes in the call. Where an async
    ``copy-start``/``copy-done`` brought the operand there from HBM, the
    bytes that copy read count too, once, for the first call that reads
    them; an operand that another instruction wrote into VMEM is that
    instruction's traffic. An operand whose definition is not in the text
    is taken from ``operand_layout_constraints``, in HBM."""
    defined = {}
    for line in hlo_text.splitlines():
        m = _DEFINITION.match(line)
        if m:
            defined[m.group(1)] = m.group(2)
    out, charged = {}, set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.strip().split()[-1].lstrip("%")
        result, _, rest = rhs.partition(" custom-call(")
        operands = [o.split()[-1].lstrip("%") for o in rest.split(
            "), custom_call_target", 1)[0].split(", ") if o]
        m = re.search(r"operand_layout_constraints=\{(.*?\})\}", rhs)
        constraints = [a.group(0) for a in _ARRAY.finditer(m.group(1))] \
            if m else []
        nbytes = hbm_bytes(result)
        for i, op in enumerate(operands):
            definition = defined.get(op, "")
            shape = _ARRAY.match(definition)
            if shape:
                placed = hbm_bytes(shape.group(0))
                nbytes += placed or _prefetched(definition, defined, charged)
            elif i < len(constraints):
                # a fragment without the operand's definition, as a test
                # writes by hand: a compiled program defines every operand
                nbytes += hbm_bytes(constraints[i])
        m = re.search(r'"body":"([^"]*)"', rhs)
        body = base64.b64decode(m.group(1)) if m else b""
        k = re.search(rb"\.py\x00([A-Za-z_][\w.<>]*)\x00", body)
        kernel = k.group(1).decode() if k else "pallas_call"
        out[name] = (kernel, nbytes)
    return out
