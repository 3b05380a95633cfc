"""Which solve of the ADMM iteration each instruction of the compiled chunk
program belongs to.

The step program runs each phase of an iteration under a
``jax.named_scope`` named ``admm.<solve>``, and the compiler writes the
scope path into the ``op_name`` metadata of the HLO instructions the phase
lowers to; a fusion carries its root's (``jit(_scan_chunk)/while/body/
closed_call/admm.p/vmap()/dot_general``). ``scope_map`` reads a compiled
program's text line by line, as ``counts.pallas_calls`` does, and maps each
instruction to the innermost ``admm.*`` component of its ``op_name`` that
names one of ``SOLVES``.

The reader context holds no program text, so ``solve_ms`` takes it from
the programs the runtime holds compiled (``live_executables``): of those
with scopes, the one whose scoped instructions cover the most device time
in the trace, which is the chunk program the window ran. The benchmark
owns these names: a program that has none maps nothing, and its readers
report nothing.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional

SOLVES = ("exchange", "residual", "p", "W", "b", "z", "z_last", "q", "u",
          "metrics")
UNSCOPED = "unscoped"        # ops of no solve: scan bookkeeping, copies

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|[/(])admm\.(\w+)")


@functools.lru_cache(maxsize=8)
def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the solve it belongs to, for every instruction
    whose ``op_name`` holds an ``admm.<solve>`` scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        op = _OP_NAME.search(line) if m else None
        if op is None:
            continue
        solves = [s for s in _SCOPE.findall(op.group(1)) if s in SOLVES]
        if solves:
            out[m.group(1)] = solves[-1]
    return out


def program_texts() -> List[str]:
    """The HLO text of every program the runtime holds compiled."""
    import jax

    return [m.to_string()
            for ex in jax.devices()[0].client.live_executables()
            for m in ex.hlo_modules()]


def traced_scopes(ops) -> Dict[str, str]:
    """``scope_map`` of the held program whose scoped instructions take the
    most of ``ops``' device time; empty when no held program has scopes."""
    best, most = {}, 0.0
    for text in program_texts():
        scopes = scope_map(text)
        covered = sum(o.end - o.start for o in ops if o.name in scopes)
        if covered > most:
            best, most = scopes, covered
    return best


def solve_ms(ctx, solve: str) -> Optional[float]:
    """Device time of ``solve``'s ops (``UNSCOPED``: of the ops of no
    solve) in the traced window, in ms per iteration; the largest over the
    chips, since the slowest stage sets the pace. None when the program
    names no solve or the trace holds no device op."""
    ops = [o for d in ctx.trace.devices.values() for o in d]
    if not ops or ctx.iterations <= 0:
        return None
    scopes = traced_scopes(ops)
    if not scopes:
        return None
    per_chip = [sum(o.end - o.start for o in d
                    if scopes.get(o.name, UNSCOPED) == solve)
                for d in ctx.trace.devices.values()]
    return max(per_chip) * 1e-6 / ctx.iterations
