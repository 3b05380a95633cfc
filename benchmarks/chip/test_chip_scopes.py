"""CPU tests of the readers of the program's solve scopes and compile count:
``scopes.py`` on a hand-written HLO fragment and on a program the runtime
holds, the ``solve_ms.*`` readers on a hand-made trace, and
``setup_compile_s``. Nothing here needs a chip."""
from __future__ import annotations

import collections
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import run  # noqa: E402
import scopes  # noqa: E402
import trace_reduce as tr  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

SCOPED_HLO = """\
HloModule jit__scan_chunk, is_scheduled=true
%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(_scan_chunk)/while/body/closed_call/admm.q/sin"}
}
%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_scan_chunk)/while/body/closed_call/admm.p/vmap()/dot_general" source_file="stage_parallel.py" source_line=320}
  %_fista_zlast.3 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_scan_chunk)/while/body/closed_call/jit(<lambda>)/admm.z_last/jit(_fista_zlast)/pallas_call"}
  %pad.5 = f32[16]{0} pad(%fusion.1, %c), padding=0_8, metadata={op_name="jit(_scan_chunk)/while/body/admm.z/jvp(admm.z_last)/pad"}
  %copy.4 = f32[8]{0} copy(%fusion.1)
  %fusion.6 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_scan_chunk)/while/body/admm.unknown/add"}
  %collective-permute-done.7 = f32[8]{0} collective-permute-done(%s), metadata={op_name="jit(_scan_chunk)/while/body/closed_call/admm.exchange/ppermute"}
  ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.6), metadata={op_name="jit(_scan_chunk)/while/body/closed_call/admm.metrics/psum"}
}
"""
BARE_HLO = re.sub(r"admm\.\w+", "x", SCOPED_HLO)
NAMES = [f"solve_ms.{s}" for s in scopes.SOLVES + (scopes.UNSCOPED,)]
MS = 1e6                                               # ns


def _ops(*triples):
    return [tr.Op(n, float(s), float(e)) for n, s, e in triples]


def _ctx(devices, iterations=2):
    Ctx = collections.namedtuple("Ctx", "trace iterations")
    return Ctx(tr.Trace(devices, [], (0.0, 1e9)), iterations)


def _read(metric, ctx):
    return run.Cell("pubmed-L8.fp32").metric_reader(metric).read(ctx)


def test_scope_map_takes_the_innermost_known_solve():
    assert scopes.scope_map(SCOPED_HLO) == {
        "sine.1": "q", "fusion.1": "p", "_fista_zlast.3": "z_last",
        "pad.5": "z_last", "collective-permute-done.7": "exchange",
        "tuple.9": "metrics"}
    assert scopes.scope_map(BARE_HLO) == {}


def test_solve_readers_split_the_busy_time_by_scope(monkeypatch):
    monkeypatch.setattr(scopes, "program_texts",
                        lambda: [BARE_HLO, SCOPED_HLO])
    d0 = _ops(("fusion.1", 0, 2 * MS), ("_fista_zlast.3", 2 * MS, 6 * MS),
              ("pad.5", 6 * MS, 7 * MS), ("copy.4", 7 * MS, 8 * MS),
              ("fusion.6", 8 * MS, 9 * MS),
              ("collective-permute-done.7", 9 * MS, 9.5 * MS),
              ("tuple.9", 9.5 * MS, 10 * MS), ("while.3", 10 * MS, 11 * MS))
    d1 = _ops(("fusion.1", 0, 6 * MS))                 # a slower p stage
    got = {n: _read(n, _ctx({0: d0})) for n in NAMES}
    want = {"p": 1.0, "z_last": 2.5, "exchange": 0.25, "metrics": 0.25,
            "unscoped": 1.5}          # copy.4, fusion.6, while.3 over 2
    for n, v in got.items():
        assert v == pytest.approx(want.get(n.split(".", 1)[1], 0.0)), n
    # the eleven shares sum to the busy time per iteration
    assert sum(got.values()) == pytest.approx(tr.busy_ns(d0) * 1e-6 / 2)
    two = _ctx({0: d0, 1: d1})
    assert _read("solve_ms.p", two) == pytest.approx(3.0)   # largest chip
    assert _read("solve_ms.z_last", two) == pytest.approx(2.5)
    for n in NAMES:                   # an empty trace reports nothing
        assert _read(n, _ctx({0: []})) is None, n


def test_solve_readers_report_nothing_for_a_program_without_scopes(
        monkeypatch):
    monkeypatch.setattr(scopes, "program_texts", lambda: [BARE_HLO])
    d0 = _ops(("fusion.1", 0, 2 * MS), ("copy.4", 2 * MS, 3 * MS))
    for n in NAMES:
        assert _read(n, _ctx({0: d0})) is None, n


def test_traced_scopes_finds_the_held_program_that_ran():
    @jax.jit
    def f(x):
        with jax.named_scope("admm.p"):
            y = jnp.cosh(x) @ x
        with jax.named_scope("admm.z_last"):
            return jnp.arctan(y) + 3.0

    f(jnp.ones((8, 8))).block_until_ready()
    held = [t for t in scopes.program_texts() if "jit_f" in t.split("\n")[0]
            and scopes.scope_map(t)]
    assert held, "the compiled program is not among those the runtime holds"
    mine = scopes.scope_map(held[-1])
    assert {"p", "z_last"} <= set(mine.values())
    ops = _ops(*((name, 2 * i, 2 * i + 1) for i, name in enumerate(mine)))
    assert scopes.traced_scopes(ops) == mine
    assert scopes.traced_scopes(_ops(("no-such-op.1", 0, 1))) == {}


def test_setup_compile_s_reads_the_programs_compile_count(monkeypatch):
    compile_cache.listen_for_compiles()
    before = compile_cache.compile_seconds()
    t0 = time.time()
    jax.jit(lambda x: jnp.tanh(x) * 7.0 - 2.0)(
        np.ones(11, np.float32)).block_until_ready()
    wall = time.time() - t0
    got = _read("setup_compile_s", _ctx({0: []}))
    assert got == pytest.approx(compile_cache.compile_seconds())
    assert 0 < got - before <= wall
    monkeypatch.delattr(compile_cache, "compile_seconds")
    assert _read("setup_compile_s", _ctx({0: []})) is None
