"""The program's tracing: every ADMM solve of the step runs under a named
scope that reaches the compiled program's ``op_name`` metadata, in every
variant of the step, and ``run_chunked`` opens a dispatch and a fetch span
per chunk for the profiler. The scope names are the chip benchmark's
(``benchmarks/chip/scopes.py``), read with its own parser."""
from __future__ import annotations

import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip"))

import scopes  # noqa: E402
from repro.comm import faults as F  # noqa: E402
from repro.comm.codecs import codec_for_grid  # noqa: E402
from repro.comm.transport import PaddedWire  # noqa: E402
from repro.core import pdadmm, quantize  # noqa: E402
from repro.core.pdadmm import ADMMConfig  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.parallel import stage_parallel as SP  # noqa: E402

V, H, L, C = 128, 32, 4, 3


def _variant(kind: str):
    """(step, its arguments) of one variant of the (1, 1) step at V=128."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    key = jax.random.PRNGKey(0)
    Xp = jax.random.normal(key, (V, H))
    st = SP.init_stack(key, Xp, L, cfg)
    data = (Xp, jnp.zeros((V,), jnp.int32), jnp.ones((V,)))
    if kind == "plain":
        step, _ = SP.make_distributed_step(mesh, L, C, cfg)
        return step, (st,) + data
    if kind == "overlap":
        step, _ = SP.make_distributed_step(mesh, L, C, cfg, overlap=True)
        fly = SP.make_overlap_primer(mesh, codec_for_grid(None))(st.q, st.u)
        return step, ((st, fly),) + data
    if kind == "wire":
        grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
        step, _ = SP.make_distributed_step(
            mesh, L, C, cfg, wire=PaddedWire.from_grids(grids))
        return step, (st,) + data + (jnp.zeros((2, 1), jnp.int32),)
    step, _ = SP.make_distributed_step(mesh, L, C, cfg, health=True)
    good = SP.make_sentinel_primer(mesh)(st.q, st.u, st.p)
    return step, ((st, good),) + data + (F.null_controls(1),)


@pytest.mark.parametrize("kind", ["plain", "overlap", "wire", "sentinel"])
def test_every_solve_scope_reaches_the_compiled_op_names(kind):
    step, args = _variant(kind)
    hlo = step.lower(*args).compile().as_text()
    found = set(scopes.scope_map(hlo).values())
    assert found == set(scopes.SOLVES), sorted(set(scopes.SOLVES) - found)


def test_run_chunked_spans_each_chunks_dispatch_and_fetch(tmp_path):
    from jax.profiler import ProfileData

    def step(s, x):
        return s * 0.5 + x, {"m": jnp.sum(s)}

    s, x = jnp.ones((4,)), jnp.ones((4,))
    pdadmm.run_chunked(step, s, (x,), 2, chunk=1)      # compiled outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller.run"):
            _, ms = pdadmm.run_chunked(step, s, (x,), 2, chunk=1)
    finally:
        jax.profiler.stop_trace()
    assert ms["m"].shape == (2,)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    spans = sorted(
        (e.start_ns, e.end_ns, e.name)
        for p in ProfileData.from_file(path[-1]).planes
        if p.name.startswith("/host:") for line in p.lines
        for e in line.events
        if e.name.startswith(("pdadmm.", "caller.")))
    (c0, c1, _), program = spans[0], spans[1:]
    assert spans[0][2] == "caller.run"
    assert [n for _, _, n in program] == [
        "pdadmm.chunk.dispatch", "pdadmm.chunk.fetch"] * 2
    assert all(c0 <= s0 <= s1 <= c1 for s0, s1, _ in program)
    assert all(a[1] <= b[0] for a, b in zip(program, program[1:]))


def test_compile_seconds_counts_nested_compile_spans_once(monkeypatch):
    from repro.launch import compile_cache as cc

    monkeypatch.setattr(cc, "_listening", False)
    assert cc.compile_seconds() is None
    trace, lower, build = cc.COMPILE_EVENTS
    monkeypatch.setattr(cc, "_listening", True)
    monkeypatch.setattr(cc, "_spans", [])
    for event, a, b in [(trace, 0.0, 2.0),
                        (trace, 0.5, 1.5),    # a jit traced inside another
                        (lower, 1.8, 3.0), (build, 4.0, 4.5),
                        ("/jax/other", 10.0, 20.0)]:
        cc._on_span(event, a, b)
    assert cc.compile_seconds() == pytest.approx(3.5)
