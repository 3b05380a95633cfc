"""Overlap battery: the `overlap` knob of `make_distributed_step` must (a)
do something — double-buffered boundary exchange, proven at the jaxpr level
by ppermutes leaving the critical path — and (b) change NOTHING about the
math: bitwise-identical state/metrics and identical ledger accounting vs the
paper-faithful ordering. Plus the kwarg-observability regression test that
would have caught the original silent no-op, and the exact ragged-shard wire
accounting. Multi-device cases run in subprocesses with forced CPU devices
(the main pytest process is locked to 1 device)."""
import subprocess
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.parallel import stage_parallel as SP
# the paper's 2-stage x 2-data differential mesh
mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
"""


def test_overlap_bitwise_differential():
    """overlap=True == overlap=False bitwise — state, metrics AND ledger
    (same bytes per iteration per edge: overlap changes when bytes move, not
    how many) — over 12 iterations on a 2x2 mesh, fp32/int8/int4 wires."""
    out = _run(PRELUDE + """
from repro.comm import CommLedger
from repro.comm.codecs import codec_for_grid
from repro.graph.datasets import tiny
ds = tiny(V=64)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 32)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
cases = [("fp32", ADMMConfig(nu=1e-2, rho=1.0))] + [
    (f"int{b}", ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True,
                           quantize_q=True,
                           grid=quantize.uniform_grid(b, -2.0, 6.0)))
    for b in (8, 4)]
for name, cfg in cases:
    led_a, led_b = CommLedger(), CommLedger()
    st_a, h_a = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 4,
                                     ds.n_classes, cfg, epochs=12,
                                     ledger=led_a)
    st_b, h_b = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 4,
                                     ds.n_classes, cfg, epochs=12,
                                     ledger=led_b, overlap=True)
    for f, a, b in zip(st_a._fields, st_a, st_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name}/{f}")
    assert h_a["objective"] == h_b["objective"], name
    assert h_a["residual"] == h_b["residual"], name
    assert len(h_a["objective"]) == 12
    # ledger: the CONSUMED per-iteration traffic is identical edge by edge,
    # iteration by iteration (overlap changes when bytes move, not how many
    # an iteration consumes) ...
    edges_b = led_b.per_edge()
    inflight = {e: v for e, v in edges_b.items() if e.endswith("/inflight")}
    consumed_b = {e: v for e, v in edges_b.items()
                  if not e.endswith("/inflight")}
    assert {k: v for k, v in led_b.per_iteration().items()
            if k < 12} == led_a.per_iteration(), name
    assert consumed_b == led_a.per_edge(), name
    # ... plus exactly the tail q/u pair still in flight in the carry at
    # termination, charged explicitly (it DID cross the link)
    pc = codec_for_grid(cfg.grid if cfg.quantize_p else None)
    qc = codec_for_grid(cfg.grid if cfg.quantize_q else None)
    wb = SP.wire_bytes_per_iteration(mesh, 4, Xp.shape[0], 32, pc, qc)
    assert inflight == {"q_fwd/inflight": wb["q_fwd"],
                        "u_fwd/inflight": wb["u_fwd"]}, (name, inflight)
    assert led_b.total_bytes() == led_a.total_bytes() + wb["q_fwd"] \
        + wb["u_fwd"], name
    # and training went somewhere (the differential is not vacuous)
    assert h_a["objective"][-1] < h_a["objective"][0], name
    print(name, "DIFF_OK")
print("OVERLAP_BITWISE_OK")
""")
    assert "OVERLAP_BITWISE_OK" in out


def test_overlap_single_step_bitwise_vs_fused():
    """One primed overlap step == one fused step, bitwise, starting from the
    same placed state (the split exchange halves compose exactly)."""
    out = _run(PRELUDE + """
from jax.sharding import NamedSharding, PartitionSpec as P
V, h, L, C = 64, 32, 4, 4
cfg = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True, quantize_q=True,
                 grid=quantize.uniform_grid(8, -2.0, 6.0))
key = jax.random.PRNGKey(1)
Xp = jax.random.normal(key, (V, h))
state = SP.init_stack(key, Xp, L, cfg)
specs = SP.stack_partition_specs(mesh)
put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
state = jax.tree.map(put, state, specs)
args = (put(Xp, P("data")), put(jnp.zeros((V,), jnp.int32), P("data")),
        put(jnp.ones((V,)), P("data")))
base, _ = SP.make_distributed_step(mesh, L, C, cfg)
ov, _ = SP.make_distributed_step(mesh, L, C, cfg, overlap=True)
from repro.comm.codecs import codec_for_grid
primer = SP.make_overlap_primer(mesh, codec_for_grid(cfg.grid))
carry = (state, primer(state.q, state.u))
for k in range(3):
    st_a, m_a = base(state, *args)
    carry, m_b = ov(carry, *args)
    state = st_a
    st_b = carry[0]
    for f, a, b in zip(st_a._fields, st_a, st_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"iter{k}/{f}")
    for kk in m_a:
        np.testing.assert_array_equal(np.asarray(m_a[kk]),
                                      np.asarray(m_b[kk]), err_msg=kk)
print("STEP_BITWISE_OK")
""")
    assert "STEP_BITWISE_OK" in out


def test_overlap_moves_ppermutes_off_critical_path():
    """Jaxpr-level proof that the knob does something, now stated once as
    the registered schedule contracts (repro.analysis.contracts): the
    `baseline` spec pins every ppermute consumed in-body on the critical
    path, the `overlap` spec pins the carried q/u pair and the p exchange
    hidden behind the W-solve. Here both specs must pass their schedule
    family cleanly AND the overlap plan must bite when the traced program
    regresses to the paper-faithful ordering (the original silent no-op)."""
    out = _run(PRELUDE + """
from repro.analysis import contracts as CT
for name in ("baseline", "overlap"):
    f = CT.check_contracts(name, families=["schedule"])
    assert not f, [(x.key, x.message) for x in f]
plan = CT.ProgramView(CT.get_spec("overlap")).plan
assert plan.n_carried == 2 and plan.min_work_to_consumer >= 2, plan
# regression bite: overlap silently off -> schedule contracts must fire
f = CT.check_contracts("overlap", overrides={"overlap": False},
                       families=["schedule"])
assert {x.key for x in f} >= {"schedule.carried",
                              "schedule.work_to_consumer"}, f
print("SCHEDULE_OK")
""")
    assert "SCHEDULE_OK" in out


def test_make_distributed_step_kwargs_observable():
    """Every documented kwarg of make_distributed_step must observably
    change the traced/lowered program — the regression test that would have
    caught the original ignored `overlap` flag, now stated once as the
    cache contract family (repro.analysis.contracts): cache.kwarg_set pins
    the kwarg-only surface to the registered cache-key set (a NEW kwarg
    fails it until it registers contracts), cache.kwarg_observable flips
    each pinned kwarg and requires a distinct trace fingerprint. The
    per-kwarg program shapes (carried pair, donor markers, wire dtypes,
    sentinel headers, xor injector) are each pinned by their own
    dispatch/schedule/wire/memory contracts over the registered specs."""
    out = _run(PRELUDE + """
from repro.analysis import contracts as CT
f = CT.check_contracts("baseline", families=["cache"])
assert not f, [(x.key, x.message) for x in f]
# bite check: a kwarg whose flip changes nothing must be rejected
f = CT.check_contracts("baseline", families=["cache"],
                       variants={"overlap": {}})
assert [x.key for x in f] == ["cache.kwarg_observable"], f
# and the kwarg surface itself is the pinned set
import inspect
kw = {n for n, p in
      inspect.signature(SP.make_distributed_step).parameters.items()
      if p.kind == inspect.Parameter.KEYWORD_ONLY}
assert kw == set(CT.PINNED_STEP_KWARGS), kw
print("KWARGS_OK")
""")
    assert "KWARGS_OK" in out


def test_distributed_train_controller_lazy_steps_and_overlap():
    """Controller path: steps compile lazily (cache holds exactly the
    schedules that ran — the eager schedule[0] pre-compile is gone) and
    overlap=True stays bitwise-identical, including across the re-primed
    schedule changes; dropped in-flight slabs are charged on the ledger."""
    out = _run(PRELUDE + """
from repro.comm import BitWidthController, CommLedger, ControllerConfig
from repro.graph.datasets import tiny
ds = tiny(V=64)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 32)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
V = Xp.shape[0]
grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (8, 16)}
mk_ctl = lambda: BitWidthController([2 * V * 32], ControllerConfig(
    allowed_bits=(8, 16), min_bits=8, max_bits=16, min_dwell=1,
    hysteresis=0.0, thresholds=((0.5, 8),)))
# unprojected optimization + quantized WIRE: with p and q on a shared grid
# the primal residual collapses to exactly 0 (no control signal), so the
# adaptive-wire case drives the controller off the live fp32 residual
cfg = ADMMConfig(nu=1e-2, rho=1.0)
led_a, led_b = CommLedger(), CommLedger()
st_a, h_a = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 4,
                                 ds.n_classes, cfg, epochs=14,
                                 controller=mk_ctl(), grids_by_bits=grids,
                                 ledger=led_a)
st_b, h_b = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 4,
                                 ds.n_classes, cfg, epochs=14,
                                 controller=mk_ctl(), grids_by_bits=grids,
                                 ledger=led_b, overlap=True)
assert h_a["schedules"] == h_b["schedules"]
assert h_a["objective"] == h_b["objective"]
assert h_a["residual"] == h_b["residual"]
for f, a, b in zip(st_a._fields, st_a, st_b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f)
# lazy build: exactly one compiled step per DISTINCT schedule that ran
assert h_a["n_compiled_steps"] == len(set(h_a["schedules"])), h_a
assert h_b["n_compiled_steps"] == len(set(h_b["schedules"])), h_b
assert len(set(h_a["schedules"])) >= 2, h_a["schedules"]  # it DID adapt
# unconsumed-slab accounting (overlap ledger only): one q+u dropped pair
# per schedule CHANGE after the first, plus the in-flight tail pair the
# finished run leaves in its carry
n_changes = sum(1 for x, y in zip(h_a["schedules"], h_a["schedules"][1:])
                if x != y)
extra = {e: b for e, b in led_b.per_edge().items() if "/" in e}
expect = {"q_fwd/inflight", "u_fwd/inflight"}
if n_changes:
    expect |= {"q_fwd/dropped", "u_fwd/dropped"}
assert set(extra) == expect, (extra, n_changes)
assert not any("/" in e for e in led_a.per_edge())
consumed = {e: b for e, b in led_b.per_edge().items() if "/" not in e}
assert consumed == led_a.per_edge()
print("CTL_LAZY_OK")
""")
    assert "CTL_LAZY_OK" in out


# --- exact ragged-shard wire accounting (pure functions, no devices) --------


def _fake_mesh(**shape):
    return types.SimpleNamespace(shape=shape)


def test_shard_rows_partitions_exactly():
    from repro.parallel.stage_parallel import shard_rows
    for V in (1, 7, 64, 2485, 2708, 3327):
        for n in (1, 2, 3, 4, 8):
            rows = shard_rows(V, n)
            assert len(rows) == n
            assert sum(rows) == V, (V, n, rows)
            c = -(-V // n)
            assert all(r <= c for r in rows)


@pytest.mark.parametrize("V", [256, 2485, 2708, 3327])
@pytest.mark.parametrize("mesh_shape", [
    {"data": 1, "model": 4}, {"data": 2, "model": 4},
    {"data": 4, "model": 2}, {"pod": 2, "data": 2, "model": 2},
    {"data": 3, "model": 4},
])
def test_wire_bytes_matches_per_shard_payload_bytes(V, mesh_shape):
    """The ledger model == sum of codec.payload_bytes over the ACTUAL
    per-shard boundary slabs, for ragged real-graph V on every mesh shape —
    the remainder rows the old `V // dp_total` formula silently dropped."""
    from repro.comm.codecs import FP32, GridCodec
    from repro.core.quantize import uniform_grid
    from repro.parallel.stage_parallel import (shard_rows,
                                               wire_bytes_per_iteration)
    mesh = _fake_mesh(**mesh_shape)
    h, L = 64, 8
    n_stages = mesh_shape["model"]
    dp_total = mesh_shape.get("pod", 1) * mesh_shape.get("data", 1)
    p_codec = GridCodec(uniform_grid(8, 0.0, 1.0))
    q_codec = GridCodec(uniform_grid(4, 0.0, 1.0))
    wb = wire_bytes_per_iteration(mesh, L, V, h, p_codec, q_codec)
    rows = shard_rows(V, dp_total)
    for key, codec in (("q_fwd", q_codec), ("u_fwd", FP32),
                       ("p_bwd", p_codec)):
        exact = n_stages * sum(codec.payload_bytes((1, r, h)) for r in rows)
        assert wb[key] == exact, (key, wb[key], exact)
    # no dropped rows: elements cover every node exactly once per stage ring
    assert wb["elements_per_edge"] == n_stages * V * h
    assert sum(wb["shard_rows"]) == V
    # regression: the ragged cases must NOT match the old floor formula
    if V % dp_total:
        old = n_stages * dp_total * FP32.payload_bytes(
            (1, V // dp_total, h))
        assert wb["u_fwd"] > old


def test_wire_bytes_divisible_matches_closed_form():
    """On evenly divisible V the exact accounting reduces to the old
    closed form (links * per-slab bytes)."""
    from repro.comm.codecs import FP32, GridCodec
    from repro.core.quantize import uniform_grid
    from repro.parallel.stage_parallel import wire_bytes_per_iteration
    mesh = _fake_mesh(data=2, model=4)
    V, h, L = 256, 64, 8
    g8 = GridCodec(uniform_grid(8, 0.0, 1.0))
    wb = wire_bytes_per_iteration(mesh, L, V, h, g8, g8)
    links = 4 * 2
    assert wb["q_fwd"] == links * g8.payload_bytes((1, V // 2, h))
    assert wb["u_fwd"] == links * FP32.payload_bytes((1, V // 2, h))
    assert wb["p_bwd"] == wb["q_fwd"]
    assert wb["links"] == links
