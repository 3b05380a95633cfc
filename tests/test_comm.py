"""Comm-runtime tests: codec round-trips + exact byte accounting, the
residual-driven bit-width controller (bounds, budget, hysteresis), the
CommLedger, error-feedback unbiasedness, and the distributed transport
(subprocess with forced multi-device CPU, like test_distributed)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommLedger
from repro.comm.codecs import (FP32, AffineCodec, Fp32Codec, GridCodec,
                               codec_for_bits, codec_for_grid,
                               encode_with_error_feedback)
from repro.comm.controller import BitWidthController, ControllerConfig
from repro.comm.ledger import record_admm_iteration
from repro.core.quantize import uniform_grid

ROOT = Path(__file__).resolve().parents[1]


# --- codecs ----------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8, 16])
def test_grid_codec_roundtrip_error_bound(bits):
    grid = uniform_grid(bits, -2.0, 6.0)
    codec = GridCodec(grid)
    x = jax.random.uniform(jax.random.PRNGKey(0), (37, 5), jnp.float32,
                           -2.0, 6.0)
    payload = codec.encode(x)
    dec = codec.decode(payload, shape=x.shape)
    assert dec.shape == x.shape
    assert float(jnp.max(jnp.abs(dec - x))) <= grid.step / 2 + 1e-6


@pytest.mark.parametrize("bits", [4, 8, 16])
def test_affine_codec_roundtrip_error_bound(bits):
    codec = AffineCodec(bits)
    x = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 3.0
    payload = codec.encode(x)
    dec = codec.decode(payload, shape=x.shape)
    step = (float(jnp.max(x)) - float(jnp.min(x))) / (2 ** bits - 1)
    assert float(jnp.max(jnp.abs(dec - x))) <= step * 0.51 + 1e-6


def test_fp32_codec_is_exact():
    x = jax.random.normal(jax.random.PRNGKey(2), (13, 7))
    payload = FP32.encode(x)
    np.testing.assert_array_equal(np.asarray(FP32.decode(payload)),
                                  np.asarray(x))


def test_payload_bytes_exact():
    # fp32: 4 B/elem, no header
    assert Fp32Codec().payload_bytes((10, 3)) == 120
    # grid16: 2 B/elem, no header
    assert GridCodec(uniform_grid(16, 0, 1)).payload_bytes((10, 3)) == 60
    # grid8: 1 B/elem
    assert GridCodec(uniform_grid(8, 0, 1)).payload_bytes((10, 3)) == 30
    # grid4: nibble-packed, odd element count rounds up
    assert GridCodec(uniform_grid(4, 0, 1)).payload_bytes((7,)) == 4
    assert GridCodec(uniform_grid(4, 0, 1)).payload_bytes((10, 3)) == 15
    # affine adds the 8-byte scale/zero header
    assert AffineCodec(8).payload_bytes((10, 3)) == 38
    assert AffineCodec(16).payload_bytes((10, 3)) == 68
    assert AffineCodec(4).payload_bytes((7,)) == 12


def test_int4_pack_unpack_roundtrip_odd_length():
    grid = uniform_grid(4, 0.0, 1.0)
    codec = GridCodec(grid)
    x = jnp.linspace(0.0, 1.0, 11)  # odd length exercises the pad path
    payload = codec.encode(x)
    assert payload.codes.shape == (6,)  # ceil(11/2) packed bytes
    dec = codec.decode(payload, shape=x.shape)
    assert float(jnp.max(jnp.abs(dec - x))) <= grid.step / 2 + 1e-6


def test_codec_factories():
    assert isinstance(codec_for_bits(32), Fp32Codec)
    assert isinstance(codec_for_bits(8), AffineCodec)
    assert isinstance(codec_for_bits(8, -1.0, 1.0), GridCodec)
    assert isinstance(codec_for_grid(None), Fp32Codec)
    g = uniform_grid(8, 0, 1)
    assert codec_for_grid(g).grid is g


def test_error_feedback_unbiased_over_rounds():
    """Carried residual keeps the cumulative transmitted mean within one
    round's quantization error of the true mean (no accumulating bias)."""
    codec = GridCodec(uniform_grid(4, -1.0, 1.0))
    x = jax.random.normal(jax.random.PRNGKey(3), (256,)) * 0.3
    err = jnp.zeros_like(x)
    sent_sum = jnp.zeros_like(x)
    one_round = None
    for k in range(32):
        _, sent, err = encode_with_error_feedback(codec, x, err)
        sent_sum = sent_sum + sent
        if k == 0:
            one_round = float(jnp.max(jnp.abs(sent - x)))
    drift = float(jnp.max(jnp.abs(sent_sum / 32 - x)))
    assert drift <= one_round + 1e-6
    # and plain (no-feedback) repetition really is worse on this input
    plain = codec.decode(codec.encode(x), shape=x.shape)
    assert drift <= float(jnp.max(jnp.abs(plain - x))) + 1e-6


# --- controller ------------------------------------------------------------

def _controller(n_edges=3, elements=1000, **cfg_kw):
    cfg = ControllerConfig(**cfg_kw)
    return BitWidthController([elements] * n_edges, cfg), cfg


def test_controller_respects_min_max_bits():
    ctl, cfg = _controller(min_bits=4, max_bits=16, min_dwell=0)
    for it, r in enumerate([1.0, 1.0, 0.5, 0.2, 0.01, 1e-6, 0.0]):
        bits = ctl.assign([r] * 3, it)
        assert all(cfg.min_bits <= b <= cfg.max_bits for b in bits)
        assert all(b in cfg.allowed_bits for b in bits)
    # fully converged residual -> everyone graduates to max bits
    assert set(ctl.schedule) == {16}


def test_controller_starts_coarse_and_graduates():
    ctl, _ = _controller(min_dwell=0)
    assert set(ctl.schedule) == {4}
    ctl.assign([1.0, 1.0, 1.0], 0)           # at peak -> coarse
    assert set(ctl.schedule) == {4}
    ctl.assign([0.01, 0.01, 0.01], 1)        # contracted -> fine
    assert set(ctl.schedule) == {16}


def test_controller_respects_byte_budget():
    epochs, elements, n_edges = 20, 1000, 3
    budget = epochs * n_edges * elements        # == flat 8-bit spend
    ctl, _ = _controller(n_edges=n_edges, elements=elements, min_dwell=0,
                         byte_budget=budget, total_iters=epochs)
    residuals = [1.0] * n_edges
    for it in range(epochs):
        ctl.assign(residuals, it)
        residuals = [r * 0.5 for r in residuals]  # fast convergence: wants 16
    assert ctl.spent_bytes <= budget + 1e-6


def test_controller_budget_requires_total_iters():
    with pytest.raises(ValueError):
        BitWidthController([100], ControllerConfig(byte_budget=1000.0))


def test_controller_hysteresis_bounds_switches():
    """A residual oscillating around a threshold must not thrash schedules:
    dwell + hysteresis keep the number of switches far below one-per-iter."""
    ctl, _ = _controller(n_edges=1, min_dwell=3, hysteresis=0.2)
    ctl.assign([1.0], 0)  # set the peak
    thr = 0.30            # the 4<->8 threshold
    for it in range(1, 60):
        wiggle = thr * (1.05 if it % 2 else 0.95)  # +/-5% around threshold
        ctl.assign([wiggle], it)
    assert ctl.n_switches <= 2


def test_controller_dwell_time():
    ctl, _ = _controller(n_edges=1, min_dwell=5, hysteresis=0.0)
    ctl.assign([1.0], 0)
    ctl.assign([0.001], 1)   # wants 16, but switched at init? no: first real
    b1 = ctl.schedule[0]
    ctl.assign([1.0], 2)     # wants 4 again — must be held by dwell
    assert ctl.schedule[0] == b1


# --- ledger ----------------------------------------------------------------

def test_ledger_totals_match_hand_computed():
    led = CommLedger()
    g8 = GridCodec(uniform_grid(8, 0, 1))
    led.record_payload(0, "q_fwd/l0", "ppermute", g8, (100, 50))     # 5000
    led.record_payload(0, "u_fwd/l0", "ppermute", FP32, (100, 50))   # 20000
    led.record_payload(0, "x", "psum", AffineCodec(8), (10,))        # 18
    led.record_handshake(0, "x")                                     # 8
    assert led.total_bytes() == 5000 + 20000 + 18 + 8
    assert led.iteration_bytes(0) == led.total_bytes()
    # fp32 baseline: same elements at 4 B, handshake not charged
    assert led.baseline_fp32_bytes() == 4 * (5000 + 5000 + 10)
    assert led.per_edge()["q_fwd/l0"] == 5000


def test_ledger_record_admm_iteration_matches_formula():
    """Ledger totals == the closed-form Fig-5 model for the fixed case:
    per boundary, q fwd (1 B/el at 8 bit) + u fwd (4 B/el fp32) + p bwd
    (1 B/el), V*50 elements each, 3 boundaries, 3 iterations."""
    from repro.core.quantize import uniform_grid as ug
    dims, V = [100, 50, 50, 50, 7], 1000
    g8 = ug(8, 0, 1)
    led = CommLedger()
    for it in range(3):
        record_admm_iteration(led, it, dims, V, GridCodec(g8), GridCodec(g8))
    expect = 3 * 3 * V * 50 * (1 + 4 + 1)
    assert led.total_bytes() == expect
    assert abs(led.savings_vs_fp32() - 0.5) < 1e-9


def test_ledger_per_iteration_rollup():
    led = CommLedger()
    for it in range(4):
        led.record(it, "e", "ppermute", 100, 8)
    assert led.per_iteration() == {0: 100, 1: 100, 2: 100, 3: 100}
    assert led.summary()["bytes_per_iteration"] == 100.0


def test_ledger_record_span_matches_per_iteration_records():
    """The chunked-driver rollup == n individual records, iteration by
    iteration (same totals, same per-iteration map, same edge rollups)."""
    a, b = CommLedger(), CommLedger()
    a.record_span(3, 5, "q_fwd", "ppermute", 200, 8, 220)
    for i in range(5):
        b.record(3 + i, "q_fwd", "ppermute", 200, 8, 220)
    assert a.per_iteration() == b.per_iteration() == {
        3 + i: 220 for i in range(5)}
    assert a.per_edge() == b.per_edge()
    assert a.total_bytes() == b.total_bytes()
    assert a.baseline_fp32_bytes() == b.baseline_fp32_bytes()
    # default byte computation (no explicit payload_bytes) matches too
    a.record_span(0, 2, "x", "psum", 10, 4)
    assert a.iteration_bytes(0) == 5  # ceil(10 * 4 / 8)


# --- adaptive training loop (single-host wire model) -----------------------

def test_train_adaptive_legacy_pq_layout():
    """Controller over only the p/q edges: u stays fp32 and the ledger total
    is exactly controller-managed bytes + the fp32 u traffic."""
    from repro.comm.controller import train_adaptive
    from repro.core import pdadmm
    from repro.core.pdadmm import ADMMConfig
    from repro.graph.datasets import tiny
    ds = tiny()
    X = ds.augmented(4)
    dims = [X.shape[1], 32, 32, ds.n_classes]
    key = jax.random.PRNGKey(0)
    epochs = 12
    V = X.shape[0]
    grids = {b: pdadmm.calibrate_grid(key, X, dims, b) for b in (4, 8, 16)}
    edges = [2 * V * dims[l + 1] for l in range(len(dims) - 2)]
    budget = sum(edges) * epochs            # == flat 8-bit managed bytes
    ctl = BitWidthController(edges, ControllerConfig(
        byte_budget=budget, total_iters=epochs))
    led = CommLedger()
    _, hist = train_adaptive(key, X, ds.labels, ds.masks, dims,
                             ADMMConfig(nu=1e-2, rho=1.0), epochs,
                             controller=ctl, ledger=led, grids_by_bits=grids)
    assert len(hist["schedules"]) == epochs
    assert all(b in (4, 8, 16) for sched in hist["schedules"] for b in sched)
    assert ctl.spent_bytes <= budget + 1e-6
    # ledger == controller-managed p/q bytes + the fp32 u traffic
    u_bytes = epochs * sum(4 * V * dims[l + 1]
                           for l in range(len(dims) - 2))
    assert led.total_bytes() == int(ctl.spent_bytes) + u_bytes
    # adaptive must at least match the flat-8-bit saving (u fp32): >= 45%
    assert led.savings_vs_fp32() >= 0.45
    assert hist["test_acc"][-1] > 0.5


def test_train_adaptive_managed_u_beats_fixed8_savings():
    """Full admm_edges layout (p/q + u managed): strictly more saving than
    the fixed-8-bit case (50% incl. fp32 u) under the 75%-of-fixed-8 budget,
    with all bit-widths at the accuracy-safe >= 8 floor."""
    from repro.comm.controller import admm_edges, train_adaptive
    from repro.core import pdadmm
    from repro.core.pdadmm import ADMMConfig
    from repro.graph.datasets import tiny
    ds = tiny()
    X = ds.augmented(4)
    dims = [X.shape[1], 32, 32, ds.n_classes]
    key = jax.random.PRNGKey(0)
    epochs = 12
    V = X.shape[0]
    grids = {b: pdadmm.calibrate_grid(key, X, dims, b) for b in (8, 16)}
    n_bound = len(dims) - 2
    edges = admm_edges(dims, V)
    assert len(edges) == 2 * n_bound
    fixed8_total = epochs * sum(6 * V * dims[l + 1] for l in range(n_bound))
    ctl = BitWidthController(edges, ControllerConfig(
        allowed_bits=(8, 16), min_bits=8, max_bits=16,
        byte_budget=0.75 * fixed8_total, total_iters=epochs))
    led = CommLedger()
    _, hist = train_adaptive(key, X, ds.labels, ds.masks, dims,
                             ADMMConfig(nu=1e-2, rho=1.0), epochs,
                             controller=ctl, ledger=led, grids_by_bits=grids)
    assert all(len(s) == 2 * n_bound and all(b in (8, 16) for b in s)
               for s in hist["schedules"])
    # strictly better than the fixed-8-bit total (= 50% of fp32)
    assert led.total_bytes() < 0.5 * led.baseline_fp32_bytes()
    assert led.savings_vs_fp32() > 0.5
    assert hist["test_acc"][-1] > 0.5


# --- axis size inside shard_map -------------------------------------------


def test_axis_size_inside_shard_map():
    """`jax.lax.axis_size` must return the static int under a shard_map
    trace (NeighborExchange builds its ppermute ring from it)."""
    out = _run(PRELUDE + """
from jax import shard_map
from jax.sharding import PartitionSpec as P

sizes = []
def f(x):
    n = jax.lax.axis_size("model")
    assert type(n) is int, type(n)
    sizes.append(n)
    return x * n
sm = shard_map(f, mesh=mesh, in_specs=(P("model"),), out_specs=P("model"),
               check_vma=False)
y = sm(jnp.ones((8, 2)))
assert sizes and all(n == 4 for n in sizes), sizes
assert np.allclose(np.asarray(y), 4.0)
print("AXIS_SIZE_OK")
""")
    assert "AXIS_SIZE_OK" in out


# --- distributed transport (multi-device subprocess) ------------------------

def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
"""


def test_transport_psum_and_error_feedback_unbiased():
    """transport.quantized_psum stays within one rounding of the exact psum,
    and the error-feedback variant keeps `quantized_psum` unbiased over
    repeated calls (drift bounded by a single round's error)."""
    out = _run(PRELUDE + """
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.comm.codecs import AffineCodec, GridCodec
from repro.comm import transport
from repro.core.quantize import uniform_grid

codec = AffineCodec(8)
def f(x, e):
    s = transport.quantized_psum(x, "data", codec)
    s2, ne = transport.psum_with_error_feedback(x, e, "data", codec)
    return s, s2, ne

sm = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
               out_specs=(P("data"), P("data"), P("data")), check_vma=False)
x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
exact = x.reshape(2, 4, 32).sum(0)
e = jnp.zeros_like(x)
s, s2, ne = sm(x, e)
err0 = np.abs(np.asarray(s).reshape(2, 4, 32)[0] - np.asarray(exact)).max()
assert err0 < 0.1, err0
tot = np.zeros((4, 32)); e = jnp.zeros_like(x)
for i in range(20):
    _, s2, e = sm(x, e)
    tot += np.asarray(s2).reshape(2, 4, 32)[0]
drift = np.abs(tot / 20 - np.asarray(exact)).max()
assert drift < err0 + 1e-6, (drift, err0)
print("TRANSPORT_EF_OK")
""")
    assert "TRANSPORT_EF_OK" in out


def test_neighbor_exchange_int4_wire():
    """int4 nibble-packed boundary exchange round-trips through ppermute
    (payload physically half the int8 size) and matches the ring shift."""
    out = _run(PRELUDE + """
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.comm.codecs import GridCodec
from repro.comm.transport import NeighborExchange
from repro.core.quantize import uniform_grid

grid = uniform_grid(4, 0.0, 1.0)
ex = NeighborExchange("model", GridCodec(grid))
def f(x):
    return ex.shift_from_prev(x)
sm = shard_map(f, mesh=mesh, in_specs=(P("model"),), out_specs=P("model"),
               check_vma=False)
x = jax.random.uniform(jax.random.PRNGKey(0), (8, 16, 4))
out = sm(x)
# global semantics: out[i] = project(x[i-1]) at stage boundaries (stage size
# 2: within-stage rows are exact copies, boundary rows are grid-projected)
x_np = np.asarray(x); o = np.asarray(out)
shifted = np.roll(x_np, 1, axis=0)
# within-stage (odd global rows): exact
assert np.abs(o[1::2] - shifted[1::2]).max() < 1e-6
# boundary rows: on the grid, within half a step
bnd = o[0::2]
assert np.abs(bnd - np.asarray(grid.project(jnp.asarray(bnd)))).max() < 1e-6
assert np.abs(bnd - shifted[0::2]).max() <= grid.step / 2 + 1e-6
assert ex.wire_bytes((1, 16, 4)) == 32   # 64 int4 elements -> 32 bytes
print("INT4_WIRE_OK")
""")
    assert "INT4_WIRE_OK" in out
