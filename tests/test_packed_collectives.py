"""Physical quantized collectives battery: pack/unpack roundtrip property
sweep (all widths x ragged counts x both kernel policies), the gather-based
packed all-reduce vs the int32 code-psum (bit-identical values, honest
physical byte accounting at world 2/4/8), error feedback against the decoded
packed payload (1k seeded rounds), and the padded-container mixed-width
boundary exchange (per-boundary widths in ONE compiled step, bitwise vs the
static-codec step, incl. under overlap). Multi-device cases run in
subprocesses with forced CPU devices (the main pytest process is locked to
1 device)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommLedger
from repro.comm.codecs import (FP32, AffineCodec, GridCodec, _body_bytes,
                               pack_codes_jnp, unpack_codes_jnp)
from repro.comm.transport import (PaddedWire, psum_mode, psum_wire_bytes,
                                  record_psum)
from repro.core.quantize import uniform_grid
from repro.kernels import ops

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
"""


# --- pack/unpack roundtrip property battery ---------------------------------

POLICIES = [{"use_pallas": False},                      # jnp oracle
            {"use_pallas": True, "interpret": True}]    # Pallas kernel


@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("n", [0, 1, 2, 7, 17, 128, 1000, 2485, 3327, 20001])
def test_pack_unpack_roundtrip_all_policies(bits, n):
    """Roundtrip identity + exact container size for every width, odd and
    ragged element counts, on both the jnp oracle and the Pallas kernel —
    and the two policies produce the IDENTICAL byte stream (the wire layout
    is a contract, not an implementation detail)."""
    rng = np.random.default_rng(bits * 10007 + n)
    dtype = jnp.uint8 if bits <= 8 else jnp.uint16
    codes = jnp.asarray(rng.integers(0, 2 ** bits, n), dtype)
    streams = []
    for kw in POLICIES:
        packed = ops.pack_codes(codes, bits, **kw)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (_body_bytes(bits, n),)
        out = ops.unpack_codes(packed, bits, n, **kw)
        assert out.dtype == dtype
        np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))
        streams.append(np.asarray(packed))
    np.testing.assert_array_equal(streams[0], streams[1])
    # cross-policy: oracle-packed bytes unpack on the kernel and vice versa
    for a, b in ((POLICIES[0], POLICIES[1]), (POLICIES[1], POLICIES[0])):
        out = ops.unpack_codes(ops.pack_codes(codes, bits, **a), bits, n,
                               **b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))


def test_pack_layout_matches_codecs_contract():
    """ops-level packing IS `codecs.pack_codes_jnp` byte for byte (the
    GridCodec/AffineCodec int4 wire shares the layout)."""
    rng = np.random.default_rng(3)
    for bits, n in [(4, 11), (8, 13), (16, 9)]:
        dtype = jnp.uint8 if bits <= 8 else jnp.uint16
        codes = jnp.asarray(rng.integers(0, 2 ** bits, n), dtype)
        np.testing.assert_array_equal(
            np.asarray(ops.pack_codes(codes, bits, use_pallas=True,
                                      interpret=True)),
            np.asarray(pack_codes_jnp(codes, bits)))
        np.testing.assert_array_equal(
            np.asarray(unpack_codes_jnp(pack_codes_jnp(codes, bits), bits,
                                        n)),
            np.asarray(codes))


# --- psum cost model + honest physical accounting (satellite 1) -------------

def test_psum_mode_break_even():
    """gather iff world * bits < 64; fp32 never compresses."""
    g4 = GridCodec(uniform_grid(4, 0, 1))
    assert [psum_mode(g4, w) for w in (2, 4, 8, 15, 16)] == \
        ["gather"] * 4 + ["code_psum"]
    a8 = AffineCodec(8)
    assert [psum_mode(a8, w) for w in (2, 4, 7, 8)] == \
        ["gather"] * 3 + ["code_psum"]
    assert psum_mode(AffineCodec(16), 4) == "code_psum"
    assert psum_mode(FP32, 2) == "psum"


@pytest.mark.parametrize("world", [2, 4, 8])
def test_psum_ledger_totals_match_selected_path(world):
    """Regression for the silent int32 undercount: the ledger's PHYSICAL
    bytes follow whichever collective the cost model selects — packed
    container on the gather path, 4 B/element int32 on the code-psum path —
    for int4/int8/fp32 at world 2/4/8, while the logical codec bytes stay a
    separate field."""
    shape = (100, 3)
    n = 300
    cases = {
        "int4": GridCodec(uniform_grid(4, 0, 1)),
        "int8": AffineCodec(8),
        "fp32": FP32,
    }
    for name, codec in cases.items():
        cost = psum_wire_bytes(codec, shape, world)
        led = CommLedger()
        record_psum(led, 0, "g", codec, shape, world)
        if name == "fp32":
            assert cost.mode == "psum"
            assert cost.wire_bytes == cost.logical_bytes == 4 * n
        elif cost.mode == "gather":
            assert world * codec.bits < 64
            assert cost.wire_bytes == _body_bytes(codec.bits, n)
        else:
            assert world * codec.bits >= 64
            assert cost.wire_bytes == 4 * n       # int32 on the wire
            assert cost.logical_bytes < cost.wire_bytes
        handshake = 8 if isinstance(codec, AffineCodec) else 0
        assert led.total_wire_bytes() == cost.wire_bytes + handshake
        assert led.total_bytes() == cost.logical_bytes + handshake
    # the headline: int4 gather ships < 1/4 of what the int32 code-sum ships
    led_g, led_c = CommLedger(), CommLedger()
    record_psum(led_g, 0, "g", cases["int4"], shape, world)
    record_psum(led_c, 0, "g", cases["int4"], shape, world, mode="code_psum")
    assert led_g.total_wire_bytes() < 0.25 * led_c.total_wire_bytes()


def test_psum_mode_override_validated():
    """An explicit mode must be one of the documented vocabulary — a typo
    must not silently fall through to the quantizing code-psum — and
    mode="psum" means the UNCOMPRESSED collective in the accounting too."""
    with pytest.raises(ValueError):
        psum_wire_bytes(AffineCodec(8), (4,), 2, mode="Gather")
    cost = psum_wire_bytes(AffineCodec(8), (4,), 2, mode="psum")
    assert cost.mode == "psum"
    assert cost.wire_bytes == cost.logical_bytes == 16
    assert cost.handshake_bytes == 0


def test_old_accounting_was_dishonest_for_code_psum():
    """The pre-fix behavior (logical bytes reported as THE bytes) and the
    physical truth now disagree exactly where they should: an int8 code-psum
    at world 8 ships int32."""
    cost = psum_wire_bytes(AffineCodec(8), (64,), 8)
    assert cost.mode == "code_psum"
    assert cost.logical_bytes == 64 and cost.wire_bytes == 256


# --- gather vs code_psum equivalence (f64) + EF bias (satellite 2) ----------

def test_gather_equals_code_psum_bitwise_f64():
    """The two physical collectives decode to BIT-IDENTICAL values in f64
    (integer code-sums are exact whichever fabric carries them) — grid and
    affine codecs, world sizes 2/4/8."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_ENABLE_X64"] = "1"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.comm import transport
from repro.comm.codecs import AffineCodec, GridCodec
from repro.core.quantize import uniform_grid

for w in (2, 4, 8):
    mesh = make_mesh((w,), ("data",), devices=jax.devices()[:w])
    for codec in (GridCodec(uniform_grid(4, -3.0, 3.0)),
                  GridCodec(uniform_grid(8, -3.0, 3.0)),
                  AffineCodec(8), AffineCodec(16)):
        def f(x):
            return (transport.quantized_psum(x, "data", codec,
                                             mode="gather"),
                    transport.quantized_psum(x, "data", codec,
                                             mode="code_psum"))
        sm = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                       out_specs=(P("data"), P("data")), check_vma=False)
        x = jax.random.normal(jax.random.PRNGKey(0), (w * 3, 17),
                              jnp.float64)
        a, b = sm(x)
        # affine codecs carry the f64 handshake scale through the decode;
        # grid codecs decode on the static python-float grid (weak f32) —
        # identically on BOTH paths, which is what the differential locks
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        if isinstance(codec, AffineCodec):
            assert a.dtype == jnp.float64, a.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{w}/{codec.name}")
print("F64_EQUIV_OK")
""")
    assert "F64_EQUIV_OK" in out


def test_error_feedback_unbiased_on_gather_path_1k_rounds():
    """Satellite bugfix lock: `psum_with_error_feedback` computes its
    residual against the DECODED PACKED payload, so 1000 stochastic rounds
    on the gather path keep the cumulative mean within one round's
    quantization error of the exact psum — for several seeds."""
    out = _run(PRELUDE + """
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.comm import transport
from repro.comm.codecs import AffineCodec

codec = AffineCodec(4)        # coarse wire makes any bias glaring
mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])

def rounds(x, keys):
    def one(e, key):
        s, e = transport.psum_with_error_feedback(
            x, e, "data", codec, key=key[0], mode="gather")
        return e, s
    _, sums = jax.lax.scan(one, jnp.zeros_like(x), keys)
    return sums

sm = shard_map(rounds, mesh=mesh, in_specs=(P("data"), P(None, "data")),
               out_specs=P(None, "data"), check_vma=False)

for seed in (0, 1, 2):
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (4, 64)) * 2.0
    keys = jax.random.split(jax.random.PRNGKey(seed), 2000).reshape(
        1000, 2, 2)
    sums = np.asarray(sm(x, keys))           # [1000, 4, 64]
    exact = np.asarray(x.reshape(2, 2, 64).sum(0))
    got = sums.reshape(1000, 2, 2, 64)[:, 0]
    one_round = np.abs(got[0] - exact).max()
    drift = np.abs(got.mean(0) - exact).max()
    assert drift <= one_round + 1e-6, (seed, drift, one_round)
    # and the mean is genuinely tighter than any single round (the 1k
    # stochastic rounds average out: EF + unbiased rounding at work)
    assert drift < 0.5 * one_round, (seed, drift, one_round)
    print("seed", seed, "drift", drift, "one_round", one_round)
print("EF_1K_OK")
""")
    assert "EF_1K_OK" in out


# --- padded containers: mixed per-boundary widths in one step ---------------

def test_padded_wire_capacity_and_logical_bytes():
    wire = PaddedWire.from_grids(
        {b: uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)})
    assert wire.widths == (4, 8, 16) and wire.widest == 16
    assert wire.capacity((1, 37, 5)) == 2 * 37 * 5
    assert wire.payload_bytes((1, 37, 5), 4) == (37 * 5 + 1) // 2
    assert wire.payload_bytes((1, 37, 5), 8) == 37 * 5
    assert list(np.asarray(wire.sel_of_bits([8, 16, 4]))) == [1, 2, 0]
    with pytest.raises(ValueError):
        wire.sel_of_bits([12])


def test_padded_wire_roundtrip_matches_static_codec():
    """Inside jit (the only place the wire runs), container encode/decode at
    each active width equals the static GridCodec roundtrip bit for bit."""
    grids = {b: uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
    wire = PaddedWire.from_grids(grids)
    x = jax.random.uniform(jax.random.PRNGKey(0), (1, 37, 5), jnp.float32,
                           -2.0, 6.0)

    @jax.jit
    def via_wire(x, sel):
        return wire.decode(wire.encode(x, sel), sel, x.shape, x.dtype)

    for i, b in enumerate(wire.widths):
        codec = GridCodec(grids[b])

        @jax.jit
        def via_codec(x, codec=codec):
            return codec.decode(codec.encode(x), shape=x.shape)

        np.testing.assert_array_equal(
            np.asarray(via_wire(x, jnp.int32(i))),
            np.asarray(via_codec(x)), err_msg=str(b))


def test_container_step_uniform_width_matches_static_step():
    """A container step driven at a UNIFORM width table is bitwise the
    static-codec step at that width — for every width in the table — and
    different width VALUES reuse the one compilation."""
    out = _run(PRELUDE + """
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.comm.codecs import GridCodec
from repro.comm.transport import PaddedWire
from repro.parallel import stage_parallel as SP

mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
V, h, L, C = 64, 32, 4, 4
grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
wire = PaddedWire.from_grids(grids)
cfg = ADMMConfig(nu=1e-2, rho=1.0)
key = jax.random.PRNGKey(1)
Xp = jax.random.normal(key, (V, h))
state0 = SP.init_stack(key, Xp, L, cfg)
specs = SP.stack_partition_specs(mesh)
put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
state0 = jax.tree.map(put, state0, specs)
args = (put(Xp, P("data")), put(jnp.zeros((V,), jnp.int32), P("data")),
        put(jnp.ones((V,)), P("data")))
cstep, _ = SP.make_distributed_step(mesh, L, C, cfg, wire=wire)
for i, b in enumerate(wire.widths):
    sstep, _ = SP.make_distributed_step(mesh, L, C, cfg,
                                        p_codec=GridCodec(grids[b]),
                                        q_codec=GridCodec(grids[b]))
    widths = jnp.full((2, 2), i, jnp.int32)
    st_c, st_s = state0, state0
    for k in range(3):
        st_c, m_c = cstep(st_c, *args, widths)
        st_s, m_s = sstep(st_s, *args)
        for f, a, bb in zip(st_c._fields, st_c, st_s):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb),
                                          err_msg=f"{b}/iter{k}/{f}")
        for kk in m_c:
            np.testing.assert_array_equal(np.asarray(m_c[kk]),
                                          np.asarray(m_s[kk]),
                                          err_msg=f"{b}/{kk}")
# a schedule change is a VALUE change, not a new specialization
if hasattr(cstep, "_cache_size"):
    assert cstep._cache_size() == 1, cstep._cache_size()
# trace-level wire check (library walker): the p/q boundary exchanges ship
# as uint8 containers whatever the active width, u stays fp32
from repro.analysis.jaxpr_tools import collective_profile
dts = sorted(p["dtype"] for p in collective_profile(
    jax.make_jaxpr(cstep)(state0, *args, jnp.zeros((2, 2), jnp.int32)).jaxpr))
assert dts == ["float32", "uint8", "uint8"], dts
print("UNIFORM_CONTAINER_OK")
""")
    assert "UNIFORM_CONTAINER_OK" in out


def test_mixed_width_distributed_train_one_compiled_step():
    """The acceptance path: distributed_train(mixed_width=True) runs
    genuinely per-boundary widths (the controller emits schedules where two
    stages differ) with EXACTLY one compiled step, overlap=True stays
    bitwise-identical across re-primed schedule changes (the carried slab
    is a container), and the ledger splits physical container bytes from
    the active codec's logical bytes."""
    out = _run(PRELUDE + """
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.comm import BitWidthController, CommLedger, ControllerConfig
from repro.comm.controller import stage_ring_edges
from repro.graph.datasets import tiny
from repro.parallel import stage_parallel as SP

mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
ds = tiny(V=64)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 32)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
V, h, L = Xp.shape[0], 32, 4
n_stages = 2
grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
mk_ctl = lambda: BitWidthController(
    stage_ring_edges(n_stages, V, h),
    ControllerConfig(allowed_bits=(4, 8, 16), min_bits=4, max_bits=16,
                     min_dwell=1, hysteresis=0.0, signal="per_edge",
                     thresholds=((0.5, 4), (0.1, 8))))
cfg = ADMMConfig(nu=1e-2, rho=1.0)
led_a, led_b = CommLedger(), CommLedger()
st_a, h_a = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, L,
                                 ds.n_classes, cfg, epochs=14,
                                 controller=mk_ctl(), grids_by_bits=grids,
                                 ledger=led_a, mixed_width=True)
st_b, h_b = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, L,
                                 ds.n_classes, cfg, epochs=14,
                                 controller=mk_ctl(), grids_by_bits=grids,
                                 ledger=led_b, overlap=True,
                                 mixed_width=True)
# ONE compiled step, schedule changes included
assert h_a["n_compiled_steps"] == 1, h_a["n_compiled_steps"]
assert h_b["n_compiled_steps"] == 1, h_b["n_compiled_steps"]
assert len(set(h_a["schedules"])) >= 2, h_a["schedules"]   # it DID adapt
# genuinely per-boundary: some schedule assigns two stages different widths
assert any(len(set(s)) > 1 for s in h_a["schedules"]), h_a["schedules"]
# overlap differential: bitwise state + identical history and schedules
assert h_a["schedules"] == h_b["schedules"]
assert h_a["objective"] == h_b["objective"]
assert h_a["residual"] == h_b["residual"]
for f, a, b in zip(st_a._fields, st_a, st_b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f)
# it trains
assert h_a["objective"][-1] < h_a["objective"][0]
# ledger: physical container bytes are schedule-independent, logical bytes
# follow the active widths; consumed overlap traffic matches exactly
wb = SP.container_wire_bytes_per_iteration(
    mesh, L, V, h, SP.PaddedWire.from_grids(grids), (8,) * n_stages,
    (8,) * n_stages)
per_edge_wire = led_a.per_edge_wire()
for i in range(n_stages):
    assert per_edge_wire[f"q_fwd/s{i}"] == 14 * wb["container_bytes"]
    assert per_edge_wire[f"p_bwd/s{i}"] == 14 * wb["container_bytes"]
assert led_a.total_bytes() < led_a.total_wire_bytes()  # narrow widths ran
consumed = {e: v for e, v in led_b.per_edge().items()
            if not (e.endswith("/inflight") or e.endswith("/dropped"))}
assert consumed == led_a.per_edge()
n_changes = sum(1 for x, y in zip(h_a["schedules"], h_a["schedules"][1:])
                if x[:n_stages] != y[:n_stages])
extra = {e for e in led_b.per_edge() if e.endswith("/inflight")
         or e.endswith("/dropped")}
expect = {"q_fwd/inflight", "u_fwd/inflight"}
if n_changes:
    expect |= {"q_fwd/dropped", "u_fwd/dropped"}
assert extra == expect, (extra, n_changes)
print("MIXED_WIDTH_TRAIN_OK")
""")
    assert "MIXED_WIDTH_TRAIN_OK" in out
