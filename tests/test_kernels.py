"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref.py, and
the pad-to-tile dispatch regression (ragged shapes must take the Pallas
path, asserted at the trace level — not just by value)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import integer_grid, uniform_grid
from repro.kernels import ops, ref
from repro.kernels.admm_pgrad import admm_pgrad
from repro.kernels.backtrack_phi import backtrack_resnorm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_linear import fused_linear
from repro.kernels.quantize_kernel import grid_project
from repro.kernels.relu_zupdate import relu_zupdate


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 512, 256),
                                   (512, 384, 128), (64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["linear", "residual"])
def test_fused_linear(M, K, N, dtype, mode):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p, W = _rand(ks[0], (M, K), dtype), _rand(ks[1], (K, N), dtype)
    b, z = _rand(ks[2], (N,), dtype), _rand(ks[3], (M, N), dtype)
    got = fused_linear(p, W, b, z, mode=mode, bm=128, bk=128, bn=128,
                       interpret=True)
    want = ref.fused_linear_ref(p, W, b, z, mode=mode)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("V,ni,no", [(128, 128, 128), (256, 256, 512),
                                     (512, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_admm_pgrad(V, ni, no, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = _rand(ks[0], (V, no), dtype)
    W = _rand(ks[1], (ni, no), dtype)
    u, p, q = (_rand(k, (V, ni), dtype) for k in ks[2:])
    got = admm_pgrad(r, W, u, p, q, nu=0.01, rho=1.0, bm=128, bk=128, bn=128,
                     interpret=True)
    want = ref.admm_pgrad_ref(r, W, u, p, q, nu=0.01, rho=1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 512, 256),
                                   (512, 384, 128), (64, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backtrack_resnorm(M, K, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    r0 = _rand(ks[0], (M, N), dtype)
    d = _rand(ks[1], (M, K), dtype) * 0.1
    W = _rand(ks[2], (K, N), dtype)
    got = backtrack_resnorm(r0, d, W, bm=128, bk=128, bn=128, interpret=True)
    want = ref.backtrack_resnorm_ref(r0, d, W)
    np.testing.assert_allclose(float(got), float(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("shape", [(128, 256), (64, 100), (512, 1024), (7, 13)])
@pytest.mark.parametrize("grid", [integer_grid(), uniform_grid(8, -2.0, 6.0),
                                  uniform_grid(16, -4.0, 4.0)])
def test_quantize_kernels(shape, grid):
    x = jax.random.normal(jax.random.PRNGKey(2), shape) * 3.0

    def assert_tie_tolerant(got, want, scale):
        # kernel and oracle may disagree by ONE grid step at exact
        # round-half ties ((x-lo)/step one ULP apart under different op
        # fusion); anywhere else they must match to float tolerance
        diff = np.abs(np.asarray(got, np.float64)
                      - np.asarray(want, np.float64))
        assert diff.max() <= scale + 1e-6
        assert (diff > 1e-6).sum() <= max(1, 1e-4 * diff.size)

    assert_tie_tolerant(grid_project(x, grid, interpret=True),
                        ref.grid_project_ref(x, grid), grid.step)


@pytest.mark.parametrize("shape", [(256, 512), (128, 100), (512, 1000),
                                   (3, 300, 1100)])
def test_relu_zupdate(shape):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    a, q, z0 = (jax.random.normal(k, shape) for k in ks)
    got = relu_zupdate(a, q, z0, interpret=True)
    want = ref.relu_zupdate_ref(a, q, z0)

    # when branch objectives tie to f32 precision either branch is a valid
    # minimizer — compare OBJECTIVE values, not the argmin itself
    def obj(z):
        return (z - a) ** 2 + (q - jnp.maximum(z, 0)) ** 2 + (z - z0) ** 2
    np.testing.assert_allclose(np.asarray(obj(got)), np.asarray(obj(want)),
                               rtol=1e-4, atol=1e-4)
    # optimality: fused output never worse than either branch candidate
    zn = jnp.minimum((a + z0) / 2, 0)
    zp = jnp.maximum((a + q + z0) / 3, 0)
    assert bool(jnp.all(obj(got) <= obj(zn) + 1e-5))
    assert bool(jnp.all(obj(got) <= obj(zp) + 1e-5))
    # and matches ref on all non-tied elements
    tied = np.abs(np.asarray(obj(zn) - obj(zp))) < 1e-3
    np.testing.assert_allclose(np.asarray(got)[~tied], np.asarray(want)[~tied],
                               rtol=1e-5, atol=1e-5)


# --- pad-to-tile dispatch regression ----------------------------------------
#
# Ragged real-graph shapes (V = 2485, 2708, 3327, ...) used to
# fail the 128-tile divisibility guard and silently fall back to `ref`. The
# dispatch layer now zero-pads up to the kernel tile and slices back, so the
# Pallas path must fire — asserted by counting pallas_call primitives in the
# lowered trace, not just by value equality.

RAGGED = [(2485, 384, 6), (2708, 100, 7), (3327, 513, 129), (97, 130, 40)]


def _pallas_calls(fn, *args) -> int:
    from repro.analysis.jaxpr_tools import count_primitive
    return count_primitive(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")


def test_padded_shape_plans_tile():
    """Every pad plan lands on a kernel-tileable shape and is the identity
    on already-aligned dims."""
    for op, blocks in ops.PAD_BLOCKS.items():
        aligned = tuple(blk for blk, _ in blocks)
        assert ops.padded_shape(op, aligned) == aligned
        for dims in [(1,) * len(blocks), (2485, 513, 129)[:len(blocks)]]:
            padded = ops.padded_shape(op, dims)
            for n, pn, (blk, al) in zip(dims, padded, blocks):
                assert pn >= n and pn % min(blk, pn) == 0 and pn % al == 0


@pytest.mark.parametrize("M,K,N", RAGGED)
@pytest.mark.parametrize("mode", ["linear", "residual"])
def test_pad_to_tile_fused_linear(M, K, N, mode):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    p, W = _rand(ks[0], (M, K), jnp.float32), _rand(ks[1], (K, N), jnp.float32)
    W = W / np.sqrt(K)
    b, z = _rand(ks[2], (N,), jnp.float32), _rand(ks[3], (M, N), jnp.float32)
    run = lambda *a: ops.fused_linear(*a, mode=mode, interpret=True)
    assert _pallas_calls(run, p, W, b, z) == 1           # Pallas path fired
    assert _pallas_calls(
        lambda *a: ops.fused_linear(*a, mode=mode, use_pallas=False),
        p, W, b, z) == 0                                  # and ref has none
    np.testing.assert_allclose(
        np.asarray(run(p, W, b, z)),
        np.asarray(ref.fused_linear_ref(p, W, b, z, mode=mode)),
        **TOL[jnp.float32])


@pytest.mark.parametrize("M,K,N", RAGGED)
def test_pad_to_tile_backtrack_resnorm(M, K, N):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    r0 = _rand(ks[0], (M, N), jnp.float32)
    d = _rand(ks[1], (M, K), jnp.float32) * 0.1
    W = _rand(ks[2], (K, N), jnp.float32) / np.sqrt(K)
    run = lambda *a: ops.backtrack_resnorm(*a, interpret=True)
    assert _pallas_calls(run, r0, d, W) == 1
    assert _pallas_calls(
        lambda *a: ops.backtrack_resnorm(*a, use_pallas=False), r0, d, W) == 0
    np.testing.assert_allclose(float(run(r0, d, W)),
                               float(ref.backtrack_resnorm_ref(r0, d, W)),
                               rtol=1e-4)


@pytest.mark.parametrize("V,ni,no", [(2485, 96, 6), (97, 130, 40)])
def test_pad_to_tile_admm_pgrad(V, ni, no):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    r = _rand(ks[0], (V, no), jnp.float32)
    W = _rand(ks[1], (ni, no), jnp.float32) / np.sqrt(ni)
    u, p, q = (_rand(k, (V, ni), jnp.float32) for k in ks[2:])
    run = lambda *a: ops.admm_pgrad(*a, nu=0.01, rho=1.0, interpret=True)
    assert _pallas_calls(run, r, W, u, p, q) == 1
    np.testing.assert_allclose(
        np.asarray(run(r, W, u, p, q)),
        np.asarray(ref.admm_pgrad_ref(r, W, u, p, q, nu=0.01, rho=1.0)),
        **TOL[jnp.float32])


@pytest.mark.parametrize("B,H,S,T,D", [(1, 2, 128, 128, 64),
                                       (2, 1, 256, 256, 32),
                                       (1, 2, 64, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, H, S, T, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (B, H, S, D), dtype)
    k = _rand(ks[1], (B, H, T, D), dtype)
    v = _rand(ks[2], (B, H, T, D), dtype)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)
