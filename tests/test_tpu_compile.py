"""Compile every main-path Pallas kernel for a described TPU v5e chip at
pubmed's full Table-II widths (V = 19,717 nodes, h = 1000, L = 8 layers).

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot — block shapes the chip's layout rules refuse, and kernels that ask
for more VMEM than the chip has. Every case goes through the ``ops``
dispatch with ``use_pallas=True, interpret=False``, so the pad-to-tile plans
are compiled too, and asserts the kernel reached the compiled program.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every pytest worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import integer_grid, uniform_grid
from repro.kernels import ops

V, H, L, C = 19717, 1000, 8, 3          # pubmed, the paper's width, depth
PALLAS = dict(use_pallas=True, interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _slab_bytes(bits: int) -> int:
    """Packed container bytes of one [V, h] boundary slab."""
    n = V * H
    return (n + 1) // 2 if bits <= 4 else 2 * n


# name -> (fn, [(shape, dtype), ...])
f32, i32, u8, u16 = jnp.float32, jnp.int32, jnp.uint8, jnp.uint16
CASES = {
    "fused_linear_linear": (
        lambda p, W, b: ops.fused_linear(p, W, b, mode="linear", **PALLAS),
        [((V, H), f32), ((H, H), f32), ((H,), f32)]),
    "fused_linear_residual": (
        lambda p, W, b, z: ops.fused_linear(p, W, b, z, mode="residual",
                                            **PALLAS),
        [((V, H), f32), ((H, H), f32), ((H,), f32), ((V, H), f32)]),
    "admm_pgrad": (
        lambda r, W, u, p, q: ops.admm_pgrad(r, W, u, p, q, nu=1e-2, rho=1.0,
                                             **PALLAS),
        [((V, H), f32), ((H, H), f32)] + [((V, H), f32)] * 3),
    "backtrack_resnorm": (
        lambda r0, d, W: ops.backtrack_resnorm(r0, d, W, **PALLAS),
        [((V, H), f32), ((V, H), f32), ((H, H), f32)]),
    "relu_zupdate_rows": (
        lambda a, q, z: ops.relu_zupdate(a, q, z, **PALLAS),
        [((L * V, H), f32)] * 3),
    "relu_zupdate_stack": (
        lambda a, q, z: ops.relu_zupdate(a, q, z, **PALLAS),
        [((L, V, H), f32)] * 3),
    "fista_zlast": (
        lambda a, z, lab, m: ops.fista_zlast(a, z, lab, m, nu=1e-2,
                                             n_iters=15, n_classes=C,
                                             **PALLAS),
        [((L * V, H), f32), ((L * V, H), f32), ((L * V,), i32),
         ((L * V,), f32)]),
    "pack_codes_4": (
        lambda c: ops.pack_codes(c, 4, **PALLAS), [((V * H,), u8)]),
    "unpack_codes_4": (
        lambda b: ops.unpack_codes(b, 4, V * H, **PALLAS),
        [((_slab_bytes(4),), u8)]),
    "pack_codes_16": (
        lambda c: ops.pack_codes(c, 16, **PALLAS), [((V * H,), u16)]),
    "unpack_codes_16": (
        lambda b: ops.unpack_codes(b, 16, V * H, **PALLAS),
        [((_slab_bytes(16),), u8)]),
}
for _grid in (integer_grid(), uniform_grid(16, -4.0, 4.0)):
    CASES[f"grid_project_{_grid.bits}"] = (
        lambda x, g=_grid: ops.grid_project(x, g, **PALLAS), [((V, H), f32)])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
