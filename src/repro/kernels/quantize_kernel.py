"""Pallas TPU kernel: fused grid projection for pdADMM-G-Q.

Elementwise, VPU-bound — the value of the kernel is fusing the scale,
round, clip and rescale of the projection into ONE pass over the tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _project_kernel(x_ref, o_ref, *, lo, step, n_levels):
    x = x_ref[...].astype(jnp.float32)
    ix = jnp.clip(jnp.round((x - lo) / step), 0, n_levels - 1)
    o_ref[...] = (lo + ix * step).astype(o_ref.dtype)


def grid_project(x, grid, *, bm: int = 512, bn: int = 1024,
                 interpret: bool = False):
    kernel = functools.partial(_project_kernel, lo=grid.lo, step=grid.step,
                               n_levels=grid.n_levels)
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    M, N = x2.shape
    bm_, bn_ = min(bm, M), min(bn, N)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(M, bm_), pl.cdiv(N, bn_)),     # ragged edge: masked
        in_specs=[pl.BlockSpec((bm_, bn_), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x2)
    return out.reshape(orig_shape)
