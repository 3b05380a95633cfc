"""Pallas TPU kernel: the pdADMM hot op z = p @ W + b and its residual form
r = z - (p @ W + b), with the elementwise epilogue fused into the matmul so
the intermediate never round-trips HBM.

Tiling: grid (M/bm, N/bn, K/bk), K innermost; f32 accumulator lives in a VMEM
scratch tile that is revisited across the K steps (standard MXU pattern,
128-aligned tiles). The bias rides as a (1, N) row so its (1, bn) block obeys
the TPU's 2-D block layout rules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(p_ref, w_ref, b_ref, z_ref, out_ref, acc_ref, *,
                   n_k: int, mode: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(p_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if mode == "linear":          # z = pW + b
            out_ref[...] = acc.astype(out_ref.dtype)
        else:                          # residual: r = z - (pW + b)
            out_ref[...] = (z_ref[...].astype(jnp.float32)
                            - acc).astype(out_ref.dtype)


def fused_linear(p, W, b, z=None, *, mode: str = "linear",
                 bm: int = 256, bk: int = 512, bn: int = 256,
                 interpret: bool = False):
    """mode='linear' -> p@W+b ; mode='residual' -> z - (p@W+b)."""
    M, K = p.shape
    K2, N = W.shape
    assert K == K2 and b.shape == (N,)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (p.shape, W.shape)
    n_k = K // bk
    if z is None:
        z = jnp.zeros((M, N), p.dtype)

    kernel = functools.partial(_matmul_kernel, n_k=n_k, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), p.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(p, W, b.reshape(1, N), z)
